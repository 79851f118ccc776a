"""Fault-injection harness tests: schedule determinism, injection
reality (an UNSUPERVISED fleet really delivers the injected garbage —
proving the faults hit the real path), malformed-request handling at
the edge, and retry/backoff determinism under a fake clock."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import FaultConfig, FleetConfig, SupervisorConfig
from repro.configs.registry import reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import init_npu
from repro.data.synthetic import make_scene_batch
from repro.serve.cognitive_engine import PerceptionRequest
from repro.serve.faults import (FaultEvent, FaultKind, FaultPlan,
                                make_malformed_request)
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import RequestStatus


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_snn("spiking_yolo")
    params = init_npu(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n, seed=0):
    scene = make_scene_batch(jax.random.PRNGKey(seed), batch=n,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps, n_events=2048)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    return [PerceptionRequest(rid=i, voxels=vox[:, i], bayer=scene.bayer[i])
            for i in range(n)]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _fleet(params, cfg, *, plan=None, sup=None, clk=None, batch=2,
           **kw):
    clk = clk if clk is not None else _FakeClock()
    return FleetEngine(
        params, cfg, fleet_cfg=FleetConfig(batch=batch, shard=False),
        supervisor_cfg=sup, fault_plan=plan, clock=clk,
        fault_advance=lambda s: setattr(clk, "t", clk.t + s), **kw), clk


# ---------------------------------------------------------------------------
# FaultPlan
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic():
    cfg = FaultConfig(seed=3, p_corrupt_input=0.1, p_nan_output=0.1,
                      p_transient=0.1, p_stall=0.05, p_malformed=0.05)
    a = FaultPlan.from_config(cfg, 300, 8)
    b = FaultPlan.from_config(cfg, 300, 8)
    assert [repr(e) for e in a] == [repr(e) for e in b]
    assert len(a) > 0
    # a chaos-rate schedule over 300 ticks exercises every kind
    assert a.kinds() == set(FaultKind)
    # different seed -> different schedule
    c = FaultPlan.from_config(dataclasses.replace(cfg, seed=4), 300, 8)
    assert [repr(e) for e in a] != [repr(e) for e in c]


def test_fault_plan_prefix_stable():
    """Extending the horizon must not rewrite the earlier ticks — the
    per-(tick, kind) draws are consumed in a fixed order."""
    cfg = FaultConfig(seed=9, p_nan_output=0.2, p_transient=0.2)
    short = FaultPlan.from_config(cfg, 50, 4)
    long = FaultPlan.from_config(cfg, 100, 4)
    for t in range(50):
        assert ([repr(e) for e in short.events_at(t)]
                == [repr(e) for e in long.events_at(t)])


def test_fault_plan_empty_config_is_clean():
    plan = FaultPlan.from_config(FaultConfig(), 100, 8)
    assert len(plan) == 0
    assert plan.kinds() == set()


# ---------------------------------------------------------------------------
# Injection reality: the faults hit the REAL serving path
# ---------------------------------------------------------------------------

def test_unsupervised_fleet_delivers_injected_nan(setup):
    """Without a supervisor there is no NaN guard: the injected
    non-finite output reaches the client.  This is the control
    experiment proving the injector corrupts the real data path (and
    exactly the accounting the supervised soak asserts to zero)."""
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.NAN_OUTPUT, slot=0)])
    fleet, clk = _fleet(params, cfg, plan=plan)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    for _ in range(4):
        clk.t += 0.01
        fleet.step()
    assert fleet.stats()["nan_delivered"] == 1
    bad = [r for r in rs if not np.isfinite(
        np.asarray(r.result.raw_pred)).all()]
    assert len(bad) == 1


def test_corrupt_input_poisons_staged_voxels(setup):
    """CORRUPT_INPUT is SILENT data corruption: the spiking threshold
    sanitises the NaN poison (NaN fails the compare -> zero spikes),
    so the output stays finite but WRONG — only the targeted slot."""
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.CORRUPT_INPUT, slot=0,
                                 value=float("nan"))])
    fleet, clk = _fleet(params, cfg, plan=plan)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    for _ in range(4):
        clk.t += 0.01
        fleet.step()
    clean, cclk = _fleet(params, cfg)            # same payloads, no plan
    refs = _requests(cfg, 2)
    for r in refs:
        clean.submit(r)
    for _ in range(4):
        cclk.t += 0.01
        clean.step()
    poisoned = np.asarray(rs[0].result.raw_pred)
    assert not np.array_equal(poisoned,
                              np.asarray(refs[0].result.raw_pred))
    np.testing.assert_array_equal(np.asarray(rs[1].result.raw_pred),
                                  np.asarray(refs[1].result.raw_pred))


def test_stall_fault_advances_serving_clock(setup):
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.STALL, stall_s=0.5)])
    fleet, clk = _fleet(params, cfg, plan=plan)
    for r in _requests(cfg, 2):
        fleet.submit(r)
    t0 = clk.t
    for _ in range(4):
        clk.t += 0.01
        fleet.step()
    # the injected 0.5 s stall moved the fake clock on top of the
    # 4 x 10 ms the loop added itself
    assert clk.t - t0 == pytest.approx(0.04 + 0.5)


# ---------------------------------------------------------------------------
# malformed requests at the edge
# ---------------------------------------------------------------------------

def test_malformed_submit_fails_without_killing_loop(setup):
    cfg, params = setup
    fleet, clk = _fleet(params, cfg, sup=SupervisorConfig())
    # all four malformed variants FAIL at submit with an error
    for v in range(4):
        bad = fleet.submit(make_malformed_request(1000 + v))
        assert bad.status is RequestStatus.FAILED
        assert bad.error
        assert bad.request.result is None
    # and the loop still serves healthy traffic afterwards
    rs = _requests(cfg, 2)
    done = fleet.run_to_completion(rs)
    assert fleet.stats()["malformed"] == 4
    assert all(r.result is not None for r in rs)
    assert sum(s.status is RequestStatus.DONE for s in done) == 2


def test_malformed_never_counted_delivered(setup):
    cfg, params = setup
    fleet, clk = _fleet(params, cfg, sup=SupervisorConfig())
    fleet.submit(make_malformed_request(0))
    s = fleet.stats()
    assert s["delivered"] == 0
    assert s["failed"] == 1
    assert s["availability"] == 0.0


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------

def test_transient_fault_retries_then_delivers(setup):
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.TRANSIENT_ERROR)])
    sup = SupervisorConfig(max_retries=2, retry_backoff_ms=5.0,
                           retry_jitter_ms=0.0)
    fleet, clk = _fleet(params, cfg, plan=plan, sup=sup)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    done = []
    for _ in range(10):
        clk.t += 0.01
        done.extend(fleet.step())
    s = fleet.stats()
    assert s["retries"] == 2                # both slots of the failed tick
    assert s["delivered"] == 2
    assert s["failed"] == 0
    assert all(r.telemetry.n_retries == 1 for r in done
               if r.status is RequestStatus.DONE)


def test_retry_budget_exhaustion_fails_terminally(setup):
    cfg, params = setup
    # every tick fails: retries must run out, requests must FAIL
    plan = FaultPlan([FaultEvent(t, FaultKind.TRANSIENT_ERROR)
                      for t in range(40)])
    sup = SupervisorConfig(max_retries=2, retry_backoff_ms=1.0,
                           retry_jitter_ms=0.0, breaker_threshold=1000)
    fleet, clk = _fleet(params, cfg, plan=plan, sup=sup)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    done = []
    for _ in range(30):
        clk.t += 0.01
        done.extend(fleet.step())
    failed = [r for r in done if r.status is RequestStatus.FAILED]
    assert len(failed) == 2
    assert all(r.attempts == 3 for r in failed)     # 1 try + 2 retries
    assert all(r.error for r in failed)
    assert fleet.stats()["availability"] == 0.0


def test_retry_backoff_deterministic(setup):
    """Two identical fleets on identical fake clocks walk the same
    retry schedule: jitter is keyed on (seed, rid, attempt)."""
    cfg, params = setup

    def run():
        plan = FaultPlan([FaultEvent(t, FaultKind.TRANSIENT_ERROR)
                          for t in (0, 2)])
        sup = SupervisorConfig(max_retries=3, retry_backoff_ms=4.0,
                               retry_jitter_ms=2.0, retry_seed=5)
        fleet, clk = _fleet(params, cfg, plan=plan, sup=sup)
        rs = _requests(cfg, 2)
        gates, lat = [], []
        for r in rs:
            fleet.submit(r)
        for _ in range(20):
            clk.t += 0.01
            lat.extend((s.rid, s.telemetry.latency_s) for s in fleet.step()
                       if s.status is RequestStatus.DONE)
            gates.extend((s.rid, s.attempts, s.not_before)
                         for s in list(fleet.queue._q))
        return gates, fleet.stats(), lat

    g1, s1, l1 = run()
    g2, s2, l2 = run()
    assert g1 == g2
    assert s1["retries"] == s2["retries"] > 0
    # every request's latency, not one percentile, walks the same way
    assert l1 and l1 == l2


def test_retry_preserves_original_enqueue_time(setup):
    """Latency percentiles must charge the WHOLE retry journey to the
    request, not restart the clock at each re-offer."""
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.TRANSIENT_ERROR)])
    sup = SupervisorConfig(max_retries=2, retry_backoff_ms=1.0,
                           retry_jitter_ms=0.0)
    fleet, clk = _fleet(params, cfg, plan=plan, sup=sup)
    rs = _requests(cfg, 2)
    clk.t = 1.0
    for r in rs:
        fleet.submit(r)
    for _ in range(10):
        clk.t += 0.01
        fleet.step()
    for r in rs:
        tel = r.result.telemetry
        assert tel.t_enqueue == 1.0
        assert tel.latency_s > 0.02     # spans the failed tick + retry
