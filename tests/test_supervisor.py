"""FleetSupervisor tests: breaker state machine under scripted
outcomes (pure unit, fake clock), fallback-ladder output parity, NaN
quarantine through the real fleet, degradation + recovery end to end,
and hedged re-dispatch."""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs.base import FleetConfig, SupervisorConfig
from repro.configs.registry import reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import init_npu
from repro.data.synthetic import make_scene_batch
from repro.kernels import blocks
from repro.serve.cognitive_engine import PerceptionRequest
from repro.serve.engine_core import EngineCore
from repro.serve.faults import FaultEvent, FaultKind, FaultPlan
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import RequestStatus
from repro.serve.supervisor import BreakerState, FleetSupervisor
from repro.serve.transport import StagingBank


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduced_snn("spiking_yolo"),
                              backend="pallas")
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


def _fleet(params, cfg, sup, *, plan=None, clk=None, batch=2):
    clk = clk if clk is not None else _FakeClock()
    return FleetEngine(
        params, cfg, fleet_cfg=FleetConfig(batch=batch, shard=False),
        supervisor_cfg=sup, fault_plan=plan, clock=clk,
        fault_advance=lambda s: setattr(clk, "t", clk.t + s)), clk


# ---------------------------------------------------------------------------
# breaker state machine (pure unit: scripted outcomes, no engines)
# ---------------------------------------------------------------------------

def _sup(**kw):
    cfg = SupervisorConfig(breaker_threshold=kw.pop("k", 3),
                           half_open_after=kw.pop("cool", 4),
                           recovery_threshold=kw.pop("rec", 2), **kw)
    return FleetSupervisor(cfg, ["fused", "layer", "jnp"], _FakeClock())


def _drive(sup, outcomes):
    """Feed a scripted pass/fail tape through the select/record cycle
    (depth-1 pipeline: record lands before the next select)."""
    for tick, ok in enumerate(outcomes):
        rung = sup.select_rung(tick)
        sup.record_tick(tick, rung, ok, wall_s=0.01,
                        reason="" if ok else "scripted")


def test_breaker_opens_after_consecutive_failures_only():
    sup = _sup(k=3)
    # interleaved failures never open it: the counter is CONSECUTIVE
    _drive(sup, [False, False, True, False, False, True])
    assert sup.state is BreakerState.CLOSED
    assert sup.rung == 0
    _drive(sup, [False, False, False])
    assert sup.state is BreakerState.OPEN
    assert sup.rung == 1                      # demoted one rung
    assert [e.event for e in sup.events] == ["demote"]


def test_half_open_probe_and_recovery():
    sup = _sup(k=2, cool=3, rec=2)
    _drive(sup, [False, False])               # open + demote -> rung 1
    assert sup.rung == 1
    _drive(sup, [True, True, True])           # cooldown on rung 1
    # next tick probes rung 0 (half-open)
    assert sup.select_rung(5) == 0
    assert sup.state is BreakerState.HALF_OPEN
    sup.record_tick(5, 0, True, 0.01)
    assert sup.rung == 1                      # one clean probe: not yet
    assert sup.select_rung(6) == 0
    sup.record_tick(6, 0, True, 0.01)
    assert sup.rung == 0                      # two clean probes: promoted
    assert sup.state is BreakerState.CLOSED
    events = [e.event for e in sup.events]
    assert events == ["demote", "probe", "promote"]


def test_failed_probe_reopens_and_restarts_cooldown():
    sup = _sup(k=2, cool=2, rec=1)
    _drive(sup, [False, False])               # rung 1
    _drive(sup, [True, True])                 # cooldown
    assert sup.select_rung(4) == 0            # probe
    sup.record_tick(4, 0, False, 0.01, "still broken")
    assert sup.state is BreakerState.OPEN
    assert sup.rung == 1                      # stays degraded
    # cooldown restarted: the immediate next tick serves rung 1
    assert sup.select_rung(5) == 1
    assert "probe_failed" in [e.event for e in sup.events]


def test_ladder_floor_keeps_serving():
    sup = _sup(k=1)
    _drive(sup, [False, False, False])        # demote 0->1->2
    assert sup.rung == 2
    _drive(sup, [False, False])               # on the floor: no demote
    assert sup.rung == 2
    assert [e.event for e in sup.events].count("breaker_floor") == 3


def test_floor_rung_breaker_recloses():
    """A single-rung ladder (jnp primary) has nowhere to demote; the
    breaker must still re-close after a clean cooldown window."""
    cfg = SupervisorConfig(breaker_threshold=2, half_open_after=3,
                           recovery_threshold=2)
    sup = FleetSupervisor(cfg, ["jnp"], _FakeClock())
    _drive(sup, [False, False])
    assert sup.state is BreakerState.OPEN
    assert sup.rung == 0
    _drive(sup, [True] * 5)
    assert sup.state is BreakerState.CLOSED
    assert [e.event for e in sup.events] == ["breaker_floor", "close"]


def test_straggler_ticks_count_as_failures():
    cfg = SupervisorConfig(breaker_threshold=1, straggler_factor=2.0,
                           straggler_patience=3)
    sup = FleetSupervisor(cfg, ["fused", "jnp"], _FakeClock())
    # establish a healthy median, then slow ticks (all "ok" — no hard
    # failure) until the straggler detector folds into the breaker
    for t in range(8):
        sup.record_tick(t, 0, True, wall_s=0.01)
    assert sup.rung == 0
    for t in range(8, 8 + 3):
        sup.record_tick(t, 0, True, wall_s=1.0)
    assert sup.rung == 1
    assert any(e.reason == "straggler" for e in sup.events)


def test_tick_outcomes_deterministic_replay():
    a, b = _sup(k=2, cool=2, rec=1), _sup(k=2, cool=2, rec=1)
    tape = [True, False, False, True, True, False, True, True, True,
            False, False, True, True, True, True]
    _drive(a, tape)
    _drive(b, tape)
    assert a.stats() == b.stats()


# ---------------------------------------------------------------------------
# fallback-ladder parity: degradation trades speed, never numbers
# ---------------------------------------------------------------------------

def test_ladder_rungs_bit_parity(setup):
    cfg, params = setup
    fleet, _ = _fleet(params, cfg, SupervisorConfig())
    assert fleet.ladder_names == ["pallas_fused", "pallas", "jnp"]
    scene = make_scene_batch(jax.random.PRNGKey(3), batch=2,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps, n_events=2048)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    bank = fleet.buffers.front
    for i in range(2):
        bank.stage_voxels(i, vox[:, i], scene.bayer[i])
    outs = [core.tick(bank) for core in fleet.cores]
    ref_out, ref_rgb, _ = outs[0]
    for out, rgb, _ in outs[1:]:
        np.testing.assert_allclose(np.asarray(out.raw_pred),
                                   np.asarray(ref_out.raw_pred),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out.control),
                                   np.asarray(ref_out.control),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rgb), np.asarray(ref_rgb),
                                   rtol=1e-5, atol=1e-5)


def test_jnp_rung_takes_the_kernels_matmul_precision(setup, monkeypatch):
    """Decided as on a TPU, every dot and conv of the jnp rung's tick asks
    for the precision the kernels' own dots use (full f32), so the
    ladder's rungs agree when compiled rather than the jnp rung falling
    to XLA's one-pass bf16 default."""
    cfg, params = setup
    monkeypatch.setattr(blocks, "interpret_mode", lambda: False)
    assert blocks.matmul_precision() == "highest"
    core = EngineCore(params, dataclasses.replace(cfg, backend="jnp"),
                      batch=2)
    bank = StagingBank(core.cfg, core.batch, core.frame_hw,
                       core.enc_cfg.event_capacity)
    text = core._step.lower(core.params, bank.slab,
                            bank.voxels).as_text()
    dots = re.findall(r"stablehlo\.(?:dot_general|convolution)\b.*", text)
    assert dots and all("HIGHEST" in d for d in dots), dots


# ---------------------------------------------------------------------------
# through the real fleet: quarantine, degradation, recovery, hedging
# ---------------------------------------------------------------------------

def test_nan_quarantine_zero_nan_delivered(setup):
    cfg, params = setup
    plan = FaultPlan([FaultEvent(0, FaultKind.NAN_OUTPUT, slot=0),
                      FaultEvent(1, FaultKind.NAN_OUTPUT, slot=1)])
    sup = SupervisorConfig(max_retries=2, retry_backoff_ms=1.0,
                           retry_jitter_ms=0.0, breaker_threshold=100)
    fleet, clk = _fleet(params, cfg, sup, plan=plan)
    rs = _requests(cfg, 4)
    for r in rs:
        fleet.submit(r)
    for _ in range(12):
        clk.t += 0.01
        fleet.step()
    s = fleet.stats()
    assert s["nan_delivered"] == 0
    assert s["supervisor"]["quarantined"] == 2
    assert s["delivered"] == 4                # quarantined slots retried
    for r in rs:
        assert np.isfinite(np.asarray(r.result.raw_pred)).all()
    # the retried requests carry the quarantine flag in telemetry
    assert sum(r.result.telemetry.quarantined for r in rs) >= 1


def test_degrade_and_recover_visible_in_telemetry(setup):
    cfg, params = setup
    plan = FaultPlan([FaultEvent(t, FaultKind.TRANSIENT_ERROR)
                      for t in range(1, 5)])
    sup = SupervisorConfig(breaker_threshold=2, half_open_after=2,
                           recovery_threshold=2, max_retries=3,
                           retry_backoff_ms=1.0, retry_jitter_ms=0.0)
    fleet, clk = _fleet(params, cfg, sup, plan=plan)
    rs = _requests(cfg, 16)
    for r in rs[:6]:
        fleet.submit(r)
    done = []
    for step in range(60):
        clk.t += 0.01
        done.extend(fleet.step())
        if step % 3 == 0 and 6 + step // 3 < len(rs):
            fleet.submit(rs[6 + step // 3])
    s = fleet.stats()
    events = [e["event"] for e in s["supervisor"]["transitions"]]
    assert "demote" in events and "promote" in events
    assert s["supervisor"]["degraded_ticks"] > 0
    assert s["supervisor"]["breaker_state"] == "closed"
    assert s["supervisor"]["active_backend"] == "pallas_fused"
    assert s["delivered"] == 16
    assert s["nan_delivered"] == 0
    # deliveries happened on BOTH sides of the degradation
    rungs = {r.telemetry.rung for r in done
             if r.status is RequestStatus.DONE}
    assert "pallas_fused" in rungs and "pallas" in rungs


def test_hedge_wins_when_primary_tick_fails(setup):
    cfg, params = setup
    # tick 0 carries the primaries and fails; the hedges (launched
    # after the SLO passes) ride a later clean tick and win
    plan = FaultPlan([FaultEvent(0, FaultKind.TRANSIENT_ERROR)])
    sup = SupervisorConfig(max_retries=0, hedge_after_ms=5.0,
                           breaker_threshold=100)
    fleet, clk = _fleet(params, cfg, sup, plan=plan)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    done = []
    for _ in range(8):
        clk.t += 0.01
        done.extend(fleet.step())
    s = fleet.stats()
    assert s["hedges"] == 2
    assert s["hedge_wins"] == 2
    assert s["delivered"] == 2
    assert s["failed"] == 0                   # parked on hedge, not failed
    for r in rs:
        assert r.result is not None
        assert r.result.telemetry.hedge_won


def test_no_hedge_before_slo(setup):
    cfg, params = setup
    sup = SupervisorConfig(hedge_after_ms=10_000.0)
    fleet, clk = _fleet(params, cfg, sup)
    rs = _requests(cfg, 2)
    for r in rs:
        fleet.submit(r)
    for _ in range(4):
        clk.t += 0.01
        fleet.step()
    s = fleet.stats()
    assert s["hedges"] == 0
    assert s["delivered"] == 2


def test_supervised_clean_run_stays_on_primary(setup):
    cfg, params = setup
    fleet, clk = _fleet(params, cfg, SupervisorConfig())
    rs = _requests(cfg, 6)
    done = fleet.run_to_completion(rs)
    s = fleet.stats()
    assert s["delivered"] == 6
    assert s["supervisor"]["breaker_state"] == "closed"
    assert s["supervisor"]["transitions"] == []
    assert s["supervisor"]["degraded_ticks"] == 0
    assert {r.telemetry.rung for r in done} == {"pallas_fused"}
    # the jit cache holds ONE executable per rung actually used
    assert fleet.cores[0]._step._cache_size() == 1
