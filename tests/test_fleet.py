"""FleetEngine continuous-batching semantics on a single device:
admission control (bounded queue -> REJECTED), deadline shedding
(EXPIRED, result None), ragged arrival, double-buffered pipelining,
telemetry stamps, and fleet-vs-CognitiveEngine parity — plus the
8-device sharded parity run as a subprocess (tests/_fleet_main.py,
mirroring test_distributed.py's isolation pattern)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import FleetConfig
from repro.configs.registry import reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import init_npu
from repro.data.synthetic import make_scene_batch
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import (AdmissionQueue, RequestStatus,
                                   ServeRequest)


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


def _event_requests(cfg, n, seed=0):
    scene = make_scene_batch(jax.random.PRNGKey(seed), batch=n,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps, n_events=2048)
    return [PerceptionRequest(
        rid=i, events=jax.tree_util.tree_map(lambda a: a[i], scene.events),
        bayer=scene.bayer[i]) for i in range(n)]


class _FakeClock:
    """Deterministic serving clock: deadlines fire exactly when the
    test advances it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# pure scheduler semantics (no engine)
# ---------------------------------------------------------------------------

def test_admission_queue_bounded_and_sheds():
    q = AdmissionQueue(2)
    a = ServeRequest(request=PerceptionRequest(rid=0))
    b = ServeRequest(request=PerceptionRequest(rid=1), deadline=5.0)
    c = ServeRequest(request=PerceptionRequest(rid=2))
    assert q.offer(a, now=0.0) and q.offer(b, now=1.0)
    assert not q.offer(c, now=2.0)            # depth 2: rejected
    assert c.status is RequestStatus.REJECTED and q.n_rejected == 1
    assert b.telemetry.t_enqueue == 1.0
    shed = q.shed_expired(now=10.0)           # b expired mid-queue
    assert shed == [b] and b.status is RequestStatus.EXPIRED
    assert q.n_expired == 1 and len(q) == 1
    assert q.pop_ready(now=10.0) is a and q.pop_ready(now=10.0) is None
    with pytest.raises(ValueError, match="max_depth"):
        AdmissionQueue(0)


# ---------------------------------------------------------------------------
# fleet serving semantics (single device)
# ---------------------------------------------------------------------------

def test_fleet_admission_control_rejects_beyond_queue(setup):
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=3))
    reqs = _requests(cfg, 5)
    sub = [fleet.submit(r) for r in reqs]
    assert [s.status for s in sub[:3]] == [RequestStatus.QUEUED] * 3
    assert [s.status for s in sub[3:]] == [RequestStatus.REJECTED] * 2
    assert all(s.request.result is None for s in sub[3:])
    done = fleet.drain()
    assert sorted(s.rid for s in done) == [0, 1, 2]
    assert fleet.stats()["rejected"] == 2
    assert fleet.stats()["delivered"] == 3


def test_fleet_deadline_shedding_is_explicit(setup):
    """A queued request whose deadline passes is shed with EXPIRED and
    a None result — never silently dropped, never delivered stale."""
    cfg, params = setup
    clk = _FakeClock()
    fleet = FleetEngine(params, cfg, clock=clk,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8))
    live, doomed = _requests(cfg, 2)
    s_live = fleet.submit(live)                       # no deadline
    s_doomed = fleet.submit(doomed, deadline_ms=10.0)  # 0.01 s
    clk.t = 5.0                                       # way past it
    done = fleet.drain()
    assert s_doomed in done and s_doomed.status is RequestStatus.EXPIRED
    assert doomed.result is None
    assert s_live.status is RequestStatus.DONE
    assert live.result is not None
    assert fleet.stats()["expired"] == 1


def test_fleet_default_deadline_inherited_from_config(setup):
    cfg, params = setup
    clk = _FakeClock()
    fleet = FleetEngine(params, cfg, clock=clk,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8,
                                              default_deadline_ms=100.0))
    sreq = fleet.submit(_requests(cfg, 1)[0])
    assert sreq.deadline == pytest.approx(0.1)
    clk.t = 1.0
    done = fleet.drain()
    assert done == [sreq] and sreq.status is RequestStatus.EXPIRED


def test_fleet_double_buffer_pipelines_one_tick_deep(setup):
    """With double buffering the first step dispatches but harvests
    nothing (pipeline fill); results arrive one step later."""
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8,
                                              double_buffer=True))
    for r in _requests(cfg, 2):
        fleet.submit(r)
    assert fleet.step() == []            # tick 1 in flight
    assert fleet._inflight is not None
    done = fleet.step()                  # harvested on the next round
    assert sorted(s.rid for s in done) == [0, 1]
    assert all(s.status is RequestStatus.DONE for s in done)

    # depth-1 profile: the same submit/step delivers immediately
    edge = FleetEngine(params, cfg,
                       fleet_cfg=FleetConfig(batch=2, max_queue=8,
                                             double_buffer=False))
    for r in _requests(cfg, 2, seed=1):
        edge.submit(r)
    assert sorted(s.rid for s in edge.step()) == [0, 1]


@pytest.mark.parametrize("double_buffer", [True, False])
def test_fleet_matches_cognitive_engine(setup, double_buffer):
    """Continuous batching must not change the math: same requests
    through FleetEngine (either pipeline depth) and CognitiveEngine
    give the same rgb/control/raw_pred."""
    cfg, params = setup
    n = 5                                # ragged: 2 full ticks + 1 part
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8,
                                              double_buffer=double_buffer))
    done = fleet.run_to_completion(_requests(cfg, n))
    assert len(done) == n
    eng = CognitiveEngine(params, cfg, batch=2)
    ref = _requests(cfg, n)
    eng.run_to_completion(ref)
    for s, r in zip(sorted(done, key=lambda s: s.rid), ref):
        assert s.rid == r.rid
        np.testing.assert_allclose(s.request.result.rgb, r.result.rgb,
                                   atol=1e-5)
        np.testing.assert_allclose(s.request.result.control,
                                   r.result.control, atol=1e-5)
        np.testing.assert_allclose(s.request.result.raw_pred,
                                   r.result.raw_pred, atol=1e-5)
    assert fleet._step._cache_size() == 1   # still ONE tick executable


def test_fleet_ragged_arrival_keeps_batch_full(setup):
    """Requests arriving between steps pack into the next tick; nothing
    waits for a 'full batch' that never comes."""
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=4, max_queue=16))
    reqs = _requests(cfg, 6)
    for r in reqs[:3]:
        fleet.submit(r)
    out = fleet.step()                   # 3/4 slots used, in flight
    for r in reqs[3:]:
        fleet.submit(r)                  # arrive mid-pipeline
    out += fleet.drain()
    assert sorted(s.rid for s in out) == list(range(6))
    assert fleet.ticks == 2              # 3-wide tick + 3-wide tick
    assert fleet._step._cache_size() == 1


def test_fleet_event_requests_and_mixed_kinds(setup):
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8))
    vr = _requests(cfg, 1)[0]
    er = _event_requests(cfg, 2, seed=2)[1]
    er.rid = 1
    s1, s2 = fleet.submit(vr), fleet.submit(er)
    assert (s1.kind, s2.kind) == ("voxels", "events")
    done = fleet.drain()
    assert sorted(s.rid for s in done) == [0, 1]
    for s in done:
        assert s.request.result.rgb.shape == (cfg.height, cfg.width, 3)
        assert np.isfinite(np.asarray(s.request.result.rgb)).all()


def test_fleet_telemetry_timestamps_and_late_delivery(setup):
    """Telemetry orders enqueue <= admit <= dispatch <= deliver; a
    request whose deadline passes AFTER dispatch is still delivered
    (compute already spent) but flagged deadline_missed."""
    cfg, params = setup
    clk = _FakeClock()
    fleet = FleetEngine(params, cfg, clock=clk,
                        fleet_cfg=FleetConfig(batch=2, max_queue=8,
                                              double_buffer=True))
    sreq = fleet.submit(_requests(cfg, 1)[0], deadline_ms=1000.0)
    clk.t = 0.25
    assert fleet.step() == []            # dispatched within deadline
    assert sreq.status is RequestStatus.IN_FLIGHT
    clk.t = 2.0                          # deadline passes in flight
    done = fleet.step()
    assert done == [sreq] and sreq.status is RequestStatus.DONE
    tel = sreq.request.result.telemetry
    assert tel.deadline_missed
    assert (tel.t_enqueue <= tel.t_admit <= tel.t_dispatch
            <= tel.t_deliver)
    assert tel.latency_s == pytest.approx(2.0)
    assert fleet.stats()["deadline_missed"] == 1


def test_fleet_stats_percentiles(setup):
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=16))
    done = fleet.run_to_completion(_requests(cfg, 4))
    st = fleet.stats()
    assert st["delivered"] == 4 and st["rejected"] == 0
    assert st["n_devices"] == 1
    # percentiles are the caller's, over its requests' telemetry
    assert not any(k.startswith("latency") for k in st)
    lat = sorted(r.telemetry.latency_s for r in done
                 if r.status is RequestStatus.DONE)
    assert len(lat) == 4
    assert 0.0 < lat[int(0.50 * 4)] <= lat[min(3, int(0.99 * 4))]


def test_fleet_rejects_batch_not_divisible_by_mesh(setup):
    cfg, params = setup
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    # 1 device always divides; the divisibility guard itself is covered
    # in the 8-device subprocess — here just check explicit mesh wiring
    fleet = FleetEngine(params, cfg, mesh=mesh,
                        fleet_cfg=FleetConfig(batch=2, max_queue=4))
    done = fleet.run_to_completion(_requests(cfg, 2))
    assert len(done) == 2
    assert fleet.core.n_devices == 1


# ---------------------------------------------------------------------------
# 8-device sharded integration (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(1200)
def test_fleet_sharded_integration():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"    # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "_fleet_main.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "fleet sharded checks failed"
    assert "ALL FLEET CHECKS PASSED" in proc.stdout
