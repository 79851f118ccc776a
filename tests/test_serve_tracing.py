"""The serving path's own tracing: ``serve.*`` profiler spans around
the stages of ``FleetEngine.step`` and the engine core's transfers, the
``tick`` that ties a request to its spans, the named stage scopes in
the tick executable, and the training-only scalars the serving tick no
longer computes."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import FleetConfig, SupervisorConfig
from repro.configs.registry import reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import init_npu, npu_forward
from repro.data.synthetic import make_scene_batch
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest
from repro.serve.engine_core import EngineCore
from repro.serve.faults import FaultEvent, FaultKind, FaultPlan
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import RequestStatus
from repro.serve.transport import StagingBank, bank_nbytes

STAGES = ("serve.shed", "serve.pack", "serve.upload", "serve.dispatch",
          "serve.fetch", "serve.deliver")


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


def _serve_spans(logdir):
    """``(name, start_ns, end_ns, args)`` of every ``serve.*`` host span
    in the profile written under ``logdir``, by start time."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                          for e in line.events
                          if e.name.startswith("serve.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _by_step(spans):
    """Each ``serve.step`` span with the stage spans inside it."""
    steps = [s for s in spans if s[0] == "serve.step"]
    return [(st, [s for s in spans if s[0] != "serve.step"
                  and st[1] <= s[1] and s[2] <= st[2]]) for st in steps]


def test_step_spans_nest_in_order_and_tie_requests_to_ticks(setup,
                                                            tmp_path):
    cfg, params = setup
    fleet = FleetEngine(params, cfg,
                        fleet_cfg=FleetConfig(batch=2, max_queue=16))
    reqs = _requests(cfg, 5)
    fleet.run_to_completion(reqs[:2])       # compiles outside the trace
    first = fleet.ticks
    returned = []
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs:
            r.result = None
            fleet.submit(r)
        while len(fleet.queue) or fleet._inflight is not None:
            returned.append(fleet.step())
    steps = _by_step(_serve_spans(str(tmp_path)))
    assert len(steps) == len(returned)
    # one span per stage per step, in the order the step runs them
    for step, inner in steps:
        names = [s[0] for s in inner]
        assert names == [n for n in STAGES if n in names]
        assert names[:2] == ["serve.shed", "serve.pack"]
        assert all(s[3]["tick"] == step[3]["tick"] for s in inner
                   if s[0] in ("serve.shed", "serve.pack", "serve.upload",
                               "serve.dispatch"))
    # the step's tick is the number of the tick it would dispatch
    ticks = [step[3]["tick"] for step, _ in steps]
    assert ticks == list(range(first, first + len(steps)))
    # pack's slots are the requests dispatched in that tick
    tick_of = {r.rid: r.result.telemetry.tick for r in reqs}
    for step, inner in steps:
        pack, = [s for s in inner if s[0] == "serve.pack"]
        assert pack[3]["slots"] == sum(
            t == step[3]["tick"] for t in tick_of.values())
        assert "queued" in pack[3]
    assert sorted(set(tick_of.values())) == [first, first + 1, first + 2]
    # each request records the tick whose deliver span handed it back
    for (step, inner), done in zip(steps, returned):
        deliver = [s for s in inner if s[0] == "serve.deliver"]
        if not done:
            assert not deliver
            continue
        d, = deliver
        assert d[3]["slots"] == len(done)
        assert all(s.request.result.telemetry.tick == d[3]["tick"]
                   for s in done)
        fetch, = [s for s in inner if s[0] == "serve.fetch"]
        assert fetch[3]["tick"] == d[3]["tick"]


def test_transfer_spans_carry_the_bytes_moved(setup, tmp_path):
    """Tick 7 stages no voxels and moves the slab alone; tick 8 stages
    one voxel slot and moves the voxel slab besides."""
    cfg, params = setup
    core = EngineCore(params, cfg, batch=2)
    bank = StagingBank(cfg, 2, core.frame_hw, core.enc_cfg.event_capacity)
    out = core.tick(bank)                       # compiles outside
    moved = {7: sum(a.nbytes for a in bank.as_tuple())}
    with jax.profiler.trace(str(tmp_path)):
        core.tick(bank, 7)
        bank.stage_voxels(0, bank.voxels[:, 0], bank.bayer[0])
        moved[8] = sum(a.nbytes for a in bank.as_tuple())
        core.tick(bank, 8)
    spans = _serve_spans(str(tmp_path))
    assert [s[0] for s in spans] == ["serve.upload", "serve.dispatch",
                                     "serve.fetch"] * 2
    assert [s[3]["tick"] for s in spans] == [7] * 3 + [8] * 3
    uploads = [s[3] for s in spans if s[0] == "serve.upload"]
    assert [(a["bytes"], a["voxels"]) for a in uploads] == [
        (moved[7], 0), (moved[8], 1)]
    assert moved[8] == moved[7] + bank.voxels.nbytes
    assert all(s[3]["bytes"] == sum(
        np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(out))
        for s in spans if s[0] == "serve.fetch")


@pytest.mark.parametrize("batch,frame_hw,capacity",
                         [(1, None, 64), (2, (48, 40), 2048),
                          (8, (16, 16), 1)])
def test_bank_nbytes_is_the_staging_bank_size(setup, batch, frame_hw,
                                              capacity):
    cfg, _ = setup
    hw = frame_hw or (cfg.height, cfg.width)
    bank = StagingBank(cfg, batch, hw, capacity)
    slab_only = sum(a.nbytes for a in bank.as_tuple())
    bank.stage_voxels(0, bank.voxels[:, 0], bank.bayer[0])
    with_voxels = sum(a.nbytes for a in bank.as_tuple())
    assert bank_nbytes(cfg, batch, hw, capacity) == (slab_only,
                                                     with_voxels)
    assert slab_only == 4 * batch * bank.layout.words


def test_cognitive_engine_ticks_carry_their_number(setup, tmp_path):
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    eng.run_to_completion(_requests(cfg, 2))    # compiles outside
    with jax.profiler.trace(str(tmp_path)):
        eng.run_to_completion(_requests(cfg, 4))
    spans = _serve_spans(str(tmp_path))
    for name in ("serve.upload", "serve.dispatch", "serve.fetch"):
        assert [s[3]["tick"] for s in spans if s[0] == name] == [1, 2]


def test_tick_hlo_carries_stage_scopes(setup):
    """The stage scopes are op metadata: every stage names its ops, and
    the instruction names (which the device trace shows) carry none."""
    cfg, params = setup
    core = EngineCore(params, cfg, batch=2)
    bank = StagingBank(cfg, 2, core.frame_hw, core.enc_cfg.event_capacity)
    text = core._step.lower(core.params, bank.slab, bank.voxels).as_text(
        dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    # (``control`` is a no-op slice where the head is as wide as the
    # ISP's control vector, and then names no op)
    for stage in ("encode", "npu", "isp"):
        assert any(n.startswith(f"jit(_step)/{stage}/") for n in names), \
            stage
    assert not re.search(r"^\s*%?\S*(encode|npu|isp)/\S* = ", text,
                         re.MULTILINE)


def test_serving_tick_returns_no_training_scalars(setup):
    """The tick drops ``sparsity`` and ``tile_skip``; what it serves is
    the NPU's own output, unchanged."""
    cfg, params = setup
    core = EngineCore(params, cfg, batch=2)
    reqs = _requests(cfg, 2)
    bank = StagingBank(cfg, 2, core.frame_hw, core.enc_cfg.event_capacity)
    for i, r in enumerate(reqs):
        bank.stage_voxels(i, r.voxels, r.bayer)
    vox = np.array(bank.voxels)
    out, _, _ = core.tick(bank)
    assert out.sparsity is None and out.tile_skip is None
    ref = jax.jit(lambda p, v: npu_forward(p, v, cfg))(params, vox)
    np.testing.assert_array_equal(out.raw_pred, np.asarray(ref.raw_pred))
    np.testing.assert_array_equal(
        out.control, np.asarray(ref.control)[:, :core.isp_cfg.control_dim])


def test_retried_request_records_the_tick_that_delivered_it(setup):
    cfg, params = setup
    sup = SupervisorConfig(max_retries=2, retry_backoff_ms=0.0,
                           retry_jitter_ms=0.0, breaker_threshold=100)
    fleet = FleetEngine(
        params, cfg, fleet_cfg=FleetConfig(batch=2, max_queue=8),
        supervisor_cfg=sup,
        fault_plan=FaultPlan([FaultEvent(0, FaultKind.TRANSIENT_ERROR)]))
    done = fleet.run_to_completion(_requests(cfg, 2))
    assert [r.status for r in done] == [RequestStatus.DONE] * 2
    assert all(r.telemetry.n_retries == 1 for r in done)
    # tick 0 failed at the fetch; the retry rode tick 1
    assert [r.telemetry.tick for r in done] == [1, 1]
