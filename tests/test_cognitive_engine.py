"""CognitiveEngine streaming tests: submit/tick lifecycle, slot
recycling, single-executable caching, reconfigured pipelines
end-to-end (acceptance: reordered/extra-stage pipeline through the
engine), and the raw-event ingestion path (submit_events with the
encode stage folded into the one jit-cached tick executable)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EncodingConfig, ISPConfig
from repro.configs.registry import get_isp_config, reduced_snn
from repro.core.cognitive import cognitive_forward, cognitive_step
from repro.core.encoding import voxel_batch
from repro.core.npu import configure_for_isp, init_npu
from repro.data.synthetic import make_scenario, make_scene_batch
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_snn("spiking_yolo")
    params = init_npu(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scene(cfg, n, seed=0, n_events=2048):
    return make_scene_batch(jax.random.PRNGKey(seed), batch=n,
                            height=cfg.height, width=cfg.width,
                            time_steps=cfg.time_steps, n_events=n_events)


def _requests(cfg, n, seed=0):
    scene = _scene(cfg, n, seed)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    return [PerceptionRequest(rid=i, voxels=vox[:, i], bayer=scene.bayer[i])
            for i in range(n)]


def _event_requests(cfg, n, seed=0, n_events=2048):
    scene = _scene(cfg, n, seed, n_events=n_events)
    return [PerceptionRequest(
        rid=i, events=jax.tree_util.tree_map(lambda a: a[i], scene.events),
        bayer=scene.bayer[i]) for i in range(n)]


def test_submit_tick_smoke(setup):
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    reqs = _requests(cfg, 2)
    assert eng.submit(reqs[0]) and eng.submit(reqs[1])
    done = eng.tick()
    assert {r.rid for r in done} == {0, 1}
    for r in done:
        assert r.result.rgb.shape == (cfg.height, cfg.width, 3)
        assert r.result.control.shape == (cfg.control_dim,)
        assert np.isfinite(np.asarray(r.result.rgb)).all()
        assert "gamma" in r.result.stage_params


def test_engine_full_then_recycles(setup):
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    reqs = _requests(cfg, 3)
    assert eng.submit(reqs[0]) and eng.submit(reqs[1])
    assert not eng.submit(reqs[2])         # pool exhausted
    eng.tick()
    assert eng.submit(reqs[2])             # slot recycled
    done = eng.tick()
    assert [r.rid for r in done] == [2]


def test_run_to_completion_single_executable(setup):
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    done = eng.run_to_completion(_requests(cfg, 5))
    assert len(done) == 5
    assert eng.ticks == 3                  # ceil(5/2) batched launches
    assert eng._step._cache_size() == 1    # one executable served all ticks


def test_engine_matches_cognitive_step(setup):
    """Default pipeline through the engine == one-shot cognitive_forward
    (registry mapping) on the same frames."""
    cfg, params = setup
    reqs = _requests(cfg, 2, seed=3)
    eng = CognitiveEngine(params, cfg, batch=2)
    done = sorted(eng.run_to_completion(list(reqs)), key=lambda r: r.rid)
    vox = jnp.stack([r.voxels for r in reqs], axis=1)
    bayer = jnp.stack([r.bayer for r in reqs])
    out = cognitive_forward(params, vox, bayer, cfg)
    np.testing.assert_allclose(
        jnp.stack([r.result.rgb for r in done]), out.rgb, atol=1e-5)


def test_engine_with_extra_stage_pipeline(setup):
    """Acceptance: a reordered/extended pipeline (hdr: +tonemap +ccm,
    moved ahead of gamma) runs end-to-end through the engine with the
    control head resized via configure_for_isp."""
    cfg, _ = setup
    hdr = get_isp_config("hdr")
    cfg_hdr = configure_for_isp(cfg, hdr)
    assert cfg_hdr.control_dim == hdr.control_dim == 10
    params = init_npu(jax.random.PRNGKey(1), cfg_hdr)
    eng = CognitiveEngine(params, cfg_hdr, hdr, batch=2)
    done = eng.run_to_completion(_requests(cfg, 3))
    assert len(done) == 3
    for r in done:
        assert r.result.rgb.shape == (cfg.height, cfg.width, 3)
        sp = r.result.stage_params
        assert "tonemap" in sp and "ccm" in sp
        assert 0.0 <= float(sp["tonemap"]["strength"]) <= 1.0
        assert 0.0 <= float(sp["ccm"]["saturation"]) <= 2.0


def test_engine_legacy_control_order_matches_shim(setup):
    """A head trained through the cognitive_step shim (legacy slot
    order) serves unchanged via control_order='legacy': engine output ==
    cognitive_step on the same frames. Pipeline-order serving of the
    same head differs (slots would be reinterpreted)."""
    cfg, params = setup
    reqs = _requests(cfg, 2, seed=5)
    vox = jnp.stack([r.voxels for r in reqs], axis=1)
    bayer = jnp.stack([r.bayer for r in reqs])
    ref = cognitive_step(params, vox, bayer, cfg)

    eng = CognitiveEngine(params, cfg, batch=2, control_order="legacy")
    done = sorted(eng.run_to_completion(list(reqs)), key=lambda r: r.rid)
    np.testing.assert_allclose(
        jnp.stack([r.result.rgb for r in done]), ref.rgb, atol=1e-5)

    with pytest.raises(ValueError, match="control_order"):
        CognitiveEngine(params, cfg, batch=2, control_order="typo")

    # a subset pipeline in legacy mode still gathers the historical
    # 8-slot layout: a 6-wide head must be rejected, not clamp-gathered
    import dataclasses
    from repro.configs import ISPConfig
    preview = ISPConfig(name="preview", stages=(
        "exposure", "dpc", "demosaic", "awb", "gamma"))
    cfg6 = dataclasses.replace(cfg, control_dim=preview.control_dim)
    params6 = init_npu(jax.random.PRNGKey(3), cfg6)
    with pytest.raises(ValueError, match="legacy slot layout"):
        CognitiveEngine(params6, cfg6, preview, batch=2,
                        control_order="legacy")


def test_engine_rejects_undersized_control_head(setup):
    cfg, params = setup                    # control_dim=8 < hdr's 10
    with pytest.raises(ValueError, match="configure_for_isp"):
        CognitiveEngine(params, cfg, get_isp_config("hdr"), batch=2)


# ---------------------------------------------------------------------------
# Event-driven ingestion path (paper §IV-A through the engine)
# ---------------------------------------------------------------------------

def test_submit_events_roundtrips_to_result(setup):
    """Acceptance: a raw event buffer round-trips to a PerceptionResult
    through the tick executable, and matches the precomputed-voxel path
    bit-for-bit (the encode stage is the same jnp reference)."""
    cfg, params = setup
    scene = _scene(cfg, 2, seed=21)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    eng = CognitiveEngine(params, cfg, batch=2)
    rv = PerceptionRequest(rid=0, voxels=vox[:, 0], bayer=scene.bayer[0])
    re = PerceptionRequest(
        rid=1, events=jax.tree_util.tree_map(lambda a: a[0], scene.events),
        bayer=scene.bayer[0])
    assert eng.submit(rv) and eng.submit_events(re)
    done = {r.rid: r for r in eng.tick()}
    assert set(done) == {0, 1}
    assert re.result.rgb.shape == (cfg.height, cfg.width, 3)
    np.testing.assert_array_equal(np.asarray(done[0].result.rgb),
                                  np.asarray(done[1].result.rgb))
    np.testing.assert_array_equal(np.asarray(done[0].result.control),
                                  np.asarray(done[1].result.control))


def test_submit_events_ragged_arrival_and_exhaustion(setup):
    """Ragged event-request arrival: pool exhaustion rejects, recycled
    slots re-admit, every request completes, ONE executable serves all
    ticks (no retrace across voxel/event mixes)."""
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    evs = _event_requests(cfg, 3, seed=4)
    assert eng.submit_events(evs[0]) and eng.submit_events(evs[1])
    assert not eng.submit_events(evs[2])       # pool exhausted
    assert len(eng.tick()) == 2
    assert eng.submit_events(evs[2])           # slot recycled
    vox_reqs = _requests(cfg, 1, seed=5)
    assert eng.submit(vox_reqs[0])             # mixed second tick
    done = eng.tick()
    assert {r.rid for r in done} == {2, 0}
    for r in done:
        assert np.isfinite(np.asarray(r.result.rgb)).all()
    assert eng.ticks == 2
    assert eng._step._cache_size() == 1        # one executable, all mixes


def test_submit_routes_event_only_requests(setup):
    """submit() on a request carrying only events goes through the
    event path; carrying neither payload is an error."""
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    req = _event_requests(cfg, 1, seed=6)[0]
    assert eng.submit(req)
    assert bool(eng.from_events[0])
    with pytest.raises(ValueError, match="neither voxels nor events"):
        eng.submit(PerceptionRequest(rid=9, bayer=jnp.zeros(
            (cfg.height, cfg.width))))
    with pytest.raises(ValueError, match="no events"):
        eng.submit_events(PerceptionRequest(rid=9, bayer=jnp.zeros(
            (cfg.height, cfg.width))))


def test_submit_events_budgets_overfull_window(setup):
    """A window beyond the engine's FIFO capacity is budgeted down on
    admission (earliest-first), not rejected and not shape-exploded."""
    cfg, params = setup
    enc = EncodingConfig(event_capacity=256)
    eng = CognitiveEngine(params, cfg, batch=2, enc_cfg=enc)
    storm = make_scenario("noise_burst", jax.random.PRNGKey(7),
                          height=cfg.height, width=cfg.width, n_events=4096)
    bayer = _scene(cfg, 1, seed=7).bayer[0]
    assert eng.submit_events(PerceptionRequest(rid=0, events=storm,
                                               bayer=bayer))
    assert eng.events.t.shape == (2, 256)      # static slot FIFO intact
    assert int(eng.events.num_events()[0]) == 256
    # budget kept the EARLIEST 256 events
    kept_latest = float(jnp.max(jnp.where(eng.events.valid[0],
                                          eng.events.t[0], -jnp.inf)))
    all_sorted = jnp.sort(jnp.where(storm.valid, storm.t, jnp.inf))
    assert kept_latest <= float(all_sorted[255]) + 1e-9
    (done,) = eng.tick()
    assert np.isfinite(np.asarray(done.result.rgb)).all()


def test_event_path_pallas_backend_matches_jnp(setup):
    """The engine's encode stage dispatches to the Pallas voxelizer and
    produces bit-identical results to the jnp backend."""
    cfg, params = setup
    req_j = _event_requests(cfg, 1, seed=8, n_events=512)[0]
    req_p = _event_requests(cfg, 1, seed=8, n_events=512)[0]
    enc_j = EncodingConfig(event_capacity=512)
    enc_p = EncodingConfig(event_capacity=512, backend="pallas")
    eng_j = CognitiveEngine(params, cfg, batch=1, enc_cfg=enc_j)
    eng_p = CognitiveEngine(params, cfg, batch=1, enc_cfg=enc_p)
    assert eng_j.submit_events(req_j) and eng_p.submit_events(req_p)
    (dj,), (dp,) = eng_j.tick(), eng_p.tick()
    np.testing.assert_array_equal(np.asarray(dj.result.rgb),
                                  np.asarray(dp.result.rgb))
    with pytest.raises(ValueError, match="backend"):
        CognitiveEngine(params, cfg, batch=1,
                        enc_cfg=EncodingConfig(backend="typo"))


def test_event_path_scenarios_through_engine(setup):
    """Every DVS scenario generator streams through submit_events; the
    oob='drop' strict policy also serves (still one executable each)."""
    from repro.data.synthetic import SCENARIOS
    cfg, params = setup
    enc = EncodingConfig(mode="count", oob="drop", event_capacity=512)
    eng = CognitiveEngine(params, cfg, batch=2, enc_cfg=enc)
    bayer = _scene(cfg, 1, seed=9).bayer[0]
    for i, name in enumerate(SCENARIOS):
        ev = make_scenario(name, jax.random.PRNGKey(i), height=cfg.height,
                           width=cfg.width, n_events=512)
        assert eng.submit_events(PerceptionRequest(rid=i, events=ev,
                                                   bayer=bayer))
        (done,) = eng.tick()
        assert np.isfinite(np.asarray(done.result.rgb)).all()
    assert eng._step._cache_size() == 1


def test_engine_tick_issues_single_device_put(setup, monkeypatch):
    """Zero-copy tick contract: a submit is a host-side memcpy (no
    device dispatch at all), and the tick uploads what its slots use
    (the slab, and the voxel slab these voxel slots staged) with
    exactly ONE jax.device_put."""
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    reqs = _requests(cfg, 4)
    for r in reqs[:2]:
        assert eng.submit(r)
    eng.tick()                                 # warm the executable

    calls = []
    real = jax.device_put

    def counting(*a, **k):
        calls.append(a[0])
        return real(*a, **k)

    monkeypatch.setattr(jax, "device_put", counting)
    for r in reqs[2:]:
        assert eng.submit(r)
    assert len(calls) == 0                     # staging is host-side
    done = eng.tick()
    assert len(done) == 2
    up, = calls                                # one upload per tick
    assert len(up) == 2 and up[0] is eng.staging.slab \
        and up[1] is eng.staging.voxels


def test_engine_with_pallas_npu_backend_matches_jnp(setup):
    """The kernel-backed NPU (SNNConfig.backend="pallas") serves
    through the engine bit-identically to the jnp backend."""
    import dataclasses
    cfg, params = setup
    cfg_p = dataclasses.replace(cfg, backend="pallas")
    reqs_j = _requests(cfg, 2, seed=11)
    reqs_p = _requests(cfg, 2, seed=11)
    eng_j = CognitiveEngine(params, cfg, batch=2)
    eng_p = CognitiveEngine(params, cfg_p, batch=2)
    done_j = sorted(eng_j.run_to_completion(reqs_j), key=lambda r: r.rid)
    done_p = sorted(eng_p.run_to_completion(reqs_p), key=lambda r: r.rid)
    for a, b in zip(done_p, done_j):
        np.testing.assert_array_equal(np.asarray(a.result.rgb),
                                      np.asarray(b.result.rgb))
        np.testing.assert_array_equal(np.asarray(a.result.control),
                                      np.asarray(b.result.control))


def test_engine_with_fused_isp_backend_matches_jnp(setup):
    """ISPConfig(backend="pallas_fused") — the fusion-planned
    streaming ISP — serves through the engine with identical
    ``PerceptionResult``s: bit-equal controls/predictions/stage params
    (the NPU half is untouched) and RGB within the fused path's NLM
    tolerance (see tests/test_isp_fused.py)."""
    cfg, params = setup
    reqs_j = _requests(cfg, 3, seed=13)
    reqs_f = _requests(cfg, 3, seed=13)
    eng_j = CognitiveEngine(params, cfg, batch=2)
    eng_f = CognitiveEngine(params, cfg, get_isp_config("fused"), batch=2)
    done_j = sorted(eng_j.run_to_completion(reqs_j), key=lambda r: r.rid)
    done_f = sorted(eng_f.run_to_completion(reqs_f), key=lambda r: r.rid)
    assert len(done_f) == len(done_j) == 3
    for a, b in zip(done_f, done_j):
        np.testing.assert_array_equal(np.asarray(a.result.control),
                                      np.asarray(b.result.control))
        np.testing.assert_array_equal(np.asarray(a.result.raw_pred),
                                      np.asarray(b.result.raw_pred))
        np.testing.assert_allclose(np.asarray(a.result.rgb),
                                   np.asarray(b.result.rgb), atol=1e-6)
        for s, d in a.result.stage_params.items():
            for k, v in d.items():
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(b.result.stage_params[s][k]))
    # the fused engine keeps the single-executable discipline
    assert eng_f._step._cache_size() == 1

    # unregistered ISP backends are rejected at construction
    with pytest.raises(ValueError, match="unknown ISP backend"):
        CognitiveEngine(params, cfg, ISPConfig(backend="no_such"))


# ---------------------------------------------------------------------------
# Per-tick staging / tune-resolution overhead (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_staging_bank_tuple_is_prebuilt(setup):
    """``as_tuple()`` returns one of two tuples built once (slots
    mutate in place): ``(slab,)``, or ``(slab, voxels)`` once a slot
    staged voxels — the upload arguments are never rebuilt on the
    per-tick path."""
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=2)
    t0 = eng.staging.as_tuple()
    assert eng.staging.as_tuple() is t0
    assert len(t0) == 1 and t0[0] is eng.staging.slab
    assert eng.submit(_requests(cfg, 1, seed=17)[0])
    t1 = eng.staging.as_tuple()            # staging mutated in place
    assert eng.staging.as_tuple() is t1
    assert t1[0] is eng.staging.slab and t1[1] is eng.staging.voxels
    assert bool(np.any(t1[1]))
    eng.tick()                             # every slot recycled
    assert eng.staging.as_tuple() is t0


def test_engine_pallas_tick_pinned_table_no_retrace(setup):
    """The engine snapshots the active tune table ONCE at construction
    and the tick body resolves against that snapshot: a tuned pallas
    engine (fused whole-backbone segments) serves every tick from ONE
    executable, a mid-serving ``set_table`` swap neither retraces nor
    changes results, and the output stays bit-equal to the jnp
    engine."""
    import dataclasses
    from repro.configs.base import TuneConfig
    from repro.core.npu import npu_forward
    from repro.kernels import tune
    from repro.kernels.tune import TuningTable

    cfg, params = setup
    cfg_p = dataclasses.replace(cfg, backend="pallas")
    reqs = _requests(cfg, 4, seed=19)
    vox = jnp.stack([r.voxels for r in reqs[:2]], axis=1)

    table = TuningTable()
    smoke = TuneConfig(name="test", reps=1, prune_to=2, max_candidates=64)
    with tune.tuning(table, smoke):
        npu_forward(params, vox, cfg_p)
    seg_keys = [k for k in table.entries if k.startswith("backbone_seg|")]
    assert seg_keys                        # the sweep saw fused segments
    for k in seg_keys:
        table.entries[k].update(fused=True, gate="inline", bm=128)

    eng_j = CognitiveEngine(params, cfg, batch=2)
    done_j = sorted(eng_j.run_to_completion(_requests(cfg, 4, seed=19)),
                    key=lambda r: r.rid)

    tune.set_table(table)
    try:
        eng_p = CognitiveEngine(params, cfg_p, batch=2)
        assert eng_p.core._tune_table is table   # hoisted at construction
        assert eng_p.submit(reqs[0]) and eng_p.submit(reqs[1])
        first = eng_p.tick()
        # mid-serving swap: the traced executable keeps serving the
        # construction-time snapshot — no retrace, no numeric change
        tune.set_table(None)
        assert eng_p.submit(reqs[2]) and eng_p.submit(reqs[3])
        second = eng_p.tick()
    finally:
        tune.set_table(None)
    assert eng_p._step._cache_size() == 1  # zero retraces across ticks
    done_p = sorted(first + second, key=lambda r: r.rid)
    assert [r.rid for r in done_p] == [r.rid for r in done_j]
    for a, b in zip(done_p, done_j):
        np.testing.assert_array_equal(np.asarray(a.result.rgb),
                                      np.asarray(b.result.rgb))
        np.testing.assert_array_equal(np.asarray(a.result.control),
                                      np.asarray(b.result.control))


def test_cognitive_step_shim_still_works(setup):
    cfg, params = setup
    scene = make_scene_batch(jax.random.PRNGKey(9), batch=2,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    out = cognitive_step(params, vox, scene.bayer, cfg)
    assert out.rgb.shape == (2, cfg.height, cfg.width, 3)
    assert out.isp_params.gamma.shape == (2,)   # legacy NamedTuple kept
