"""The staging slab: every slot's event window, Bayer frame and
``from_events`` flag in one ``int32`` row, uploaded as one array, with
the voxel slab uploaded only in a tick where a slot staged voxels.

The bank's views write into the slab; the tick's unpacking gives back,
bit for bit, what a per-leaf upload of the same bank gave; and the tick
outputs equal those of the same tick body fed the eight arrays one by
one."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import EncodingConfig
from repro.configs.registry import SNN_ARCHS, reduced_snn
from repro.core.encoding import EventStream, voxel_batch
from repro.core.npu import init_npu
from repro.data.synthetic import make_scene_batch
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest
from repro.serve.engine_core import EngineCore
from repro.serve.transport import SlabLayout, StagingBank, bank_nbytes

BATCH = 2


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_snn("spiking_yolo")
    params = init_npu(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scene(cfg, n, seed):
    return make_scene_batch(jax.random.PRNGKey(seed), batch=n,
                            height=cfg.height, width=cfg.width,
                            time_steps=cfg.time_steps, n_events=2048)


def _stage(bank, cfg, kinds, seed=0):
    """Stage slot ``i`` as ``kinds[i]`` ("events" | "voxels") from one
    synthetic scene batch; returns the bank."""
    scene = _scene(cfg, len(kinds), seed)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    cap = bank.events.t.shape[1] if bank.events is not None else 0
    for i, kind in enumerate(kinds):
        if kind == "voxels":
            bank.stage_voxels(i, vox[:, i], scene.bayer[i])
        else:
            ev = jax.tree_util.tree_map(lambda a: np.asarray(a[i])[:cap],
                                        scene.events)
            bank.stage_events(i, ev, scene.bayer[i])
    return bank


def _per_leaf(bank):
    """The bank's inputs as the per-leaf upload carried them: float32
    frames and event times, int32 coordinates, bool flags."""
    ev = bank.events
    events = None if ev is None else EventStream(
        t=np.array(ev.t), x=np.array(ev.x), y=np.array(ev.y),
        p=np.array(ev.p), valid=np.array(ev.valid, bool))
    return np.array(bank.bayer), events, np.array(bank.from_events, bool)


def test_yolo64_slab_is_padded_to_lane_tiles():
    cfg = SNN_ARCHS["spiking_yolo"]
    layout = SlabLayout(cfg, (64, 64), 2048)
    assert layout.sections == {
        "t": (0, 2048), "x": (2048, 2048), "y": (4096, 2048),
        "p": (6144, 2048), "valid": (8192, 2048), "bayer": (10240, 4096),
        "from_events": (14336, 1)}
    assert layout.words == 14464
    slab, with_voxels = bank_nbytes(cfg, 8, (64, 64), 2048)
    assert slab == 462_848
    assert with_voxels == slab + 4 * 5 * 8 * 64 * 64 * 2


def test_bank_views_write_into_the_slab(setup):
    cfg, _ = setup
    bank = _stage(StagingBank(cfg, BATCH, (cfg.height, cfg.width), 64),
                  cfg, ["events", "voxels"])
    views = [bank.bayer, bank.from_events, *bank.events]
    assert all(np.shares_memory(v, bank.slab) for v in views)
    assert not np.shares_memory(bank.voxels, bank.slab)
    lay = bank.layout
    row = bank.slab[0]
    off, n = lay.sections["bayer"]
    np.testing.assert_array_equal(row[off:off + n].view(np.float32),
                                  bank.bayer[0].ravel())
    off, n = lay.sections["t"]
    np.testing.assert_array_equal(row[off:off + n].view(np.float32),
                                  bank.events.t[0])
    assert row[lay.sections["from_events"][0]] == 1
    assert bank.slab[1, lay.sections["from_events"][0]] == 0
    off, n = lay.sections["valid"]
    assert set(np.unique(row[off:off + n])) <= {0, 1}
    # the padding after the last section stays zero
    assert not bank.slab[:, lay.sections["from_events"][0] + 1:].any()


@pytest.mark.parametrize("kinds", [["events", "events"],
                                   ["voxels", "voxels"],
                                   ["voxels", "events"], "no_dvs"])
def test_unpack_equals_the_per_leaf_upload(setup, kinds):
    cfg, _ = setup
    frame_hw = (cfg.height, cfg.width)
    if kinds == "no_dvs":                   # no event section at all
        cfg, kinds = dataclasses.replace(cfg, in_channels=1), ["voxels"] * 2
        frame_hw = (24, 40)
    bank = StagingBank(cfg, BATCH, frame_hw, 64)
    if cfg.in_channels == 2:
        _stage(bank, cfg, kinds)
    else:
        assert bank.events is None
        assert "t" not in bank.layout.sections
        rng = np.random.default_rng(0)
        for i in range(BATCH):
            bank.stage_voxels(i, rng.random(bank.voxels[:, i].shape),
                              rng.random((24, 40)))
    assert bank.voxels_staged == ("voxels" in kinds)
    bayer, events, flag = jax.device_get(
        jax.jit(bank.layout.unpack)(jax.device_put(bank.slab)))
    want_bayer, want_events, want_flag = _per_leaf(bank)
    for got, want in [(bayer, want_bayer), (flag, want_flag)] + (
            [] if events is None else list(zip(events, want_events))):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert (events is None) == (cfg.in_channels != 2)
    np.testing.assert_array_equal(want_flag, [k == "events" for k in kinds])


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("kinds", [["events", "events"],
                                   ["voxels", "events"]])
def test_tick_outputs_equal_the_per_leaf_tick(setup, monkeypatch, backend,
                                              kinds):
    """The same tick body, fed the eight per-leaf arrays in place of
    the slab, gives bit-identical outputs."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, backend=backend)
    core = EngineCore(params, cfg, batch=BATCH,
                      enc_cfg=EncodingConfig(backend=backend))
    bank = _stage(StagingBank(cfg, BATCH, core.frame_hw,
                              core.enc_cfg.event_capacity), cfg, kinds,
                  seed=5)
    got = core.tick(bank)
    # the per-leaf path: the body's unpacking replaced by the leaves
    leaves = _per_leaf(bank)
    voxels = bank.voxels if bank.voxels_staged else np.zeros_like(
        bank.voxels)
    monkeypatch.setattr(core.layout, "unpack", lambda slab: slab)
    want = jax.device_get(jax.jit(core._step.__wrapped__)(
        core.params, jax.device_put(leaves), jax.device_put(voxels)))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kinds,carries", [(["events", "events"], False),
                                           (["voxels", "events"], True)])
def test_device_put_carries_voxels_only_when_staged(setup, monkeypatch,
                                                    kinds, carries):
    cfg, params = setup
    core = EngineCore(params, cfg, batch=BATCH)
    bank = _stage(StagingBank(cfg, BATCH, core.frame_hw,
                              core.enc_cfg.event_capacity), cfg, kinds)
    calls = []
    real = jax.device_put

    def recording(x, *a, **k):
        calls.append(x)
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", recording)
    core.tick(bank)
    up, = calls
    assert up[0] is bank.slab
    assert (len(up) == 2 and up[1] is bank.voxels) == carries
    assert len(up) == 1 + carries


def test_one_executable_across_events_only_and_mixed_ticks(setup):
    """Events-only, mixed and voxel-only ticks share one executable,
    and the zero voxel buffer survives them: it is never donated."""
    cfg, params = setup
    eng = CognitiveEngine(params, cfg, batch=BATCH)
    scene = _scene(cfg, BATCH, 9)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)

    def req(i, kind):
        if kind == "voxels":
            return PerceptionRequest(rid=i, voxels=vox[:, i],
                                     bayer=scene.bayer[i])
        return PerceptionRequest(
            rid=i, events=jax.tree_util.tree_map(lambda a: a[i],
                                                 scene.events),
            bayer=scene.bayer[i])

    zero = eng.core._zero_voxels
    for kinds in (["events", "events"], ["voxels", "events"],
                  ["events", "events"], ["voxels", "voxels"]):
        for i, kind in enumerate(kinds):
            assert eng.submit(req(i, kind))
        assert eng.staging.voxels_staged == ("voxels" in kinds)
        done = eng.tick()
        assert len(done) == BATCH
        assert not eng.staging.voxels_staged
    assert eng._step._cache_size() == 1
    assert eng.core._zero_voxels is zero and not zero.is_deleted()
    assert not np.asarray(zero).any()
