"""Backend-parity suite for the activity-gated spike-conv hot path
(ISSUE 5): spike-im2col lowering + occupancy-gated Pallas kernels.

Contract: forward computes the jnp reference formulation
(``spike_conv_jnp`` — same K-blocked im2col accumulation / tap-loop
order the kernel grids walk): bit-exact where XLA keeps that order in
the interpreter, to f32 rounding in the sparsity fuzz; allclose vs the
lax.conv SAME oracle,
and gradients match the jnp path to <= 1e-5 relative.  Gating must
never change values: a skipped tile's would-be contribution is exact
zeros, fuzzed over the whole sparsity range 0%..100%.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SNN_ARCHS, reduced_snn
from repro.core.layers import (SPIKE_CONV_BLOCK, _conv2d,
                               apply_spiking_conv, init_spiking_conv,
                               spike_conv_jnp, spike_im2col)
from repro.core.npu import init_npu, npu_forward
from repro.core.sparsity import SparsityTape, tile_skip_fraction
from repro.kernels import ops
from repro.kernels.spike_conv import BK, occupancy_mask

RNG = np.random.default_rng(11)

GATES = ("mask", "inline", "none")


def _maxrel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def _spikes(shape, density=0.15):
    return jnp.asarray((RNG.random(shape) < density).astype(np.float32))


def _w(kh, kw, cin, cout):
    return jnp.asarray(RNG.normal(0, 1, (kh, kw, cin, cout))
                       .astype(np.float32))


def test_k_block_matches_kernel_bk():
    """The jnp reference's K-block IS the kernel's bk — the bit-parity
    contract of the K-blocked accumulation."""
    assert SPIKE_CONV_BLOCK == BK


# ---------------------------------------------------------------------------
# layer-level parity: normal / strided / depthwise / 1x1, ragged dims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cin,cout,k,stride,depthwise", [
    (2, 16, 3, 1, False),     # stem shape (voxel input)
    (20, 24, 3, 2, False),    # strided, ragged non-tile-multiple channels
    (24, 8, 1, 1, False),     # 1x1 (densenet transition / mobilenet pw)
    (40, 40, 3, 1, False),    # K = 360 > 2 K-blocks: multi-step K grid
    (12, 12, 3, 2, True),     # strided depthwise
    (40, 40, 3, 1, True),     # depthwise, ragged channels
])
@pytest.mark.parametrize("gate", GATES)
def test_spike_conv_op_bitexact(cin, cout, k, stride, depthwise, gate):
    """Bit-exact vs the shared jnp formulation under every gate mode
    (odd 13x17 frames exercise SAME padding + ragged M tiles)."""
    xf = _spikes((5, 13, 17, cin))
    w = _w(k, k, 1 if depthwise else cin, cin if depthwise else cout)
    got = ops.spike_conv_op(xf, w, stride=stride, depthwise=depthwise,
                            gate=gate)
    want = jax.jit(lambda x, w: spike_conv_jnp(
        x, w, stride=stride, depthwise=depthwise))(xf, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the formulation itself agrees with the textbook SAME conv
    oracle = _conv2d(xf, w, stride, depthwise, cin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               atol=1e-5)


def test_spike_conv_all_zero_skips_everything():
    """100% sparsity: every tile is gated off and the output must be
    exact zeros (the event-driven 'silence costs nothing' case)."""
    xf = jnp.zeros((3, 8, 8, 4))
    w = _w(3, 3, 4, 8)
    for depthwise in (False, True):
        wd = _w(3, 3, 1, 4) if depthwise else w
        y = ops.spike_conv_op(xf, wd, depthwise=depthwise)
        np.testing.assert_array_equal(np.asarray(y), 0.0)
        assert float(ops.spike_conv_tile_skip(
            xf, wd, depthwise=depthwise)) == 1.0


def test_spike_conv_all_one_dense():
    """0% sparsity: nothing skips, parity must still hold."""
    xf = jnp.ones((3, 8, 8, 4))
    w = _w(3, 3, 4, 8)
    got = ops.spike_conv_op(xf, w)
    want = jax.jit(lambda x, w: spike_conv_jnp(x, w))(xf, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(ops.spike_conv_tile_skip(xf, w)) == 0.0


def test_spike_conv_rejects_unknown_gate():
    with pytest.raises(ValueError, match="gate"):
        ops.spike_conv_op(jnp.zeros((1, 4, 4, 2)), _w(3, 3, 2, 4),
                          gate="typo")


def test_occupancy_mask_granularity():
    """One live spike marks exactly its (row-block, K-block) tile."""
    patches = jnp.zeros((300, 200)).at[131, 140].set(1.0)
    occ = np.asarray(occupancy_mask(patches))
    assert occ.shape == (3, 2)            # ceil(300/128), ceil(200/128)
    want = np.zeros((3, 2), np.int32)
    want[1, 1] = 1
    np.testing.assert_array_equal(occ, want)


# ---------------------------------------------------------------------------
# gradients: custom-VJP vs autodiff through the jnp formulation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depthwise", [False, True])
def test_spike_conv_grad_parity(depthwise):
    cin = 12
    xf = _spikes((4, 11, 13, cin), 0.2)
    w = _w(3, 3, 1 if depthwise else cin, 20 if not depthwise else cin)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.sin(
            fn(x, w, stride=2, depthwise=depthwise)))

    g_p = jax.grad(loss(lambda x, w, **kw: ops.spike_conv_op(x, w, **kw)),
                   argnums=(0, 1))(xf, w)
    g_j = jax.grad(loss(spike_conv_jnp), argnums=(0, 1))(xf, w)
    for got, want in zip(g_p, g_j):
        assert _maxrel(got, want) <= 1e-5
    assert float(jnp.sum(jnp.abs(g_p[1]))) > 0


def test_apply_spiking_conv_backend_grad_parity():
    """Full layer (conv + norm + LIF surrogate) through both backends."""
    cfg_j = reduced_snn("spiking_vgg")
    cfg_p = dataclasses.replace(cfg_j, backend="pallas")
    p = init_spiking_conv(jax.random.PRNGKey(0), 2, 8)
    x = _spikes((3, 2, 16, 16, 2), 0.2)
    wv = jnp.asarray(RNG.normal(0, 1, (3, 2, 16, 16, 8)).astype(np.float32))

    def loss(cfg):
        return lambda p, x: jnp.sum(apply_spiking_conv(p, x, cfg) * wv)

    g_p = jax.jit(jax.grad(loss(cfg_p)))(p, x)
    g_j = jax.jit(jax.grad(loss(cfg_j)))(p, x)
    rel = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_maxrel, g_p, g_j)))
    assert rel <= 1e-5


# ---------------------------------------------------------------------------
# whole-backbone parity: the acceptance bar, all four backbones
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SNN_ARCHS))
def test_npu_forward_conv_backend_bitexact(name):
    """npu_forward bit-exact jnp vs pallas with the gated conv path on
    every backbone (normal + strided + depthwise + 1x1 covered by the
    four architectures)."""
    cfg_j = reduced_snn(name)
    cfg_p = reduced_snn(name, backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg_j)
    vox = _spikes((cfg_j.time_steps, 2, cfg_j.height, cfg_j.width,
                   cfg_j.in_channels), 0.1)
    out_j = jax.jit(lambda p, v: npu_forward(p, v, cfg_j))(params, vox)
    out_p = jax.jit(lambda p, v: npu_forward(p, v, cfg_p))(params, vox)
    np.testing.assert_array_equal(np.asarray(out_p.raw_pred),
                                  np.asarray(out_j.raw_pred))
    np.testing.assert_array_equal(np.asarray(out_p.control),
                                  np.asarray(out_j.control))
    np.testing.assert_array_equal(np.asarray(out_p.sparsity),
                                  np.asarray(out_j.sparsity))


def test_npu_forward_mobilenet_grad_parity():
    """BPTT through the depthwise-heavy backbone on the kernel path
    (test_lif_backend covers spiking_yolo)."""
    cfg_j = reduced_snn("spiking_mobilenet")
    cfg_p = reduced_snn("spiking_mobilenet", backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg_j)
    vox = _spikes((cfg_j.time_steps, 2, cfg_j.height, cfg_j.width,
                   cfg_j.in_channels), 0.1)

    def loss(p, cfg):
        out = npu_forward(p, vox, cfg)
        return jnp.sum(jnp.sin(out.raw_pred)) + jnp.sum(out.control)

    g_p = jax.jit(jax.grad(lambda p: loss(p, cfg_p)))(params)
    g_j = jax.jit(jax.grad(lambda p: loss(p, cfg_j)))(params)
    rel = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_maxrel, g_p, g_j)))
    assert rel <= 1e-5


# ---------------------------------------------------------------------------
# sparsity fuzz: gating is value-neutral at EVERY sparsity level
# ---------------------------------------------------------------------------

try:                   # only the fuzz test needs hypothesis (CI dep);
    import hypothesis  # the rest of this module must run without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _sparsity_parity_case(density, seed):
    r = np.random.default_rng(seed)
    xf = jnp.asarray((r.random((2, 6, 7, 5)) < density)
                     .astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (3, 3, 5, 9)).astype(np.float32))
    got = ops.spike_conv_op(xf, w)
    want = np.asarray(jax.jit(lambda x, w: spike_conv_jnp(x, w))(xf, w))
    # XLA may order the canonical-block sums differently from the
    # kernel's loop: the two agree to f32 rounding of the K-term sums,
    # which scales with the output's magnitude
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(want).max()))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(density=st.floats(min_value=0.0, max_value=1.0),
           seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
    def test_spike_conv_parity_over_sparsity_levels(density, seed):
        """Fuzz sparsity 0%..100% (the extremes included by the float
        strategy): gated forward matches the jnp reference to float
        rounding."""
        _sparsity_parity_case(density, seed)
else:
    @pytest.mark.parametrize("density", [0.0, 0.03, 0.3, 1.0])
    def test_spike_conv_parity_over_sparsity_levels(density):
        """Deterministic fallback sweep when hypothesis is absent."""
        _sparsity_parity_case(density, 1234)


# ---------------------------------------------------------------------------
# tile_skip_fraction: honest ragged-tail accounting
# ---------------------------------------------------------------------------

def test_tile_skip_fraction_counts_ragged_tail():
    """The non-tile-multiple remainder is a partial tile, not silently
    dropped: 130 elements = 2 tiles; a live tail makes it 1/2 skipped
    (the old flat[:n] truncation reported 1/1)."""
    x = jnp.zeros((130,)).at[129].set(1.0)
    assert float(tile_skip_fraction(x, tile=128)) == 0.5
    # silent tail counts as a skippable (zero-padded) tile
    assert float(tile_skip_fraction(jnp.zeros((130,)), tile=128)) == 1.0
    # exact multiples unchanged
    assert float(tile_skip_fraction(jnp.ones((256,)), tile=128)) == 0.0
    # sub-tile inputs are one partial tile
    assert float(tile_skip_fraction(jnp.zeros((7,)), tile=128)) == 1.0


# ---------------------------------------------------------------------------
# SparsityTape through npu_forward / the engine (collect_sparsity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_npu_forward_collect_sparsity(backend):
    cfg = reduced_snn("spiking_yolo", backend=backend)
    params = init_npu(jax.random.PRNGKey(1), cfg)
    vox = _spikes((cfg.time_steps, 2, cfg.height, cfg.width,
                   cfg.in_channels), 0.1)
    fwd = jax.jit(lambda p, v: npu_forward(p, v, cfg,
                                           collect_sparsity=True))
    out = fwd(params, vox)
    rates = out.layer_rates
    assert rates is not None
    # backbone convs + head conv + ctrl_hidden, tagged by param name
    assert {"d0", "f0", "d1", "f1", "head_conv",
            "ctrl_hidden"} <= set(rates)
    assert "network_sparsity" in rates
    for k, v in rates.items():
        assert 0.0 <= float(v) <= 1.0, (k, float(v))
    # default path carries no extra outputs
    assert npu_forward(params, vox, cfg).layer_rates is None


def test_npu_forward_sparsity_backend_invariant():
    """Per-layer rates are derived from bit-exact spike tensors, so
    they must match across backends exactly."""
    cfg_j = reduced_snn("spiking_vgg")
    cfg_p = reduced_snn("spiking_vgg", backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg_j)
    vox = _spikes((cfg_j.time_steps, 2, cfg_j.height, cfg_j.width,
                   cfg_j.in_channels), 0.1)
    r_j = jax.jit(lambda p, v: npu_forward(
        p, v, cfg_j, collect_sparsity=True))(params, vox).layer_rates
    r_p = jax.jit(lambda p, v: npu_forward(
        p, v, cfg_p, collect_sparsity=True))(params, vox).layer_rates
    assert set(r_j) == set(r_p)
    for k in r_j:
        np.testing.assert_array_equal(np.asarray(r_j[k]),
                                      np.asarray(r_p[k]))


def test_engine_reports_sparsity():
    from repro.data.synthetic import make_scene_batch
    from repro.core.encoding import voxel_batch
    from repro.serve.cognitive_engine import (CognitiveEngine,
                                              PerceptionRequest)
    cfg = reduced_snn("spiking_yolo")
    params = init_npu(jax.random.PRNGKey(1), cfg)
    scene = make_scene_batch(jax.random.PRNGKey(3), batch=2,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    eng = CognitiveEngine(params, cfg, batch=2, collect_sparsity=True)
    for i in range(2):
        eng.submit(PerceptionRequest(rid=i, voxels=vox[:, i],
                                     bayer=scene.bayer[i]))
    done = eng.tick()
    assert len(done) == 2
    for r in done:
        assert r.result.sparsity is not None
        assert "network_sparsity" in r.result.sparsity
        assert 0.0 <= r.result.sparsity["network_sparsity"] <= 1.0
    # off by default: no telemetry outputs in the tick executable
    eng0 = CognitiveEngine(params, cfg, batch=1)
    eng0.submit(PerceptionRequest(rid=9, voxels=vox[:, 0],
                                  bayer=scene.bayer[0]))
    assert eng0.tick()[0].result.sparsity is None


def test_sparsity_tape_summary():
    tape = SparsityTape()
    tape.record("a", jnp.asarray([0.0, 1.0]))
    tape.record("b", jnp.zeros((4,)))
    s = tape.summary()
    assert s["a"] == 0.5 and s["b"] == 0.0
    assert s["network_sparsity"] == 0.75


# ---------------------------------------------------------------------------
# the direct path: canonical K blocks built in VMEM from the activation
# ---------------------------------------------------------------------------

@pytest.fixture
def patch_path(monkeypatch):
    """Turns the direct path off (the shape rule says no to every conv)
    for the rest of the test.  Jit caches are cleared at each change of
    rule, so no trace made with one rule serves the other, and the
    trace-time tally sees every conv the test runs."""
    def off():
        jax.clear_caches()
        monkeypatch.setattr(ops, "direct_conv_rule", lambda *a: False)
    jax.clear_caches()
    yield off
    jax.clear_caches()


def _fill(kind, shape):
    if kind == "zeros":
        return jnp.zeros(shape)
    if kind == "ones":
        return jnp.ones(shape)
    return _spikes(shape, 0.2)


# (cin, stride, H, W): odd extents, with Wo a multiple of 8
DIRECT_SHAPES = [(2, 1, 13, 16), (2, 2, 13, 15), (32, 1, 9, 8),
                 (32, 2, 15, 15), (64, 1, 7, 16), (64, 2, 13, 15),
                 (128, 1, 5, 8), (128, 2, 9, 15)]


@pytest.mark.parametrize("cin,stride,H,W", DIRECT_SHAPES)
@pytest.mark.parametrize("gate", GATES)
def test_spike_conv_direct_bitexact(cin, stride, H, W, gate, patch_path):
    """The direct kernel is bit-identical to the jnp formulation and to
    the patch path, under every gate, on sparse, silent and saturated
    inputs."""
    w = _w(3, 3, cin, 8)
    xs = [_fill(k, (3, H, W, cin)) for k in ("spikes", "zeros", "ones")]
    ops.reset_conv_paths()
    got = [ops.spike_conv_op(x, w, stride=stride, gate=gate) for x in xs]
    assert set(ops.conv_paths().values()) == {"direct"}
    ref = jax.jit(lambda x, w: spike_conv_jnp(x, w, stride=stride))
    for x, y in zip(xs, got):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref(x, w)))
    patch_path()
    ops.reset_conv_paths()
    for x, y in zip(xs, got):
        via_patches = ops.spike_conv_op(x, w, stride=stride, gate=gate)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(via_patches))
    assert set(ops.conv_paths().values()) == {"patches"}


@pytest.mark.parametrize("cin,stride,H,W", DIRECT_SHAPES[1:7:2]
                         + DIRECT_SHAPES[6:7])
def test_spike_conv_direct_grad_parity(cin, stride, H, W, patch_path):
    """The direct path's custom VJP (residual: the activation) gives the
    patch path's gradients, and the jnp path's to float rounding."""
    xf = _spikes((2, H, W, cin), 0.2)
    w = _w(3, 3, cin, 8)
    wv = jnp.asarray(RNG.normal(
        0, 1, (2, -(-H // stride), -(-W // stride), 8)).astype(np.float32))

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w, stride=stride) * wv)

    assert ops.direct_conv_rule(xf.shape, w.shape, stride, False)
    g_d = jax.grad(loss(ops.spike_conv_op), argnums=(0, 1))(xf, w)
    g_j = jax.grad(loss(spike_conv_jnp), argnums=(0, 1))(xf, w)
    patch_path()
    g_p = jax.grad(loss(ops.spike_conv_op), argnums=(0, 1))(xf, w)
    for d, p, j in zip(g_d, g_p, g_j):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(p))
        assert _maxrel(d, j) <= 1e-5


def test_conv_path_tally_yolo64():
    """At the registered Spiking-YOLO (64x64, T=5, 32 to 256 channels)
    and tick batch 8, the shape rule sends exactly d0 to f2 down the
    direct path; d3, f3, the head's 3x3 conv (f3's shape) and its 1x1
    readout (Wo = 4) keep the patch matrix."""
    from repro.core.backbones import yolo_specs
    from repro.kernels.backbone_fuse import layer_out_hw
    cfg = dataclasses.replace(SNN_ARCHS["spiking_yolo"], backend="pallas")
    B = 8
    params = init_npu(jax.random.PRNGKey(0), cfg)
    vox = jax.ShapeDtypeStruct((cfg.time_steps, B, cfg.height, cfg.width,
                                cfg.in_channels), jnp.float32)
    jax.clear_caches()                  # the tally counts traces
    ops.reset_conv_paths()
    jax.eval_shape(lambda p, v: npu_forward(p, v, cfg), params, vox)
    paths = ops.conv_paths()
    jax.clear_caches()
    F, h, w = B * cfg.time_steps, cfg.height, cfg.width
    want = {}
    for s in yolo_specs(cfg):
        key = f"spike_conv|C{s.cin},F{F},H{h},O{s.cout},W{w},k3,s{s.stride}"
        want[key] = "direct" if s.name in ("d0", "f0", "d1", "f1", "d2",
                                           "f2") else "patches"
        h, w = layer_out_hw(s, h, w)
    _, _, cin, cout = params["head"]["pred"]["w"].shape
    want[f"spike_conv|C{cin},F{F},H{h},O{cout},W{w},k1,s1"] = "patches"
    assert paths == want


def test_serving_tick_direct_path_bit_identical(patch_path):
    """A serving tick's detections, control vector and frame, on the
    pallas backend in the interpreter, are bit-identical with the direct
    path on and off, and to the same tick on the jnp backend."""
    from repro.configs.base import FleetConfig
    from repro.data.synthetic import make_scene_batch
    from repro.serve.cognitive_engine import PerceptionRequest
    from repro.serve.fleet import FleetEngine
    cfg_j = reduced_snn("spiking_yolo")
    cfg = reduced_snn("spiking_yolo", backend="pallas")
    params = init_npu(jax.random.PRNGKey(5), cfg)
    scene = make_scene_batch(jax.random.PRNGKey(6), batch=2,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps, n_events=2048)

    def tick(c):
        fleet = FleetEngine(params, c, fleet_cfg=FleetConfig(
            batch=2, max_queue=4, double_buffer=False))
        done = fleet.run_to_completion([PerceptionRequest(
            rid=i, events=jax.tree_util.tree_map(lambda a: a[i],
                                                 scene.events),
            bayer=scene.bayer[i]) for i in range(2)])
        return [(np.asarray(s.request.result.raw_pred),
                 np.asarray(s.request.result.control),
                 np.asarray(s.request.result.rgb))
                for s in sorted(done, key=lambda s: s.rid)]

    ops.reset_conv_paths()
    on = tick(cfg)
    assert "direct" in ops.conv_paths().values()
    ref = tick(cfg_j)
    patch_path()
    ops.reset_conv_paths()
    off = tick(cfg)
    assert "direct" not in ops.conv_paths().values()
    for a, b, r in zip(on, off, ref):
        for x, y, z in zip(a, b, r):
            np.testing.assert_array_equal(x, y)
            np.testing.assert_array_equal(x, z)
