"""Kernel-autotuner suite (ISSUE 8): tuning invariance + dispatch.

Three contracts:

1. TUNING INVARIANCE — every launch config the tuner can pick (block
   shapes, gate modes, the fused conv→LIF variant) produces BIT-EXACT
   forwards vs the shared jnp formulation and grads within 1e-5
   relative: sweeping is a pure performance decision, never a numerics
   decision (the canonical sub-block accumulation of
   ``repro.kernels.blocks`` is what makes this possible).
2. DISPATCH STABILITY — configs resolve at trace time through an lru
   cache, so repeated dispatch of the same shape reuses ONE executable
   (no retrace), and table swaps take effect on the next call.
3. TABLE LIFECYCLE — sweep-on-first-eager-call records winners; tables
   round-trip through JSON and invalidate wholesale on a schema or
   kernels_version mismatch.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TuneConfig
from repro.configs.registry import SNN_ARCHS, TUNE_CONFIGS, reduced_snn
from repro.core.layers import (SPIKE_CONV_BLOCK, apply_spiking_conv,
                               blocked_matmul, init_spiking_conv,
                               spike_conv_jnp)
from repro.core.npu import init_npu, npu_forward
from repro.kernels import ops, tune
from repro.kernels.blocks import (CANONICAL_K_BLOCK, canonical_k_slices,
                                  validate_bk)
from repro.kernels.tune import LaunchConfig, TuningTable, shape_key

RNG = np.random.default_rng(21)

SMOKE_TUNE = TuneConfig(name="test", reps=1, prune_to=2,
                        max_candidates=64)


def _maxrel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def _spikes(shape, density=0.12):
    return jnp.asarray((RNG.random(shape) < density).astype(np.float32))


def _w(kh, kw, cin, cout):
    return jnp.asarray(RNG.normal(0, 1, (kh, kw, cin, cout))
                       .astype(np.float32))


@pytest.fixture(autouse=True)
def _reset_tables():
    """Every test starts and ends on the untuned defaults — no test
    may leak a table into another (or into the rest of the suite)."""
    with tune.off():
        yield


# ---------------------------------------------------------------------------
# blocks.py: the centralized bit-parity constants (satellite 1)
# ---------------------------------------------------------------------------

def test_canonical_block_is_the_shared_source_of_truth():
    from repro.kernels.spike_conv import BK
    assert SPIKE_CONV_BLOCK == CANONICAL_K_BLOCK == BK


def test_validate_bk():
    assert validate_bk(128) == 128
    assert validate_bk(512) == 512
    for bad in (0, -128, 64, 192):
        with pytest.raises(ValueError, match="canonical"):
            validate_bk(bad)


def test_canonical_k_slices():
    assert canonical_k_slices(128) == [(0, 128)]
    assert canonical_k_slices(384) == [(0, 128), (128, 256), (256, 384)]


# ---------------------------------------------------------------------------
# tuning invariance: bit-exact forward across the FULL swept space
# ---------------------------------------------------------------------------

# K = 3*3*40 = 360 (3 canonical blocks) exercises multi-sub-block
# launch K-steps; M and N are deliberately ragged.
_X = _spikes((5, 9, 11, 40))
_W = _w(3, 3, 40, 24)
_REF = jax.jit(spike_conv_jnp)(_X, _W)


@pytest.mark.parametrize("gate", ["mask", "inline", "none"])
@pytest.mark.parametrize("bm,bk,bn", [
    (128, 128, 128), (128, 256, 128), (256, 128, 256),
    (256, 256, 128), (128, 512, 256),
])
def test_conv_bitexact_across_swept_space(gate, bm, bk, bn):
    """Every (block shape, gate) candidate the tuner can pick computes
    the identical bits — launch bk only changes gating granularity,
    the canonical sub-block loop keeps the accumulation order."""
    got = ops._spike_conv_impl(_X, _W, stride=1, depthwise=False,
                               gate=gate, bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_REF))


@pytest.mark.parametrize("bm,bk,bn", [(128, 128, 128), (256, 256, 256)])
def test_spike_matmul_bitexact_across_blocks(bm, bk, bn):
    x = _spikes((300, 260))
    w = jnp.asarray(RNG.normal(0, 1, (260, 70)).astype(np.float32))
    got = ops._spike_matmul_jit(x, w, bm=bm, bk=bk, bn=bn)
    want = blocked_matmul(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_n", [256, 1024, 2048])
def test_lif_scan_bitexact_across_blocks(block_n):
    from repro.core.lif import lif_scan
    cur = jnp.asarray(RNG.normal(0, 1, (4, 530)).astype(np.float32))
    got = ops._lif_scan_jit(cur, tau=2.0, v_th=1.0, v_reset=0.0,
                            beta=4.0, block_n=block_n)
    want = lif_scan(cur, tau=2.0, v_th=1.0, v_reset=0.0, beta=4.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grad_parity_across_swept_space():
    """Grads through tuned block shapes match the jnp path <= 1e-5 —
    the custom VJP is block-shape independent by construction, so one
    non-default config suffices alongside the default-covered tests."""
    def loss(fn):
        return lambda x, w: jnp.sum(jnp.sin(fn(x, w)))

    g_t = jax.grad(loss(lambda x, w: ops._spike_conv_impl(
        x, w, stride=1, depthwise=False, gate="inline", bm=256, bk=256,
        bn=128)), argnums=(0, 1))(_X, _W)
    g_j = jax.grad(loss(spike_conv_jnp), argnums=(0, 1))(_X, _W)
    for got, want in zip(g_t, g_j):
        assert _maxrel(got, want) <= 1e-5


# ---------------------------------------------------------------------------
# fused conv→LIF: bit-exact layer + backbone parity, grads <= 1e-5
# ---------------------------------------------------------------------------

def _layer_ref(p, x, cfg):
    """The jnp reference layer (conv + norm + affine + LIF)."""
    return apply_spiking_conv(p, x, dataclasses.replace(cfg,
                                                        backend="jnp"))


def _force_fused(cfg_p, p, x, *, gate="mask", bm=128, stride=1):
    """Install a table that routes this layer's shape to the fused
    kernel, then run the pallas layer through it."""
    T, B = x.shape[:2]
    kh, kw, cin, cout = p["w"].shape
    xf = jnp.swapaxes(x, 0, 1).reshape(B * T, *x.shape[2:])
    Ho, Wo = ops._conv_out_hw(xf, kh, kw, stride)
    key = shape_key("conv_lif", T=T, B=B, HW=Ho * Wo, K=kh * kw * cin,
                    N=cout)
    table = TuningTable()
    table.record(key, LaunchConfig(fused=True, gate=gate, bm=bm),
                 1.0, 2.0)
    tune.set_table(table)
    try:
        return apply_spiking_conv(p, x, cfg_p, stride=stride)
    finally:
        tune.set_table(None)


@pytest.mark.parametrize("gate", ["mask", "inline", "none"])
@pytest.mark.parametrize("bm", [128, 256])
def test_fused_conv_lif_layer_bitexact(gate, bm):
    cfg = reduced_snn("spiking_vgg", backend="pallas")
    p = init_spiking_conv(jax.random.PRNGKey(0), 2, 8)
    x = _spikes((3, 2, 16, 16, 2), 0.15)
    got = _force_fused(cfg, p, x, gate=gate, bm=bm)
    want = _layer_ref(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_conv_lif_strided_bitexact():
    cfg = reduced_snn("spiking_vgg", backend="pallas")
    p = init_spiking_conv(jax.random.PRNGKey(2), 6, 10)
    x = _spikes((3, 2, 13, 11, 6), 0.2)
    got = _force_fused(cfg, p, x, stride=2)
    want = apply_spiking_conv(p, x, reduced_snn("spiking_vgg"), stride=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_conv_lif_grad_parity():
    cfg = reduced_snn("spiking_vgg", backend="pallas")
    p = init_spiking_conv(jax.random.PRNGKey(1), 4, 8)
    x = _spikes((3, 2, 12, 12, 4), 0.2)
    wv = jnp.asarray(RNG.normal(0, 1, (3, 2, 12, 12, 8))
                     .astype(np.float32))

    T, B = x.shape[:2]
    key = shape_key("conv_lif", T=T, B=B, HW=12 * 12, K=3 * 3 * 4, N=8)
    table = TuningTable()
    table.record(key, LaunchConfig(fused=True, gate="mask"), 1.0, 2.0)

    def loss(p, x, cfg):
        return jnp.sum(apply_spiking_conv(p, x, cfg) * wv)

    tune.set_table(table)
    try:
        g_f = jax.grad(loss, argnums=(0, 1))(p, x, cfg)
    finally:
        tune.set_table(None)
    g_j = jax.grad(loss, argnums=(0, 1))(
        p, x, dataclasses.replace(cfg, backend="jnp"))
    rel = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_maxrel, g_f, g_j)))
    assert rel <= 1e-5
    assert float(jnp.sum(jnp.abs(g_f[0]["w"]))) > 0


def _fused_table_for(cfg, params, vox):
    """Tune a backbone by sweeping ONLY the fused-vs-not decision:
    install fused winners for every conv_lif shape the forward hits,
    by running a real tuning sweep restricted to 2 candidates."""
    table = TuningTable()
    with tune.tuning(table, SMOKE_TUNE):
        npu_forward(params, vox, cfg)      # eager: tunes layer by layer
    return table


@pytest.mark.parametrize("name", sorted(SNN_ARCHS))
def test_fused_backbone_bitexact(name):
    """Acceptance bar: tuned dispatch (including fused conv→LIF
    winners found by a real sweep) matches the jnp backbone to float
    rounding on all four architectures."""
    cfg_j = reduced_snn(name)
    cfg_p = reduced_snn(name, backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg_j)
    # the test's own draw, whichever tests ran before it: a neuron
    # within float rounding of threshold fires on one path only, and
    # one such flip moves the outputs far beyond rounding
    rng = np.random.default_rng(21)
    vox = jnp.asarray((rng.random((cfg_j.time_steps, 2, cfg_j.height,
                                   cfg_j.width, cfg_j.in_channels)) < 0.1)
                      .astype(np.float32))
    # jit BOTH backends: the comparison must isolate the kernels, and
    # XLA fuses backend-independent glue (densenet's avg-pool reduce)
    # differently under jit than eagerly, a ~5e-7 drift that has
    # nothing to do with the pallas path
    out_j = jax.jit(lambda p, v: npu_forward(p, v, cfg_j))(params, vox)
    table = _fused_table_for(cfg_p, params, vox)
    assert any(k.startswith("conv_lif|") for k in table.entries)
    tune.set_table(table)
    try:
        out_p = jax.jit(lambda p, v: npu_forward(p, v, cfg_p))(params,
                                                               vox)
    finally:
        tune.set_table(None)
    # float rounding apart: XLA may order the canonical-block sums
    # differently from the kernels' loops
    np.testing.assert_allclose(np.asarray(out_p.raw_pred),
                               np.asarray(out_j.raw_pred),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_p.control),
                               np.asarray(out_j.control),
                               rtol=1e-5, atol=1e-5)


def test_fused_backbone_grad_parity():
    """BPTT through a fused-everywhere backbone matches jnp <= 1e-5."""
    cfg_j = reduced_snn("spiking_yolo")
    cfg_p = reduced_snn("spiking_yolo", backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg_j)
    vox = _spikes((cfg_j.time_steps, 2, cfg_j.height, cfg_j.width,
                   cfg_j.in_channels), 0.1)

    def loss(p, cfg):
        out = npu_forward(p, vox, cfg)
        return jnp.sum(jnp.sin(out.raw_pred)) + jnp.sum(out.control)

    table = _fused_table_for(cfg_p, params, vox)
    # pin every tuned conv_lif shape to the FUSED variant so the grad
    # path is exercised regardless of which variant won on wall-clock
    for k in list(table.entries):
        if k.startswith("conv_lif|"):
            e = dict(table.entries[k])
            e.update(fused=True, gate="mask", bm=128)
            table.entries[k] = e
    tune.set_table(table)
    try:
        g_p = jax.jit(jax.grad(lambda p: loss(p, cfg_p)))(params)
    finally:
        tune.set_table(None)
    g_j = jax.jit(jax.grad(lambda p: loss(p, cfg_j)))(params)
    rel = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_maxrel, g_p, g_j)))
    assert rel <= 1e-5


# ---------------------------------------------------------------------------
# hypothesis fuzz: swept configs match the reference at any sparsity
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _fuzz_case(density, seed, bm, bk, gate):
    r = np.random.default_rng(seed)
    xf = jnp.asarray((r.random((2, 6, 7, 33)) < density)
                     .astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (3, 3, 33, 9)).astype(np.float32))
    got = ops._spike_conv_impl(xf, w, stride=1, depthwise=False,
                               gate=gate, bm=bm, bk=bk, bn=128)
    want = np.asarray(jax.jit(spike_conv_jnp)(xf, w))
    # float rounding apart: an f32 sum over K = 297 terms rounds
    # relative to its largest partial sums, so the absolute tolerance
    # scales with the output's magnitude
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(want).max()))


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(density=st.floats(min_value=0.0, max_value=1.0),
           seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
           bm=st.sampled_from([128, 256]),
           bk=st.sampled_from([128, 256]),
           gate=st.sampled_from(["mask", "inline", "none"]))
    def test_swept_parity_fuzz(density, seed, bm, bk, gate):
        _fuzz_case(density, seed, bm, bk, gate)
else:
    @pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
    def test_swept_parity_fuzz(density):
        _fuzz_case(density, 77, 256, 256, "mask")


# ---------------------------------------------------------------------------
# dispatch stability: lru resolve, no retrace, table swap semantics
# ---------------------------------------------------------------------------

def test_repeated_dispatch_reuses_one_executable():
    """The tuner-cache contract: N dispatches of one shape = one trace
    of the inner jit (configs resolve OUTSIDE the jit, and the lru
    makes them stable across calls)."""
    xf = _spikes((4, 8, 8, 6))
    w = _w(3, 3, 6, 12)
    ops.spike_conv_op(xf, w)               # prime
    n0 = ops._spike_conv_impl._cache_size()
    for _ in range(5):
        ops.spike_conv_op(xf, w)
    assert ops._spike_conv_impl._cache_size() == n0


def test_table_swap_changes_dispatch_no_stale_cache():
    """set_table takes effect on the NEXT call — the epoch-keyed
    resolve cache cannot serve the old table's config."""
    dims = dict(M=10, K=20, N=30)
    key = shape_key("spike_conv", **dims)
    assert tune.dispatch("spike_conv", dims) == tune.default_config(
        "spike_conv")
    t = TuningTable()
    t.record(key, LaunchConfig(bm=256, bn=256, bk=256, gate="none"),
             1.0, 2.0)
    tune.set_table(t)
    try:
        got = tune.dispatch("spike_conv", dims)
        assert got == LaunchConfig(bm=256, bn=256, bk=256, gate="none")
    finally:
        tune.set_table(None)
    assert tune.dispatch("spike_conv", dims) == tune.default_config(
        "spike_conv")


def test_off_context_forces_defaults():
    t = TuningTable()
    dims = dict(M=1, K=2, N=3)
    t.record(shape_key("spike_conv", **dims),
             LaunchConfig(bm=256), 1.0, 2.0)
    tune.set_table(t)
    try:
        assert tune.dispatch("spike_conv", dims).bm == 256
        with tune.off():
            assert tune.dispatch("spike_conv", dims) == \
                tune.default_config("spike_conv")
        assert tune.dispatch("spike_conv", dims).bm == 256
    finally:
        tune.set_table(None)


def test_tuning_context_sweeps_once_then_caches():
    xf = _spikes((3, 8, 8, 5))
    w = _w(3, 3, 5, 7)
    want = jax.jit(spike_conv_jnp)(xf, w)
    with tune.tuning(tune_cfg=SMOKE_TUNE) as table:
        out1 = ops.spike_conv_op(xf, w)
        n_after_first = len(table.entries)
        out2 = ops.spike_conv_op(xf, w)
    assert n_after_first == len(table.entries) == 1
    (key,) = table.entries
    assert key.startswith("spike_conv|")
    e = table.entries[key]
    assert e["us"] > 0 and e["default_us"] > 0
    assert e["us"] <= e["default_us"]      # winner never loses to default
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(want))


def test_tuning_under_jit_only_resolves():
    """Traced calls must not try to measure tracers — tuning inside
    jit degrades to plain resolution."""
    xf = _spikes((3, 8, 8, 5))
    w = _w(3, 3, 5, 7)
    with tune.tuning(tune_cfg=SMOKE_TUNE) as table:
        jax.jit(lambda x, w: ops.spike_conv_op(x, w))(xf, w)
    assert table.entries == {}


# ---------------------------------------------------------------------------
# table lifecycle: JSON round-trip + version invalidation
# ---------------------------------------------------------------------------

def test_table_roundtrip_and_invalidation(tmp_path):
    t = TuningTable()
    t.record("spike_conv|K1,M2,N3",
             LaunchConfig(bm=256, bn=128, bk=256, gate="inline"),
             12.5, 40.0)
    p = str(tmp_path / "table.json")
    t.save(p)
    loaded = TuningTable.load(p)
    assert loaded.entries == t.entries
    assert loaded.config_for("spike_conv|K1,M2,N3") == LaunchConfig(
        bm=256, bn=128, bk=256, gate="inline")

    for field, val in (("schema", 999), ("kernels_version", 999)):
        blob = json.loads(open(p).read())
        blob[field] = val
        stale = str(tmp_path / f"stale_{field}.json")
        with open(stale, "w") as f:
            json.dump(blob, f)
        assert TuningTable.load(stale).entries == {}


def test_env_table_chain(tmp_path, monkeypatch):
    dims = dict(M=5, K=6, N=7)
    key = shape_key("spike_conv", **dims)
    t = TuningTable()
    t.record(key, LaunchConfig(bm=256, gate="none"), 1.0, 2.0)
    p = str(tmp_path / "env_table.json")
    t.save(p)
    monkeypatch.setenv("REPRO_TUNE_TABLE", p)
    tune.set_table(None)       # leave the off() fixture's explicit OFF
    try:
        assert tune.dispatch("spike_conv", dims).gate == "none"
    finally:
        monkeypatch.delenv("REPRO_TUNE_TABLE")
        tune.set_table(None)


def test_smoke_env_picks_bounded_config(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_SMOKE", "1")
    assert tune.default_tune_config() == TUNE_CONFIGS["smoke"]
    monkeypatch.delenv("REPRO_TUNE_SMOKE")
    assert tune.default_tune_config() == TUNE_CONFIGS["default"]


# ---------------------------------------------------------------------------
# roofline seeding: the estimate prunes in the right direction
# ---------------------------------------------------------------------------

def test_roofline_estimate_prefers_fewer_grid_steps_in_interpret():
    dims = dict(T=3, B=2, HW=1024, K=72, N=16)
    fused = tune.estimate("conv_lif", dims, LaunchConfig(fused=True))
    unfused = tune.estimate("conv_lif", dims,
                            LaunchConfig(fused=False))
    assert fused < unfused     # B grid steps vs full matmul grid + B


def test_roofline_estimate_discounts_gated_flops():
    dims = dict(M=4096, K=1024, N=1024)
    sparse = tune.estimate("spike_conv", dims, LaunchConfig(),
                           live=0.05, interpret=False)
    dense = tune.estimate("spike_conv", dims,
                          LaunchConfig(gate="none"), live=0.05,
                          interpret=False)
    assert sparse < dense


def test_kernel_launch_estimate_monotone_in_grid():
    from repro.launch.roofline import kernel_launch_estimate
    a = kernel_launch_estimate(1e9, 1e6, grid_steps=10)
    b = kernel_launch_estimate(1e9, 1e6, grid_steps=1000)
    assert b > a
