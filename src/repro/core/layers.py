"""Spiking layers (multi-step mode): conv / depthwise conv / dense + LIF.

Layout: activations are [T, B, H, W, C] (time-major; conv applied to the
folded [T*B, H, W, C] batch so the MXU sees one big conv per layer).
BatchNorm is replaced by a per-channel instance norm + affine
("tdBN"-style) — running statistics across T steps are a training-
stability device from the GPU SNN literature; without it deep spiking
stacks are silent at init.

Backend dispatch (``SNNConfig.backend``): the "jnp" path is the layered
pure-XLA reference; "pallas" routes the hot path through
``repro.kernels.ops`` — the spiking conv kernels for EVERY spiking conv
(normal / strided / depthwise / 1x1: the direct conv where its shape
rule allows, else the activity-gated spike-im2col conv), the fused
norm+affine+LIF kernel after convs, the VMEM-resident LIF scan after
dense layers, and the tile-skip spike matmul for dense layers whose
inputs are spike tensors.  Forward is bit-exact across backends and
both are differentiable — the kernel ops carry surrogate-gradient
custom VJPs.

Bit-parity discipline (same contract as the norm reduce shape of PR 3):
the jnp path deliberately computes each conv in the exact formulation
the kernel blocks use — ``spike_conv_jnp`` lowers to the same
spike-im2col patch matrix and accumulates K in the same
``SPIKE_CONV_BLOCK``-sized chunks the kernel's K-grid walks (a single
[M, K] @ [K, N] dot rounds differently once K exceeds one block), and
depthwise convs accumulate their taps in the same order as the kernel's
tap loop.  The norm statistics likewise reduce in the kernel's
[T, B, HW, C] axis-(0, 2) formulation.  ``_conv2d`` (lax.conv) is kept
as the textbook oracle the parity tests cross-check at allclose
tolerance.

Layers optionally record telemetry into a ``repro.core.sparsity.
SparsityTape`` (``tape=``/``tag=``): traced per-layer spike rates that
ride out of the same jit'd forward (``npu_forward(...,
collect_sparsity=True)``) instead of a second measurement pass.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SNNConfig
from repro.core.lif import lif_scan

BACKENDS = ("jnp", "pallas")


def _check_backend(cfg: SNNConfig) -> bool:
    """True when the kernel backend is selected; raises on typos."""
    if cfg.backend not in BACKENDS:
        raise ValueError(f"SNNConfig.backend must be one of {BACKENDS}, "
                         f"got {cfg.backend!r}")
    return cfg.backend == "pallas"


def _fire(y, cfg: SNNConfig):
    if _check_backend(cfg):
        from repro.kernels.ops import lif_scan_op
        return lif_scan_op(y, tau=cfg.tau_mem, v_th=cfg.v_threshold,
                           v_reset=cfg.v_reset, beta=cfg.surrogate_beta)
    return lif_scan(y, tau=cfg.tau_mem, v_th=cfg.v_threshold,
                    v_reset=cfg.v_reset, beta=cfg.surrogate_beta)


def conv_init(rng, shape, dtype=jnp.float32):
    # shape: [kh, kw, cin, cout]
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(rng, shape, dtype) * (2.0 / fan_in) ** 0.5


def init_spiking_conv(rng, cin: int, cout: int, *, kernel: int = 3,
                      depthwise: bool = False):
    k1, _ = jax.random.split(rng)
    if depthwise:
        w = conv_init(k1, (kernel, kernel, 1, cin))
    else:
        w = conv_init(k1, (kernel, kernel, cin, cout))
    return {"w": w,
            "scale": jnp.ones((cout if not depthwise else cin,)),
            "bias": jnp.zeros((cout if not depthwise else cin,))}


def _conv2d(x, w, stride: int, depthwise: bool, cin: int):
    """Textbook SAME conv (lax.conv) — the semantic oracle the parity
    tests cross-check ``spike_conv_jnp`` against at allclose tolerance;
    no longer on the dispatch path (see module docstring)."""
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=dn,
        feature_group_count=cin if depthwise else 1)


# ---------------------------------------------------------------------------
# Spike-im2col lowering (shared formulation of both backends)
# ---------------------------------------------------------------------------

# K-block of the jnp reference accumulation — imported from the shared
# single source of truth (repro.kernels.blocks, import-light: no jax),
# so it CANNOT diverge from the kernels' canonical accumulation block
# even while the autotuner sweeps launch ``bk`` shapes (every launch
# K-step accumulates in canonical sub-blocks; see blocks.py).
from repro.kernels.blocks import CANONICAL_K_BLOCK as SPIKE_CONV_BLOCK


def blocked_matmul(a, b):
    """[M, K] @ [K, N] accumulated in ``SPIKE_CONV_BLOCK`` K-chunks —
    THE shared bit-parity formulation: the jnp reference conv, the
    Pallas kernels' canonical sub-block loops, and the fused conv→LIF
    backward's rematerialisation all compute exactly this."""
    acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
    for k0 in range(0, a.shape[1], SPIKE_CONV_BLOCK):
        acc = acc + a[:, k0:k0 + SPIKE_CONV_BLOCK] \
            @ b[k0:k0 + SPIKE_CONV_BLOCK]
    return acc


def _same_pads(size: int, k: int, stride: int):
    """XLA SAME padding: (lo, hi, out_size) along one spatial dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2, out


def _patch_slices(xf, kh: int, kw: int, stride: int):
    """The kh·kw SAME-padded strided tap views of xf [N, H, W, C], in
    (kh, kw)-major order, each [N, Ho, Wo, C]."""
    N, H, W, C = xf.shape
    plo_h, phi_h, Ho = _same_pads(H, kh, stride)
    plo_w, phi_w, Wo = _same_pads(W, kw, stride)
    xp = jnp.pad(xf, ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0)))
    taps = [xp[:, i:i + (Ho - 1) * stride + 1:stride,
               j:j + (Wo - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    return taps, (Ho, Wo)


def spike_im2col(xf, kh: int, kw: int, stride: int = 1):
    """Fold a spike tensor xf [N, H, W, C] into the patch matrix
    [N·Ho·Wo, kh·kw·C] (tap-major, channel-minor — matching
    ``w.reshape(kh*kw*cin, cout)``).  Patch rows inherit the activation
    sparsity, which is what the tile-skip matmul kernels gate on."""
    taps, (Ho, Wo) = _patch_slices(xf, kh, kw, stride)
    N, _, _, C = xf.shape
    p = jnp.stack(taps, axis=3)            # [N, Ho, Wo, taps, C]
    return p.reshape(N * Ho * Wo, kh * kw * C), (Ho, Wo)


def dw_patches(xf, kh: int, kw: int, stride: int = 1):
    """Depthwise form: [N·Ho·Wo, taps, C] (channels stay per-tap — a
    block-diagonal matmul would spend C× MACs on structural zeros)."""
    taps, (Ho, Wo) = _patch_slices(xf, kh, kw, stride)
    N, _, _, C = xf.shape
    p = jnp.stack(taps, axis=3)
    return p.reshape(N * Ho * Wo, kh * kw, C), (Ho, Wo)


def spike_conv_jnp(xf, w, *, stride: int = 1, depthwise: bool = False):
    """Pure-jnp reference conv in the kernel's exact formulation.

    xf: [N, H, W, C]; w: [kh, kw, cin, cout] (HWIO; depthwise uses
    [kh, kw, 1, C]) -> [N, Ho, Wo, cout], SAME padding.

    Normal convs: spike-im2col then K accumulated in
    ``SPIKE_CONV_BLOCK`` chunks (the kernel's K-grid).  Depthwise:
    sequential tap-loop accumulation (the kernel's static tap loop).
    Both are bit-exact against the Pallas path and agree with
    ``_conv2d`` (lax.conv SAME) to float rounding.

    Trade: here the patch matrix transiently holds kh·kw copies of the
    activation, bought deliberately for cross-backend bit-parity.  The
    pallas backend's per-op conv builds the same K blocks in VMEM from
    the activation instead (``spike_conv_direct_pallas``) wherever its
    shape rule allows; the convs it does not take, the fused conv→LIF
    kernel and the megakernel still consume a patch matrix.  A
    formulation-free dense conv for memory-constrained jnp-only use
    remains available as ``_conv2d``.
    """
    kh, kw = w.shape[:2]
    N = xf.shape[0]
    if depthwise:
        taps, (Ho, Wo) = _patch_slices(xf, kh, kw, stride)
        wf = w.reshape(kh * kw, -1)
        acc = jnp.zeros((N, Ho, Wo, xf.shape[-1]), jnp.float32)
        for t, xt in enumerate(taps):
            acc = acc + xt * wf[t]
        return acc
    patches, (Ho, Wo) = spike_im2col(xf, kh, kw, stride)
    wmat = w.reshape(kh * kw * w.shape[2], w.shape[3])
    acc = blocked_matmul(patches, wmat)
    return acc.reshape(N, Ho, Wo, wmat.shape[1])


def apply_spiking_conv(p, x, cfg: SNNConfig, *, stride: int = 1,
                       depthwise: bool = False, fire: bool = True,
                       normalize: bool = True, tape=None,
                       tag: Optional[str] = None):
    """x: [T, B, H, W, C] -> spikes [T, B, H', W', C'].

    ``normalize`` applies per-channel instance normalisation over
    (T, H, W) before the LIF — the functional stand-in for the tdBN the
    GPU SNN literature folds into thresholds; without it deep spiking
    stacks are silent at init (currents never cross v_th).

    Backend dispatch: under ``cfg.backend == "pallas"`` the conv itself
    lowers through ``repro.kernels.ops.spike_conv_op`` — the direct
    conv, or spike-im2col into the activity-gated tile-skip matmul
    kernel (tap-loop kernel for depthwise), where all-zero activation
    tiles skip their MXU pass — and the norm+affine+LIF epilogue fuses
    into one
    VMEM-resident kernel.  The jnp path computes the identical
    K-blocked im2col / tap-loop formulation (``spike_conv_jnp``), so
    forward is bit-exact across backends; gating cannot perturb values
    because a skipped tile's contribution is exact zeros.

    ``tape``: optional ``SparsityTape``; when given (and ``fire``) the
    output spike rate is recorded under ``tag`` inside the same traced
    forward.
    """
    T, B, H, W, C = x.shape
    use_kernels = _check_backend(cfg)
    # fold BATCH-major: reshape(T*B, ...) would merge the time dim over
    # the SPMD-sharded batch dim, which GSPMD cannot express — it
    # replicates the whole conv on every chip (256x compute in the
    # dry-run; EXPERIMENTS.md §Perf hillclimb C). (B*T, ...) keeps the
    # merged dim block-sharded by batch.
    xf = jnp.swapaxes(x, 0, 1).reshape(B * T, H, W, C)
    if use_kernels and normalize and fire and not depthwise:
        # the whole layer through one dispatch point: the tuner picks
        # the fused conv→LIF kernel (conv output never leaves VMEM
        # before the norm+affine+LIF epilogue) or the per-op
        # composition, per (op, shape) — see repro.kernels.tune
        from repro.kernels.ops import spike_conv_lif_op
        out = spike_conv_lif_op(xf, p["w"], p["scale"], p["bias"],
                                T=T, B=B, stride=stride,
                                tau=cfg.tau_mem, v_th=cfg.v_threshold,
                                v_reset=cfg.v_reset,
                                beta=cfg.surrogate_beta)
        if tape is not None:
            tape.record(tag or f"conv{len(tape.records)}", out)
        return out
    if use_kernels:
        from repro.kernels.ops import spike_conv_op
        y = spike_conv_op(xf, p["w"], stride=stride, depthwise=depthwise)
    else:
        y = spike_conv_jnp(xf, p["w"], stride=stride, depthwise=depthwise)
    _, Ho, Wo, Co = y.shape
    y = jnp.swapaxes(y.reshape(B, T, Ho, Wo, Co), 0, 1)
    if normalize and fire and use_kernels:
        # depthwise epilogue: stats + affine + T-step recurrence in
        # one VMEM-resident kernel pass
        from repro.kernels.ops import norm_affine_lif_op
        out = norm_affine_lif_op(y, p["scale"], p["bias"],
                                 tau=cfg.tau_mem, v_th=cfg.v_threshold,
                                 v_reset=cfg.v_reset,
                                 beta=cfg.surrogate_beta)
        if tape is not None:
            tape.record(tag or f"conv{len(tape.records)}", out)
        return out
    if normalize:
        # rsqrt(var + eps): jnp.std has a non-finite gradient at zero
        # variance (silent channels on sparse spike inputs).  Reduce on
        # the [T, B, HW, C] view over axes (0, 2) — the same reduce
        # shape the fused kernel's per-batch slabs see, which is what
        # makes the backends bit-exact rather than merely allclose.
        y4 = y.reshape(T, B, Ho * Wo, Co)
        mu = jnp.mean(y4, axis=(0, 2), keepdims=True)
        var = jnp.var(y4, axis=(0, 2), keepdims=True)
        y = ((y4 - mu) * jax.lax.rsqrt(var + 1e-6)).reshape(y.shape)
    y = y * p["scale"] + p["bias"]
    if not fire:
        return y
    out = _fire(y, cfg)
    if tape is not None:
        tape.record(tag or f"conv{len(tape.records)}", out)
    return out


def init_spiking_dense(rng, cin: int, cout: int):
    return {"w": jax.random.normal(rng, (cin, cout)) * (2.0 / cin) ** 0.5,
            "bias": jnp.zeros((cout,))}


def apply_spiking_dense(p, x, cfg: SNNConfig, *, fire: bool = True,
                        spike_input: bool = False, tape=None,
                        tag: Optional[str] = None):
    """x: [T, B, C].  ``spike_input`` marks x as a 0/1 spike tensor
    (i.e. the upstream layer fired), letting the pallas backend route
    the matmul through the tile-skip ``spike_matmul_op`` — the MXU
    granularity of the paper's silent-neurons-cost-nothing claim."""
    if spike_input and _check_backend(cfg):
        from repro.kernels.ops import spike_matmul_op
        T, B, C = x.shape
        y = spike_matmul_op(x.reshape(T * B, C), p["w"])
        y = y.reshape(T, B, -1) + p["bias"]
    else:
        y = x @ p["w"] + p["bias"]
    if not fire:
        return y
    out = _fire(y, cfg)
    if tape is not None:
        tape.record(tag or f"dense{len(tape.records)}", out)
    return out


def max_pool(x, window: int = 2, cfg: Optional[SNNConfig] = None):
    """x: [T, B, H, W, C] (batch-major fold — see apply_spiking_conv).

    With a pallas ``cfg`` on a COMPILED backend the reduction routes
    through the gated Pallas pooling kernel (``repro.kernels.ops.
    max_pool_op`` — an all-silent frame skips its reduction); under the
    interpreter a standalone pool launch is a net loss (per-grid-step
    tax), so reduce_window serves — bit-identical either way (max has
    no rounding).  Fused backbone segments never reach here: pooling is
    absorbed as an in-kernel epilogue (repro.kernels.backbone_fuse)."""
    T, B, H, W, C = x.shape
    xf = jnp.swapaxes(x, 0, 1).reshape(B * T, H, W, C)
    if cfg is not None and _check_backend(cfg):
        from repro.kernels import blocks, ops
        if not blocks.interpret_mode():
            y = ops.max_pool_op(xf, window=window)
            return jnp.swapaxes(
                y.reshape(B, T, H // window, W // window, C), 0, 1)
    y = jax.lax.reduce_window(xf, -jnp.inf, jax.lax.max,
                              (1, window, window, 1),
                              (1, window, window, 1), "VALID")
    return jnp.swapaxes(
        y.reshape(B, T, H // window, W // window, C), 0, 1)
