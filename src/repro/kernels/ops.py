"""Jit'd public wrappers for the Pallas kernels.

Every wrapper passes ``interpret=blocks.interpret_mode()``: the kernels
lower through Mosaic when the default backend is a TPU and run in the
Pallas interpreter everywhere else.  The decision is taken at trace
time, never at import.

The ISP stage registry's "pallas" backend resolves to ``demosaic_op``
and ``nlm_op`` here (lazily, from repro.isp.stages, so the pure-jnp
path never imports Pallas), and the "pallas_fused" streaming backend's
planner (repro.isp.fuse) executes its segments through
``pointwise_segment_op`` / ``stencil_segment_op``.  The SNN stack's "pallas" backend
(``SNNConfig.backend``) resolves to ``spike_conv_op`` (the direct conv,
or the activity-gated spike-im2col conv) / ``norm_affine_lif_op`` /
``lif_scan_op`` / ``spike_matmul_op`` from repro.core.layers.

The spiking ops carry a ``jax.custom_vjp`` whose backward implements
the sigmoid surrogate gradient (BPTT through the LIF recurrence, à la
SpikingJelly), so the kernel-backed forward is legal under training:
``jax.grad`` through a pallas-backend network matches ``jax.grad``
through the jnp reference to float rounding.  Residuals are the raw
inputs; intermediates (membrane trajectory, norm statistics) are
rematerialised in the backward — the FlashAttention trade of recompute
for HBM traffic.

Tuned dispatch (ISSUE 8): the spiking ops are thin Python dispatchers
now, not top-level jits.  Each call builds a shape key, resolves a
``repro.kernels.tune.LaunchConfig`` (lru-cached, pure at trace time —
so repeated traces of the same layer see ONE stable config and reuse
one executable), and calls an inner jit whose static args carry the
launch shapes / gate mode / fusion variant.  The config lookup must
never happen INSIDE a jit body: a jitted table read would bake the
epoch's value into the executable and silently serve stale configs
after a table swap.  Under an active ``tune.tuning()`` context, the
first eager call of an untuned shape runs the measured sweep on that
call's real inputs (real activation sparsity) before dispatching.

``spike_conv_lif_op`` is the fused layer op: the whole spiking-conv
layer (im2col conv + instance-norm + affine + T-step LIF) through one
dispatch point, routed to either the single-kernel fused path
(``spike_conv_lif_pallas`` — one HBM round-trip) or the per-op
composition, per the tuned config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layers import (_same_pads, blocked_matmul, dw_patches,
                               max_pool, spike_conv_jnp, spike_im2col)
from repro.core.lif import lif_scan as lif_scan_ref
from repro.kernels import blocks, tune
from repro.kernels.backbone_fuse import (backbone_segment_pallas,
                                         max_pool_pallas, segment_macs,
                                         segment_activation_elems,
                                         segment_unfused_grid_steps)
from repro.kernels.blocks import CANONICAL_K_BLOCK
from repro.kernels.demosaic import demosaic_pallas
from repro.kernels.event_voxel import event_voxel_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.isp_fused import (pointwise_segment_pallas,
                                     stencil_segment_pallas)
from repro.kernels.lif_scan import lif_scan_pallas, norm_affine_lif_pallas
from repro.kernels.nlm import nlm_pallas
from repro.kernels.spike_conv import (direct_conv_frames, occupancy_mask,
                                      spike_conv_direct_pallas,
                                      spike_conv_lif_pallas,
                                      spike_conv_pallas, spike_dwconv_pallas,
                                      tap_occupancy_mask)
from repro.kernels.spike_matmul import spike_matmul_pallas

NORM_EPS = 1e-6


def _live_fraction(x) -> float:
    """Eager live-activation fraction of a concrete spike tensor — the
    roofline ranking discount the tuner uses (only evaluated on eager
    tuning calls; never inside a trace)."""
    return float(jnp.mean((x != 0).astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=(
    "time_steps", "height", "width", "window", "mode", "oob", "block_t"))
def event_voxel_op(events, *, time_steps: int, height: int, width: int,
                   window: float = 1.0, mode: str = "binary",
                   oob: str = "clip", block_t: int = 0):
    """Batched EventStream ([B, N] leaves) -> voxel grids [B, T, H, W, 2],
    kernel-backed (the ingestion hot path).  Bit-identical to the jnp
    reference ``repro.core.encoding.events_to_voxel_batch``."""
    return event_voxel_pallas(
        events.t.astype(jnp.float32), events.x.astype(jnp.int32),
        events.y.astype(jnp.int32), events.p.astype(jnp.int32),
        events.valid.astype(jnp.int32), time_steps=time_steps,
        height=height, width=width, window=window, mode=mode, oob=oob,
        block_t=block_t, interpret=blocks.interpret_mode())


# ---------------------------------------------------------------------------
# Surrogate-gradient BPTT (shared by the LIF-carrying custom VJPs)
# ---------------------------------------------------------------------------

def _lif_replay(z, *, tau: float, v_th: float, v_reset: float):
    """Re-run the LIF recurrence on currents z [T, ...], returning the
    pre-threshold distances x_t = u_t - v_th and spikes s_t (the
    residuals the surrogate backward needs)."""
    decay = jnp.exp(-1.0 / tau).astype(z.dtype)

    def fstep(u, z_t):
        u = decay * (u - v_reset) + v_reset + z_t
        x = u - v_th
        s = (x >= 0).astype(z.dtype)
        u = u * (1.0 - s) + v_reset * s
        return u, (x, s)

    u0 = jnp.full(z.shape[1:], v_reset, z.dtype)
    _, (xs, ss) = jax.lax.scan(fstep, u0, z, unroll=z.shape[0])
    return xs, ss


def _lif_bwd_scan(g, xs, ss, *, tau: float, v_th: float, v_reset: float,
                  beta: float):
    """Reverse-time BPTT through the LIF recurrence with the sigmoid
    surrogate H'(x) ≈ β·σ(βx)·(1-σ(βx)).  g: dL/d(spikes) [T, ...];
    returns dL/d(currents) [T, ...].

    The spike enters twice — as the output and in the hard reset
    u⁺ = u·(1-s) + v_reset·s — so the adjoint is
      du_t = du⁺·(1-s_t) + (g_t + du⁺·(v_reset - u_t))·σ'  ,
    exactly what jax.grad derives through the reference's custom-vjp
    ``spike``."""
    decay = jnp.exp(-1.0 / tau).astype(g.dtype)

    def bstep(du, inp):
        g_t, x_t, s_t = inp
        u_t = x_t + v_th
        ds = g_t + du * (v_reset - u_t)
        sig = jax.nn.sigmoid(beta * x_t)
        dut = du * (1.0 - s_t) + ds * (beta * sig * (1.0 - sig))
        return dut * decay, dut

    du0 = jnp.zeros_like(g[0])
    _, dz = jax.lax.scan(bstep, du0, (g, xs, ss), reverse=True,
                         unroll=g.shape[0])
    return dz


# ---------------------------------------------------------------------------
# lif_scan_op: kernel forward + surrogate BPTT backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lif_scan(currents, tau, v_th, v_reset, beta, block_n):
    T = currents.shape[0]
    out = lif_scan_pallas(currents.reshape(T, -1), tau=tau, v_th=v_th,
                          v_reset=v_reset, block_n=block_n,
                          interpret=blocks.interpret_mode())
    return out.reshape(currents.shape)


def _lif_scan_fwd(currents, tau, v_th, v_reset, beta, block_n):
    return _lif_scan(currents, tau, v_th, v_reset, beta, block_n), currents


def _lif_scan_bwd(tau, v_th, v_reset, beta, block_n, currents, g):
    xs, ss = _lif_replay(currents, tau=tau, v_th=v_th, v_reset=v_reset)
    dz = _lif_bwd_scan(g, xs, ss, tau=tau, v_th=v_th, v_reset=v_reset,
                       beta=beta)
    return (dz,)


_lif_scan.defvjp(_lif_scan_fwd, _lif_scan_bwd)


@functools.partial(jax.jit, static_argnames=("tau", "v_th", "v_reset",
                                             "beta", "block_n"))
def _lif_scan_jit(currents, *, tau, v_th, v_reset, beta, block_n):
    return _lif_scan(currents, tau, v_th, v_reset, beta, block_n)


def lif_scan_op(currents, tau: float = 2.0, v_th: float = 1.0,
                v_reset: float = 0.0, beta: float = 4.0):
    """currents: [T, ...] -> spikes, kernel-backed + differentiable
    (surrogate BPTT backward).  Folds trailing dims for the kernel;
    the neuron block (``block_n``) is the tuned knob."""
    T = currents.shape[0]
    n_flat = 1
    for d in currents.shape[1:]:
        n_flat *= d
    dims = dict(T=T, N=n_flat)
    runner = None
    live = 1.0
    if tune.tuning_active() and tune.concrete(currents):
        runner = lambda c: _lif_scan_jit(        # noqa: E731
            currents, tau=tau, v_th=v_th, v_reset=v_reset, beta=beta,
            block_n=c.bn)
    cfg = tune.dispatch("lif_scan", dims, runner, live=live)
    return _lif_scan_jit(currents, tau=tau, v_th=v_th, v_reset=v_reset,
                         beta=beta, block_n=cfg.bn)


# ---------------------------------------------------------------------------
# norm_affine_lif_op: fused spiking-conv epilogue + analytic backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_affine_lif(y, scale, bias, tau, v_th, v_reset, beta):
    T, B = y.shape[:2]
    C = y.shape[-1]
    y4 = y.reshape(T, B, -1, C)
    out = norm_affine_lif_pallas(y4, scale, bias, tau=tau, v_th=v_th,
                                 v_reset=v_reset, eps=NORM_EPS,
                                 interpret=blocks.interpret_mode())
    return out.reshape(y.shape)


def _norm_stats(y4):
    """Instance-norm intermediates over (T, HW) per (B, C), in the
    exact reduce formulation both backends share."""
    mu = jnp.mean(y4, axis=(0, 2), keepdims=True)
    var = jnp.var(y4, axis=(0, 2), keepdims=True)
    r = jax.lax.rsqrt(var + NORM_EPS)
    return (y4 - mu) * r, r


def _norm_affine_lif_fwd(y, scale, bias, tau, v_th, v_reset, beta):
    return _norm_affine_lif(y, scale, bias, tau, v_th, v_reset, beta), \
        (y, scale, bias)


def _norm_affine_lif_bwd(tau, v_th, v_reset, beta, res, g):
    y, scale, bias = res
    T, B = y.shape[:2]
    C = y.shape[-1]
    # rematerialise the fused intermediates (norm stats + membrane
    # trajectory) instead of spilling them from the forward kernel
    yhat, r = _norm_stats(y.reshape(T, B, -1, C))
    z = yhat * scale + bias
    xs, ss = _lif_replay(z, tau=tau, v_th=v_th, v_reset=v_reset)
    dz = _lif_bwd_scan(g.reshape(z.shape), xs, ss, tau=tau, v_th=v_th,
                       v_reset=v_reset, beta=beta)
    # affine
    dyhat = dz * scale
    dscale = jnp.sum(dz * yhat, axis=(0, 1, 2))
    dbias = jnp.sum(dz, axis=(0, 1, 2))
    # instance-norm backward (1/N variance):
    #   dy = r · (dyhat - mean(dyhat) - yhat · mean(dyhat · yhat))
    m1 = jnp.mean(dyhat, axis=(0, 2), keepdims=True)
    m2 = jnp.mean(dyhat * yhat, axis=(0, 2), keepdims=True)
    dy4 = r * (dyhat - m1 - yhat * m2)
    return dy4.reshape(y.shape), dscale, dbias


_norm_affine_lif.defvjp(_norm_affine_lif_fwd, _norm_affine_lif_bwd)


@functools.partial(jax.jit, static_argnames=("tau", "v_th", "v_reset",
                                             "beta"))
def norm_affine_lif_op(y, scale, bias, *, tau: float = 2.0,
                       v_th: float = 1.0, v_reset: float = 0.0,
                       beta: float = 4.0):
    """Fused instance-norm + affine + LIF.  y: [T, B, ..., C] pre-norm
    conv output; scale, bias: [C] -> spikes, same shape as y.
    Forward is the single-pass Pallas kernel (bit-exact vs the layered
    jnp path); backward is the analytic surrogate-gradient BPTT."""
    return _norm_affine_lif(y, scale, bias, tau, v_th, v_reset, beta)


# ---------------------------------------------------------------------------
# spike_matmul_op: tile-skip forward + plain matmul backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _spike_matmul(x, w, bm, bk, bn):
    return spike_matmul_pallas(x, w, bm=bm, bk=bk, bn=bn,
                               interpret=blocks.interpret_mode())


def _spike_matmul_fwd(x, w, bm, bk, bn):
    return _spike_matmul(x, w, bm, bk, bn), (x, w)


def _spike_matmul_bwd(bm, bk, bn, res, g):
    x, w = res
    # d/dx is dense (g is not a spike tensor); d/dw contracts over the
    # spike activations — the sparsity the forward exploits lives in x,
    # not in the adjoints, so both sides are plain MXU matmuls
    return g @ w.T, x.T @ g


_spike_matmul.defvjp(_spike_matmul_fwd, _spike_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def _spike_matmul_jit(x, w, *, bm, bk, bn):
    return _spike_matmul(x, w, bm, bk, bn)


def spike_matmul_op(x, w):
    """x: [M, K] spikes (0/1), w: [K, N] -> x @ w with whole-zero VMEM
    tiles skipping their MXU pass; differentiable (plain matmul
    adjoints — the Heaviside lives upstream in the LIF that produced
    x, so no surrogate is needed here).  Launch tile shapes are tuned
    per shape (repro.kernels.tune)."""
    dims = dict(M=x.shape[0], K=x.shape[1], N=w.shape[1])
    runner = None
    live = 1.0
    if tune.tuning_active() and tune.concrete(x, w):
        live = _live_fraction(x)
        runner = lambda c: _spike_matmul_jit(    # noqa: E731
            x, w, bm=c.bm, bk=c.bk, bn=c.bn)
    cfg = tune.dispatch("spike_matmul", dims, runner, live=live)
    return _spike_matmul_jit(x, w, bm=cfg.bm, bk=cfg.bk, bn=cfg.bn)


# ---------------------------------------------------------------------------
# spike_conv_op: spike-im2col lowering into the activity-gated conv path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _spike_conv_mm(patches, wmat, gate, bm, bk, bn):
    if gate == "inline":
        # route through the existing tile-skip spike matmul (per-tile
        # jnp.any check inside the kernel)
        return spike_matmul_pallas(patches, wmat, bm=bm, bk=bk, bn=bn,
                                   interpret=blocks.interpret_mode())
    return spike_conv_pallas(patches, wmat, gated=(gate == "mask"),
                             bm=bm, bk=bk, bn=bn,
                             interpret=blocks.interpret_mode())


def _spike_conv_mm_fwd(patches, wmat, gate, bm, bk, bn):
    return _spike_conv_mm(patches, wmat, gate, bm, bk, bn), \
        (patches, wmat)


def _spike_conv_mm_bwd(gate, bm, bk, bn, res, g):
    patches, wmat = res
    # d/dpatches is dense (g is not a spike tensor); d/dwmat contracts
    # over the spike patches — as with spike_matmul, the sparsity the
    # forward gates on lives in the activations, not the adjoints, and
    # the Heaviside lives upstream in the LIF that produced them, so
    # both sides are plain MXU matmuls (no surrogate needed HERE; the
    # conv layer's surrogate-grad BPTT rides in norm_affine_lif_op /
    # lif_scan_op, which fire on the conv output)
    return g @ wmat.T, patches.T @ g


_spike_conv_mm.defvjp(_spike_conv_mm_fwd, _spike_conv_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _spike_conv_direct(xf, w, stride, gate, bm):
    # "inline" gates in the kernel, on the block it has built in VMEM;
    # "mask" (an occupancy pass over a patch matrix the direct path
    # never builds) and "none" run dense — see spike_conv_direct_pallas
    return spike_conv_direct_pallas(xf, w, stride=stride,
                                    gated=(gate == "inline"), bm=bm,
                                    interpret=blocks.interpret_mode())


def _spike_conv_direct_fwd(xf, w, stride, gate, bm):
    return _spike_conv_direct(xf, w, stride, gate, bm), (xf, w)


def _spike_conv_direct_bwd(stride, gate, bm, res, g):
    xf, w = res
    kh, kw, cin, cout = w.shape
    # the residual is the activation: rebuild the patches and take the
    # patch path's adjoints (_spike_conv_mm_bwd, then im2col's own)
    patches, im2col_vjp = jax.vjp(
        lambda x: spike_im2col(x, kh, kw, stride)[0], xf)
    wmat = w.reshape(kh * kw * cin, cout)
    dx, = im2col_vjp(g @ wmat.T)
    return dx, (patches.T @ g).reshape(w.shape)


_spike_conv_direct.defvjp(_spike_conv_direct_fwd, _spike_conv_direct_bwd)


# The trace-time tally of the spiking conv's two lowerings: shape key
# -> "direct" or "patches", one entry per conv shape traced since the
# last reset (a cached trace adds nothing).
_CONV_PATHS: dict = {}


def conv_paths() -> dict:
    """Which lowering each traced spiking conv took: ``{key: path}``,
    key ``tune.shape_key(op, F=frames, H=, W=, C=cin, O=cout, k=kernel,
    s=stride)`` and path ``"direct"`` (``spike_conv_direct_pallas``)
    or ``"patches"`` (an im2col patch matrix in HBM)."""
    return dict(_CONV_PATHS)


def reset_conv_paths() -> None:
    _CONV_PATHS.clear()


def direct_conv_rule(xshape, wshape, stride: int, depthwise: bool) -> bool:
    """Whether a spiking conv takes the direct kernel: non-depthwise
    and ``direct_conv_frames`` > 0 (``Wo % 8 == 0``; ``C`` divides 128
    or is a multiple of it; one frame's blocks fit the VMEM budget).
    Shapes only — no config key, tuned entry or flag enters it."""
    return not depthwise and direct_conv_frames(xshape, wshape, stride) > 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _spike_dwconv(patches3, wflat, gate, bm):
    return spike_dwconv_pallas(patches3, wflat, gated=(gate != "none"),
                               bm=bm, interpret=blocks.interpret_mode())


def _spike_dwconv_fwd(patches3, wflat, gate, bm):
    return _spike_dwconv(patches3, wflat, gate, bm), (patches3, wflat)


def _spike_dwconv_bwd(gate, bm, res, g):
    patches3, wflat = res
    return g[:, None, :] * wflat[None], \
        jnp.einsum("mtc,mc->tc", patches3, g)


_spike_dwconv.defvjp(_spike_dwconv_fwd, _spike_dwconv_bwd)


@functools.partial(jax.jit, static_argnames=("stride", "depthwise",
                                             "gate", "bm", "bk", "bn"))
def _spike_conv_impl(xf, w, *, stride, depthwise, gate, bm, bk, bn):
    kh, kw = w.shape[:2]
    N, H, W, C = xf.shape
    direct = direct_conv_rule(xf.shape, w.shape, stride, depthwise)
    key = tune.shape_key("spike_dwconv" if depthwise else "spike_conv",
                         F=N, H=H, W=W, C=C, O=w.shape[3], k=kh, s=stride)
    _CONV_PATHS[key] = "direct" if direct else "patches"
    if direct:
        Ho, Wo = _conv_out_hw(xf, kh, kw, stride)
        y = _spike_conv_direct(xf, w, stride, gate, bm)
    elif depthwise:
        patches3, (Ho, Wo) = dw_patches(xf, kh, kw, stride)
        y = _spike_dwconv(patches3, w.reshape(kh * kw, -1), gate, bm)
    else:
        patches, (Ho, Wo) = spike_im2col(xf, kh, kw, stride)
        y = _spike_conv_mm(patches,
                           w.reshape(kh * kw * w.shape[2], w.shape[3]),
                           gate, bm, bk, bn)
    return y.reshape(N, Ho, Wo, -1)


def _conv_out_hw(xf, kh, kw, stride):
    """Static SAME output extent (Python ints, for shape keys)."""
    _, _, Ho = _same_pads(xf.shape[1], kh, stride)
    _, _, Wo = _same_pads(xf.shape[2], kw, stride)
    return Ho, Wo


def spike_conv_op(xf, w, *, stride: int = 1, depthwise: bool = False,
                  gate=None):
    """Activity-gated spiking conv.  xf: [N, H, W, C] folded spike
    tensor; w: [kh, kw, cin, cout] HWIO weights (depthwise:
    [kh, kw, 1, C]) -> [N, Ho, Wo, cout], SAME padding.

    A normal conv whose shape passes ``direct_conv_rule`` runs the
    direct kernel (``spike_conv_direct_pallas``: the patch matrix's K
    blocks built in VMEM from the activation, none in HBM); the others
    lower via spike-im2col (``repro.core.layers.spike_im2col``) into
    the tile-skip matmul kernels.  Which way each traced shape went is
    in ``conv_paths()``.  ``gate``: None (default) resolves the tuned
    gate mode for this shape; "mask" forces the per-tile precomputed
    occupancy gate, "inline" the in-kernel jnp.any check (depthwise
    treats it as "mask"; the direct kernel runs "mask" dense), "none"
    the dense baseline the benchmark sweep compares against.  Launch
    tile shapes always come from the tuned config.  Differentiable:
    plain matmul adjoints — the surrogate gradient lives in the LIF
    epilogue downstream.

    Bit-exact vs the jnp reference ``spike_conv_jnp`` (shared canonical
    K-block / tap-loop formulation — for EVERY tuned block shape) and
    allclose vs lax.conv SAME."""
    if gate not in (None, "mask", "inline", "none"):
        raise ValueError(f"gate must be None, 'mask', 'inline' or "
                         f"'none', got {gate!r}")
    kh, kw = w.shape[:2]
    Ho, Wo = _conv_out_hw(xf, kh, kw, stride)
    if depthwise:
        op = "spike_dwconv"
        dims = dict(M=xf.shape[0] * Ho * Wo, taps=kh * kw,
                    C=xf.shape[3])
    else:
        op = "spike_conv"
        dims = dict(M=xf.shape[0] * Ho * Wo, K=kh * kw * w.shape[2],
                    N=w.shape[3])
    runner = None
    live = 1.0
    if tune.tuning_active() and tune.concrete(xf, w):
        live = _live_fraction(xf)
        runner = lambda c: _spike_conv_impl(     # noqa: E731
            xf, w, stride=stride, depthwise=depthwise,
            gate=(gate if gate is not None else c.gate),
            bm=c.bm, bk=c.bk, bn=c.bn)
    cfg = tune.dispatch(op, dims, runner, live=live)
    return _spike_conv_impl(
        xf, w, stride=stride, depthwise=depthwise,
        gate=(gate if gate is not None else cfg.gate),
        bm=cfg.bm, bk=cfg.bk, bn=cfg.bn)


# ---------------------------------------------------------------------------
# spike_conv_lif_op: the whole spiking-conv layer through one dispatch
# point — fused conv→LIF kernel or per-op composition, per tuned config
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _conv_lif(patches, wmat, scale, bias, T, B, HW, gate, bm, tau, v_th,
              v_reset, beta):
    return spike_conv_lif_pallas(
        patches, wmat, scale, bias, T=T, B=B, HW=HW, tau=tau, v_th=v_th,
        v_reset=v_reset, eps=NORM_EPS, gate=gate, bm=bm,
        interpret=blocks.interpret_mode())


def _conv_lif_fwd(patches, wmat, scale, bias, T, B, HW, gate, bm, tau,
                  v_th, v_reset, beta):
    out = _conv_lif(patches, wmat, scale, bias, T, B, HW, gate, bm, tau,
                    v_th, v_reset, beta)
    return out, (patches, wmat, scale, bias)


def _conv_lif_bwd(T, B, HW, gate, bm, tau, v_th, v_reset, beta, res, g):
    patches, wmat, scale, bias = res
    # rematerialise the fused kernel's resident intermediates in the
    # exact shared formulation: canonical K-blocked conv output, the
    # per-(B, C) norm statistics, then the membrane trajectory — one
    # recompute instead of three HBM spills from the forward kernel
    y = blocked_matmul(patches, wmat)           # [B·T·HW, N], bit-exact
    N = y.shape[-1]
    y4 = jnp.swapaxes(y.reshape(B, T, HW, N), 0, 1)   # [T, B, HW, N]
    yhat, r = _norm_stats(y4)
    z = yhat * scale + bias
    xs, ss = _lif_replay(z, tau=tau, v_th=v_th, v_reset=v_reset)
    dz = _lif_bwd_scan(g, xs, ss, tau=tau, v_th=v_th, v_reset=v_reset,
                       beta=beta)
    dyhat = dz * scale
    dscale = jnp.sum(dz * yhat, axis=(0, 1, 2))
    dbias = jnp.sum(dz, axis=(0, 1, 2))
    m1 = jnp.mean(dyhat, axis=(0, 2), keepdims=True)
    m2 = jnp.mean(dyhat * yhat, axis=(0, 2), keepdims=True)
    dy4 = r * (dyhat - m1 - yhat * m2)
    dy = jnp.swapaxes(dy4, 0, 1).reshape(B * T * HW, N)
    # conv adjoints are plain matmuls (sparsity lives in the patches;
    # the Heaviside of THIS layer's spikes is handled by the surrogate
    # above, the one that produced the patches by the upstream layer)
    return dy @ wmat.T, patches.T @ dy, dscale, dbias


_conv_lif.defvjp(_conv_lif_fwd, _conv_lif_bwd)


@functools.partial(jax.jit, static_argnames=(
    "T", "B", "stride", "fused", "gate", "bm", "bk", "bn", "tau",
    "v_th", "v_reset", "beta"))
def _conv_lif_apply(xf, w, scale, bias, *, T, B, stride, fused, gate,
                    bm, bk, bn, tau, v_th, v_reset, beta):
    kh, kw = w.shape[:2]
    wmat = w.reshape(kh * kw * w.shape[2], w.shape[3])
    if fused:
        patches, (Ho, Wo) = spike_im2col(xf, kh, kw, stride)
        out = _conv_lif(patches, wmat, scale, bias, T, B, Ho * Wo,
                        gate, bm, tau, v_th, v_reset, beta)
        return out.reshape(T, B, Ho, Wo, -1)
    # per-op composition (the conv's own launch shapes resolve through
    # its nested spike_conv dispatch at trace time)
    y = spike_conv_op(xf, w, stride=stride, gate=gate)
    _, Ho, Wo, Co = y.shape
    y = jnp.swapaxes(y.reshape(B, T, Ho, Wo, Co), 0, 1)
    return norm_affine_lif_op(y, scale, bias, tau=tau, v_th=v_th,
                              v_reset=v_reset, beta=beta)


def spike_conv_lif_op(xf, w, scale, bias, *, T: int, B: int,
                      stride: int = 1, tau: float = 2.0,
                      v_th: float = 1.0, v_reset: float = 0.0,
                      beta: float = 4.0):
    """The whole spiking-conv layer: conv + instance-norm + affine +
    T-step LIF.  xf: [B·T, H, W, C] batch-major folded spike tensor;
    w: [kh, kw, cin, cout] -> spikes [T, B, Ho, Wo, cout].

    The tuned config decides the FUSION BOUNDARY per shape: the fused
    single-kernel path (``spike_conv_lif_pallas`` — conv output stays
    VMEM-resident through the epilogue, one HBM round-trip) or the
    per-op composition (``spike_conv_op`` + ``norm_affine_lif_op``).
    Both variants are bit-exact vs the jnp reference; the surrogate-
    gradient custom VJP rematerialises the fused intermediates, so the
    fused path is training-legal with grads matching the per-op path
    to float rounding."""
    kh, kw = w.shape[:2]
    Ho, Wo = _conv_out_hw(xf, kh, kw, stride)
    dims = dict(T=T, B=B, HW=Ho * Wo, K=kh * kw * w.shape[2],
                N=w.shape[3])
    runner = None
    live = 1.0
    if tune.tuning_active() and tune.concrete(xf, w, scale, bias):
        live = _live_fraction(xf)
        runner = lambda c: _conv_lif_apply(      # noqa: E731
            xf, w, scale, bias, T=T, B=B, stride=stride, fused=c.fused,
            gate=c.gate, bm=c.bm, bk=c.bk, bn=c.bn, tau=tau, v_th=v_th,
            v_reset=v_reset, beta=beta)
    cfg = tune.dispatch("conv_lif", dims, runner, live=live)
    return _conv_lif_apply(
        xf, w, scale, bias, T=T, B=B, stride=stride, fused=cfg.fused,
        gate=cfg.gate, bm=cfg.bm, bk=cfg.bk, bn=cfg.bn, tau=tau,
        v_th=v_th, v_reset=v_reset, beta=beta)


# ---------------------------------------------------------------------------
# backbone_segment_op: a whole planned backbone segment through one
# dispatch point — the layer-chained megakernel (spikes stay VMEM-
# resident across layer boundaries) or the per-layer composition, per
# tuned config (ISSUE 9)
# ---------------------------------------------------------------------------

def _seg_prep(params, specs):
    """Flatten per-layer (w, scale, bias) into the megakernel's
    operands: normal layers pass the canonical-padded [Kp, N] weight
    matrix (trailing-zero K rows — the bit-preserving padding PR 8
    established), depthwise layers the [taps, C] tap matrix."""
    flat = []
    for (w, scale, bias), s in zip(params, specs):
        if s.depthwise:
            flat.append(w.reshape(s.kernel * s.kernel, -1))
        else:
            wmat = w.reshape(s.kernel * s.kernel * w.shape[2], w.shape[3])
            pk = (-wmat.shape[0]) % CANONICAL_K_BLOCK
            if pk:
                wmat = jnp.pad(wmat, ((0, pk), (0, 0)))
            flat.append(wmat)
        flat += [scale, bias]
    return tuple(flat)


def _segment_ref(x, params, specs, *, tau, v_th, v_reset, beta):
    """Bit-exact jnp reference of a fused segment: per layer, the
    canonical K-blocked ``spike_conv_jnp``, the axis-(0, 2) instance
    norm + affine, the ``repro.core.lif.lif_scan`` recurrence (whose
    ``spike`` carries the sigmoid-surrogate custom VJP), then the
    reduce_window max-pool — exactly the jnp backend's layer
    composition.  Doubles as the megakernel's backward: ``jax.vjp``
    through THIS composition is the surrogate-gradient BPTT, so the
    custom VJP below rematerialises the whole segment (one recompute
    instead of L·3 HBM spills from the forward kernel) and replays the
    scan."""
    cur = x
    for (w, scale, bias), s in zip(params, specs):
        T, B, h, wdim, c = cur.shape
        xf = jnp.swapaxes(cur, 0, 1).reshape(B * T, h, wdim, c)
        y = spike_conv_jnp(xf, w, stride=s.stride, depthwise=s.depthwise)
        _, ho, wo, co = y.shape
        y5 = jnp.swapaxes(y.reshape(B, T, ho, wo, co), 0, 1)
        y4 = y5.reshape(T, B, ho * wo, co)
        mu = jnp.mean(y4, axis=(0, 2), keepdims=True)
        var = jnp.var(y4, axis=(0, 2), keepdims=True)
        z = ((y4 - mu) * jax.lax.rsqrt(var + NORM_EPS)).reshape(y5.shape)
        z = z * scale + bias
        cur = lif_scan_ref(z, tau=tau, v_th=v_th, v_reset=v_reset,
                           beta=beta)
        if s.pool:
            cur = max_pool(cur, s.pool)
    return cur


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _backbone_seg(x, params, specs, gate, bm, tau, v_th, v_reset, beta):
    return backbone_segment_pallas(
        x, _seg_prep(params, specs), specs=specs, tau=tau, v_th=v_th,
        v_reset=v_reset, eps=NORM_EPS, gate=gate, bm=bm,
        interpret=blocks.interpret_mode())


def _backbone_seg_fwd(x, params, specs, gate, bm, tau, v_th, v_reset,
                      beta):
    out = _backbone_seg(x, params, specs, gate, bm, tau, v_th, v_reset,
                        beta)
    return out, (x, params)


def _backbone_seg_bwd(specs, gate, bm, tau, v_th, v_reset, beta, res, g):
    x, params = res
    # rematerialise per segment, replay the scan: differentiate the
    # bit-exact jnp composition of the SAME segment (surrogate spike,
    # canonical K blocks), so fused-path grads match the per-layer
    # path to float rounding
    _, vjp = jax.vjp(
        lambda xx, pp: _segment_ref(xx, pp, specs, tau=tau, v_th=v_th,
                                    v_reset=v_reset, beta=beta),
        x, params)
    return vjp(g)


_backbone_seg.defvjp(_backbone_seg_fwd, _backbone_seg_bwd)


@functools.partial(jax.jit, static_argnames=(
    "specs", "gate", "bm", "tau", "v_th", "v_reset", "beta"))
def _backbone_seg_jit(x, params, *, specs, gate, bm, tau, v_th, v_reset,
                      beta):
    return _backbone_seg(x, params, specs, gate, bm, tau, v_th, v_reset,
                         beta)


def _pool_spikes(x, window: int):
    """Max-pool spikes [T, B, H, W, C] on the unfused pallas path:
    kernel-backed when compiled, reduce_window under the interpreter
    (bit-identical; a standalone interpret-mode launch is a net loss —
    fused segments absorb pooling as an in-kernel epilogue instead)."""
    if blocks.interpret_mode():
        return max_pool(x, window)
    T, B, H, W, C = x.shape
    xf = jnp.swapaxes(x, 0, 1).reshape(B * T, H, W, C)
    y = max_pool_op(xf, window=window)
    return jnp.swapaxes(
        y.reshape(B, T, H // window, W // window, C), 0, 1)


def _seg_unfused(x, params, specs, *, tau, v_th, v_reset, beta):
    """The per-layer kernel composition of a segment (each layer its own
    tuned dispatch, one HBM round-trip per layer) — the default path and
    the ``fused=False`` tuning candidate.  Deliberately PLAIN EAGER
    Python (the inner ops carry their own jits): during a measured
    sweep this candidate's nested conv_lif dispatches stay eager, so
    untuned per-layer shapes run their own sweeps and record their own
    table entries instead of degrading to resolution-only."""
    cur = x
    for (w, scale, bias), s in zip(params, specs):
        T, B, h, wdim, c = cur.shape
        xf = jnp.swapaxes(cur, 0, 1).reshape(B * T, h, wdim, c)
        if s.depthwise:
            y = spike_conv_op(xf, w, stride=s.stride, depthwise=True)
            _, ho, wo, co = y.shape
            y = jnp.swapaxes(y.reshape(B, T, ho, wo, co), 0, 1)
            cur = norm_affine_lif_op(y, scale, bias, tau=tau, v_th=v_th,
                                     v_reset=v_reset, beta=beta)
        else:
            cur = spike_conv_lif_op(xf, w, scale, bias, T=T, B=B,
                                    stride=s.stride, tau=tau, v_th=v_th,
                                    v_reset=v_reset, beta=beta)
        if s.pool:
            cur = _pool_spikes(cur, s.pool)
    return cur


def backbone_segment_op(x, params, *, specs, tau: float = 2.0,
                        v_th: float = 1.0, v_reset: float = 0.0,
                        beta: float = 4.0):
    """One planned backbone segment (``repro.kernels.backbone_fuse.
    plan_segments``) through one dispatch point.  x: [T, B, H, W, C]
    spikes; params: tuple of (w, scale, bias) per layer; specs: the
    segment's ``LayerSpec`` tuple (anonymized — shape keys carry only
    shape facts, so same-shaped segments share one table entry and one
    executable) -> spikes after the segment's last layer, pooling
    absorbed.

    The tuned config decides the SEGMENT'S fusion boundary per shape:
    the layer-chained megakernel (``backbone_segment_pallas`` — spikes
    and membranes VMEM-resident across layer boundaries, ONE launch) or
    the per-layer composition (``_seg_unfused`` — each layer's own
    tuned conv→LIF dispatch).  Default is per-layer: whole-backbone
    fusion must WIN a measured sweep to be served, so an untuned
    deployment behaves exactly like PR 8.  Both variants are bit-exact
    vs the jnp reference; the custom VJP rematerialises the segment and
    replays the scan, so the fused path is training-legal."""
    T, B, H, W, _ = x.shape
    dims = dict(T=T, B=B, H=H, W=W)
    for i, s in enumerate(specs):
        dims[f"L{i}"] = s.dim_token
    # aggregate roofline terms for the tuner's candidate ranking: total
    # MACs, total per-layer activation traffic, and the grid steps the
    # per-layer path would pay (the interpret-mode wall-clock term)
    dims["F"] = segment_macs(specs, H=H, W=W, T=T, B=B)
    dims["A"] = segment_activation_elems(specs, H=H, W=W, T=T, B=B)
    dims["G"] = segment_unfused_grid_steps(specs, H=H, W=W, T=T, B=B)
    runner = None
    live = 1.0
    if tune.tuning_active() and tune.concrete(x):
        live = _live_fraction(x)

        def runner(c):
            if c.fused:
                return _backbone_seg_jit(
                    x, params, specs=specs, gate=c.gate, bm=c.bm,
                    tau=tau, v_th=v_th, v_reset=v_reset, beta=beta)
            return _seg_unfused(x, params, specs, tau=tau, v_th=v_th,
                                v_reset=v_reset, beta=beta)
    cfg = tune.dispatch("backbone_seg", dims, runner, live=live)
    if cfg.fused:
        return _backbone_seg_jit(x, params, specs=specs, gate=cfg.gate,
                                 bm=cfg.bm, tau=tau, v_th=v_th,
                                 v_reset=v_reset, beta=beta)
    return _seg_unfused(x, params, specs, tau=tau, v_th=v_th,
                        v_reset=v_reset, beta=beta)


# ---------------------------------------------------------------------------
# max_pool_op: gated Pallas spike pooling (the unfused compiled path)
# ---------------------------------------------------------------------------

def _pool_ref(xf, window: int):
    return jax.lax.reduce_window(xf, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, window, window, 1), "VALID")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _max_pool_k(xf, window, gated):
    return max_pool_pallas(xf, window=window, gated=gated,
                           interpret=blocks.interpret_mode())


def _max_pool_k_fwd(xf, window, gated):
    return _max_pool_k(xf, window, gated), xf


def _max_pool_k_bwd(window, gated, xf, g):
    _, vjp = jax.vjp(lambda v: _pool_ref(v, window), xf)
    return vjp(g)


_max_pool_k.defvjp(_max_pool_k_fwd, _max_pool_k_bwd)


@functools.partial(jax.jit, static_argnames=("window", "gated"))
def max_pool_op(xf, *, window: int = 2, gated: bool = True):
    """Gated Pallas max-pool of a folded [N, H, W, C] SPIKE tensor —
    an all-silent frame skips its reduction and writes zeros (exact
    only because spikes are non-negative).  Bit-exact vs reduce_window
    (max has no rounding); differentiable via the reduce_window
    adjoint.  Serves the unfused pallas path on compiled backends;
    fused backbone segments absorb pooling in-kernel instead."""
    return _max_pool_k(xf, window, gated)


@functools.partial(jax.jit, static_argnames=("stride", "depthwise"))
def spike_conv_tile_skip(xf, w, *, stride: int = 1,
                         depthwise: bool = False):
    """Fraction of the gated conv's K-loop tiles whose occupancy bit is
    clear — the achieved MXU-pass skip rate of ``spike_conv_op`` on
    this input (benchmark telemetry; reported next to each speedup
    row).  Same im2col granularity the kernel gates at, unlike the
    flat-tile ``repro.core.sparsity.tile_skip_fraction``."""
    kh, kw = w.shape[:2]
    if depthwise:
        patches3, _ = dw_patches(xf, kh, kw, stride)
        occ = tap_occupancy_mask(patches3)
    else:
        patches, _ = spike_im2col(xf, kh, kw, stride)
        occ = occupancy_mask(patches)
    return jnp.mean((occ == 0).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("chain", "bh", "bw"))
def pointwise_segment_op(x, pvec, stats, consts=(), *, chain,
                         bh: int = 128, bw: int = 128):
    """One fused-ISP pointwise segment (a run of contiguous pointwise
    stages, optionally led by a reduce-stage apply) as ONE tiled
    kernel pass.  ``chain``: tuple of ``isp_fused.ChainStep`` — a jit
    static, so each planned segment compiles once and serves every
    control vector.  ``consts``: traced array constants chain steps
    need (e.g. the CCM luma row)."""
    return pointwise_segment_pallas(x, pvec, stats, chain=chain,
                                    consts=tuple(consts), bh=bh, bw=bw,
                                    interpret=blocks.interpret_mode())


@functools.partial(jax.jit, static_argnames=(
    "prologue", "window_fn", "wstep", "radius", "pad", "out_tail", "bh",
    "bw"))
def stencil_segment_op(x, pvec, stats, consts=(), *, prologue, window_fn,
                       wstep, radius: int, pad: str, out_tail,
                       bh: int = 128, bw: int = 128):
    """One fused-ISP stencil segment: halo'd row/column-tiled kernel
    with the segment's pointwise prologue recomputed on the halo.
    ``consts``: traced array constants the window_fn needs (e.g. the
    MHC filter bank)."""
    return stencil_segment_pallas(
        x, pvec, stats, prologue=prologue, window_fn=window_fn,
        wstep=wstep, radius=radius, pad=pad, out_tail=out_tail,
        consts=tuple(consts), bh=bh, bw=bw,
        interpret=blocks.interpret_mode())


@jax.jit
def demosaic_op(raw):
    return demosaic_pallas(raw, interpret=blocks.interpret_mode())


@jax.jit
def nlm_op(img, strength):
    return nlm_pallas(img, strength, interpret=blocks.interpret_mode())


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention_op(q, k, v, causal: bool = True):
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=blocks.interpret_mode())
