"""Whole-backbone fusion: the cross-layer segment planner and the
layer-chained Pallas megakernel (ISSUE 9).

The paper's NPU wins on FPGA because the whole spiking backbone is one
streaming dataflow — spikes never round-trip to external memory between
layers.  After PR 8's conv→LIF epilogue fusion our Pallas path still
paid one HBM round-trip per LAYER: layer k's spikes leave the kernel,
land in HBM, and re-enter layer k+1's im2col.  This module removes
those boundaries the same way PR 4's ISP stage-fusion planner
(``repro.isp.fuse``) removed them between ISP stages:

* :func:`plan_segments` segments a backbone's linear layer run into
  maximal fusible segments, forcing a boundary where residency breaks —
  the per-batch VMEM working set exceeding the budget
  (``repro.launch.roofline.VMEM_BYTES``), a stride the in-kernel im2col
  does not chain (> ``MAX_FUSED_STRIDE``), or a non-float32 activation
  dtype.
* :func:`backbone_segment_pallas` lowers one segment as ONE kernel in
  which the spike/membrane tensors stay VMEM-resident across layer
  boundaries: layer k's T-step LIF epilogue feeds layer k+1's
  im2col/tap accumulation without touching HBM, and a trailing 2x2
  max-pool is absorbed as an epilogue reduction instead of its own
  launch.

Grid discipline: one program per batch element (the instance-norm
statistics, the LIF recurrence, and pooling are all per-batch-element
independent, so the segment is embarrassingly parallel over B).  In
interpret mode this collapses L kernel launches of B grid steps each
into ONE launch of B grid steps — the first-order wall-clock term
(``roofline.INTERPRET_STEP_OVERHEAD_S``); compiled, it is the HBM
round-trips that disappear.

Bit-exactness contract (tests/test_backbone_fuse.py): every piece of
the in-kernel layer is the SHARED formulation, not a parallel
implementation —

* the in-kernel im2col replicates ``repro.core.layers._patch_slices``
  exactly (same SAME-padding, same (kh, kw)-major tap order, same
  channel-minor patch layout), and is pure data movement of 0/1 spike
  values;
* the MAC loop accumulates K in ``CANONICAL_K_BLOCK`` sub-blocks in the
  same order as ``repro.core.layers.blocked_matmul`` (depthwise: the
  same in-order tap loop as ``spike_conv_jnp``);
* the norm+affine+LIF epilogue is ``norm_affine_lif_epilogue`` — the
  same function every other spiking kernel runs;
* the pooling epilogue is an elementwise max of strided slices, exact
  for floats (max has no rounding).

Activity gating (``gate="inline"``) skips a MAC tile when its resident
patch tile is all-zero — the skipped contribution is exact zeros, so
gating never changes bits.  The one-shot precomputed "mask" gate of the
per-layer kernels does not apply here: interior layers' patch matrices
never exist outside the kernel, so there is nothing to precompute a
mask from.

The fused-vs-per-layer decision and the row-chunk ``bm`` are tunable,
shape-keyed entries in the persistent autotuner table
(``repro.kernels.tune``, op ``"backbone_seg"``; ``KERNELS_VERSION``
bumped for this PR).  The default is the per-layer composition —
whole-backbone fusion is an earned, measured win, never a silent
default.  Dispatch + the surrogate-gradient custom VJP (rematerialize
per segment, replay the scan) live in
``repro.kernels.ops.backbone_segment_op``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layers import _same_pads
from repro.kernels.blocks import (CANONICAL_K_BLOCK, DEFAULT_BM,
                                  matmul_precision)
from repro.kernels.lif_scan import norm_affine_lif_epilogue
from repro.launch.roofline import VMEM_BYTES, vmem_residency_estimate

# The in-kernel im2col chains strides 1 and 2 (every backbone here);
# anything larger forces a segment boundary — conservative residency
# contract, not a numerics limit.
MAX_FUSED_STRIDE = 2


# ---------------------------------------------------------------------------
# Layer graph declaration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One spiking-conv layer of a backbone's linear run, as the
    planner and the megakernel see it: the param-dict key plus the
    static shape facts that decide fusibility.  ``pool`` is the window
    of a max-pool IMMEDIATELY AFTER the layer (0 = none) — pooling is a
    property of the layer so the planner can absorb it as an epilogue
    reduction instead of a segment break.  Frozen/hashable, so a tuple
    of specs rides into jit static args and lru caches unchanged."""
    name: str
    kernel: int = 3
    stride: int = 1
    depthwise: bool = False
    cin: int = 1
    cout: int = 1
    pool: int = 0

    @property
    def dim_token(self) -> str:
        """Anonymous shape token for autotuner keys (no layer name —
        same-shaped segments share one table entry)."""
        return (f"k{self.kernel}s{self.stride}c{self.cin}n{self.cout}"
                f"d{int(self.depthwise)}p{self.pool}")

    def anon(self) -> "LayerSpec":
        return dataclasses.replace(self, name="")


def layer_out_hw(spec: LayerSpec, h: int, w: int) -> Tuple[int, int]:
    """Static output extent of one layer (SAME conv, then pool)."""
    _, _, ho = _same_pads(h, spec.kernel, spec.stride)
    _, _, wo = _same_pads(w, spec.kernel, spec.stride)
    if spec.pool:
        ho, wo = ho // spec.pool, wo // spec.pool
    return ho, wo


@dataclasses.dataclass(frozen=True)
class Segment:
    """One planned kernel launch: a maximal run of layers whose
    spike/membrane tensors stay VMEM-resident across layer boundaries.
    ``fusible=False`` marks a run the megakernel must not take (a
    single layer already over the VMEM budget, an unchainable stride,
    or a non-f32 dtype) — the executor runs it per-layer instead."""
    layers: Tuple[LayerSpec, ...]
    fusible: bool = True

    def describe(self) -> str:
        mark = "" if self.fusible else "?"
        names = [s.name + ("+pool" if s.pool else "") for s in self.layers]
        return "[" + "+".join(names) + mark + "]"


def segment_vmem_bytes(specs: Tuple[LayerSpec, ...], *, H: int, W: int,
                       T: int) -> int:
    """Per-batch-element VMEM working set of a fused segment: the input
    slab plus, per layer, the resident patch matrix (K canonical-
    padded), the f32 accumulator, the spike scratch, and the membrane
    register file.  Feeds the planner's budget rule via
    ``roofline.vmem_residency_estimate``."""
    elems: List[int] = [T * H * W * (specs[0].cin if specs else 0)]
    h, w = H, W
    for s in specs:
        _, _, ho = _same_pads(h, s.kernel, s.stride)
        _, _, wo = _same_pads(w, s.kernel, s.stride)
        taps = s.kernel * s.kernel
        if s.depthwise:
            k = taps * s.cin
        else:
            kk = taps * s.cin
            k = kk + ((-kk) % CANONICAL_K_BLOCK)
        elems.append(T * ho * wo * k)                  # patch matrix
        elems.append(T * ho * wo * s.cout)             # accumulator
        elems.append(T * ho * wo * s.cout)             # spike scratch
        elems.append(ho * wo * s.cout)                 # membrane u
        h, w = layer_out_hw(s, h, w)
    return vmem_residency_estimate(*elems)


def segment_macs(specs: Tuple[LayerSpec, ...], *, H: int, W: int,
                 T: int, B: int) -> int:
    """Total MACs of a segment (roofline flops term for the tuner)."""
    total, h, w = 0, H, W
    for s in specs:
        _, _, ho = _same_pads(h, s.kernel, s.stride)
        _, _, wo = _same_pads(w, s.kernel, s.stride)
        taps = s.kernel * s.kernel
        k = taps * s.cin if not s.depthwise else taps
        n = s.cout if not s.depthwise else s.cin
        total += T * B * ho * wo * k * n
        h, w = layer_out_hw(s, h, w)
    return total


def segment_activation_elems(specs: Tuple[LayerSpec, ...], *, H: int,
                             W: int, T: int, B: int) -> int:
    """Total per-layer activation elements — the HBM traffic the
    per-layer path round-trips and the fused path keeps resident."""
    total, h, w = 0, H, W
    for s in specs:
        _, _, ho = _same_pads(h, s.kernel, s.stride)
        _, _, wo = _same_pads(w, s.kernel, s.stride)
        total += T * B * ho * wo * s.cout
        h, w = layer_out_hw(s, h, w)
    return total


def segment_unfused_grid_steps(specs: Tuple[LayerSpec, ...], *, H: int,
                               W: int, T: int, B: int) -> int:
    """Grid steps the per-layer composition pays for this segment at
    default block shapes (the launch-count term that dominates
    interpret-mode wall-clock): per layer, the conv matmul grid plus
    the epilogue's batch grid, plus one pooling pass per absorbed
    pool."""
    def cdiv(a, b):
        return -(-a // b)

    steps, h, w = 0, H, W
    for s in specs:
        _, _, ho = _same_pads(h, s.kernel, s.stride)
        _, _, wo = _same_pads(w, s.kernel, s.stride)
        if s.depthwise:
            steps += cdiv(T * B * ho * wo, DEFAULT_BM) + B
        else:
            k = s.kernel * s.kernel * s.cin
            steps += (cdiv(T * B * ho * wo, DEFAULT_BM)
                      * cdiv(s.cout, DEFAULT_BM)
                      * cdiv(k, CANONICAL_K_BLOCK)) + B
        if s.pool:
            steps += B
        h, w = layer_out_hw(s, h, w)
    return steps


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def _plan(specs: Tuple[LayerSpec, ...], H: int, W: int, T: int,
          f32: bool, budget: int) -> Tuple[Segment, ...]:
    segments: List[Segment] = []
    run: List[LayerSpec] = []
    h, w = H, W
    run_h, run_w = H, W                     # input extent of the open run

    def flush():
        nonlocal run, run_h, run_w
        if run:
            segments.append(Segment(layers=tuple(run)))
        run, run_h, run_w = [], h, w

    for s in specs:
        if not f32 or s.stride > MAX_FUSED_STRIDE:
            # residency break: the layer cannot enter ANY fused segment
            flush()
            segments.append(Segment(layers=(s,), fusible=False))
            h, w = layer_out_hw(s, h, w)
            run_h, run_w = h, w
            continue
        cand = tuple(run) + (s,)
        if segment_vmem_bytes(cand, H=run_h, W=run_w, T=T) > budget:
            flush()
            # re-check the layer alone against the budget at ITS input
            # extent — a single over-budget layer stays per-layer
            if segment_vmem_bytes((s,), H=h, W=w, T=T) > budget:
                segments.append(Segment(layers=(s,), fusible=False))
                h, w = layer_out_hw(s, h, w)
                run_h, run_w = h, w
                continue
        run.append(s)
        h, w = layer_out_hw(s, h, w)
    flush()
    return tuple(segments)


@functools.lru_cache(maxsize=None)
def _plan_cached(specs, H, W, T, f32, budget):
    return _plan(specs, H, W, T, f32, budget)


def plan_segments(specs, *, H: int, W: int, T: int, dtype=jnp.float32,
                  vmem_budget: Optional[int] = None) -> Tuple[Segment, ...]:
    """Segment a linear layer run into maximal fusible segments.

    Boundary rules (the VMEM-residency contract):

    * greedy maximal runs — a layer joins the open segment unless the
      segment's per-batch working set (``segment_vmem_bytes``) would
      exceed ``vmem_budget`` (default ``roofline.VMEM_BYTES``);
    * ``stride > MAX_FUSED_STRIDE`` breaks residency: the layer becomes
      its own non-fusible segment;
    * non-float32 dtypes break residency everywhere (the epilogue's
      f32 statistics/recurrence contract): every layer becomes its own
      non-fusible segment;
    * a single layer over the budget by itself is non-fusible.

    Plans are static per (specs, extent, budget) and lru-cached, so the
    planner is pure Python at trace time — zero per-tick cost."""
    budget = VMEM_BYTES if vmem_budget is None else int(vmem_budget)
    f32 = jnp.dtype(dtype) == jnp.dtype(jnp.float32)
    return _plan_cached(tuple(specs), int(H), int(W), int(T), f32, budget)


def describe_plan(specs, *, H: int, W: int, T: int,
                  vmem_budget: Optional[int] = None) -> str:
    """Human-readable segment diagram, e.g. vgg's
    ``[s0_a+s0_b+pool+s1_a+s1_b+pool]``."""
    return " ".join(s.describe() for s in plan_segments(
        specs, H=H, W=W, T=T, vmem_budget=vmem_budget))


# ---------------------------------------------------------------------------
# The megakernel
# ---------------------------------------------------------------------------

def _pool_slices(act, window: int):
    """2x2 (or ``window``²) max-pool of act [T, H, W, C] as an
    elementwise max of strided slices — exactly ``lax.reduce_window``
    (VALID, stride = window) for max (no rounding), with the tail rows
    a non-dividing extent drops."""
    T, H, W, C = act.shape
    ho, wo = H // window, W // window
    out = None
    for di in range(window):
        for dj in range(window):
            s = act[:, di:ho * window:window, dj:wo * window:window, :]
            out = s if out is None else jnp.maximum(out, s)
    return out


def _im2col_resident(act, kernel: int, stride: int):
    """In-kernel im2col of the resident activation value act
    [T, H, W, C] -> patch matrix [T·Ho·Wo, kh·kw·C] plus (Ho, Wo).
    Replicates ``repro.core.layers._patch_slices`` / ``spike_im2col``
    exactly — same SAME padding, same (kh, kw)-major tap order, same
    channel-minor layout — so the patch rows for one batch element are
    the SAME VALUES the HBM patch matrix holds for that element (pure
    data movement; bit-parity is structural)."""
    T, H, W, C = act.shape
    plo_h, phi_h, ho = _same_pads(H, kernel, stride)
    plo_w, phi_w, wo = _same_pads(W, kernel, stride)
    xp = jnp.pad(act, ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0)))
    taps = [xp[:, i:i + (ho - 1) * stride + 1:stride,
               j:j + (wo - 1) * stride + 1:stride, :]
            for i in range(kernel) for j in range(kernel)]
    p = jnp.stack(taps, axis=3)            # [T, Ho, Wo, taps, C]
    return p.reshape(T * ho * wo, kernel * kernel * C), (ho, wo)


def _mac_canonical(patches, w_ref, acc_ref, *, bm: int, inline: bool,
                   precision):
    """Row-chunked, canonical-K-blocked MAC of the resident patch
    matrix into the f32 accumulator scratch — the same accumulation
    order as ``blocked_matmul`` (per output element: K blocks in
    ascending order), with optional inline activity gating (a skipped
    tile's contribution is exact zeros)."""
    M, Kp = patches.shape
    n_rc = -(-M // bm)
    k_steps = Kp // CANONICAL_K_BLOCK
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for rc in range(n_rc):
        r0, r1 = rc * bm, min((rc + 1) * bm, M)
        for k in range(k_steps):
            c0 = k * CANONICAL_K_BLOCK
            c1 = c0 + CANONICAL_K_BLOCK
            tile = patches[r0:r1, c0:c1]
            cond = jnp.any(tile != 0) if inline else True

            @pl.when(cond)
            def _mac(tile=tile, r0=r0, r1=r1, c0=c0, c1=c1):
                acc_ref[r0:r1, :] += jnp.dot(
                    tile.astype(jnp.float32),
                    w_ref[c0:c1, :].astype(jnp.float32), precision=precision,
                    preferred_element_type=jnp.float32)


def _mac_depthwise(patches3, w_ref, acc_ref, *, inline: bool):
    """In-order tap-loop accumulation for a depthwise layer — the same
    order as ``spike_conv_jnp``'s depthwise path, with per-tap inline
    gating (an all-silent tap slab adds exact zeros)."""
    taps = patches3.shape[1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for t in range(taps):
        slab = patches3[:, t, :]
        cond = jnp.any(slab != 0) if inline else True

        @pl.when(cond)
        def _mac(slab=slab, t=t):
            acc_ref[...] += slab * w_ref[t, :]


def _segment_kernel(*refs, specs: Tuple[LayerSpec, ...], T: int, H: int,
                    W: int, bm: int, inline: bool, tau: float,
                    v_th: float, v_reset: float, eps: float, precision):
    """One grid step = one batch element through the WHOLE segment.
    refs: [x, (w, scale, bias) per layer, out, (acc, s, u) per layer]."""
    L = len(specs)
    x_ref = refs[0]
    out_ref = refs[1 + 3 * L]
    scratch = refs[2 + 3 * L:]
    act = x_ref[0]                          # [T, H, W, C] resident value
    h, w = H, W
    for i, spec in enumerate(specs):
        w_ref, scale_ref, bias_ref = refs[1 + 3 * i:4 + 3 * i]
        acc_ref, s_ref, u_ref = scratch[3 * i:3 * i + 3]
        if spec.depthwise:
            patches3, (ho, wo) = _im2col_resident(act, spec.kernel,
                                                  spec.stride)
            patches3 = patches3.reshape(-1, spec.kernel * spec.kernel,
                                        spec.cin)
            _mac_depthwise(patches3, w_ref, acc_ref, inline=inline)
        else:
            patches, (ho, wo) = _im2col_resident(act, spec.kernel,
                                                 spec.stride)
            pk = (-patches.shape[1]) % CANONICAL_K_BLOCK
            if pk:
                patches = jnp.pad(patches, ((0, 0), (0, pk)))
            _mac_canonical(patches, w_ref, acc_ref, bm=bm, inline=inline,
                           precision=precision)
        # layer k's epilogue runs on the resident accumulator and its
        # spikes feed layer k+1's im2col without touching HBM — the
        # layer-chained VMEM residency this module exists for
        n = acc_ref.shape[-1]
        y = acc_ref[...].reshape(T, 1, ho * wo, n)
        norm_affine_lif_epilogue(y, scale_ref[...], bias_ref[...],
                                 s_ref, u_ref, tau=tau, v_th=v_th,
                                 v_reset=v_reset, eps=eps, T=T)
        act = s_ref[...].reshape(T, ho, wo, n)
        if spec.pool:
            act = _pool_slices(act, spec.pool)
        h, w = act.shape[1], act.shape[2]
    out_ref[...] = act.reshape(T, 1, h, w, act.shape[-1])


def backbone_segment_pallas(x, flat_params, *, specs, tau: float,
                            v_th: float, v_reset: float, eps: float,
                            gate: str = "inline", bm: int = DEFAULT_BM,
                            interpret: bool):
    """Run one planned segment as ONE Pallas kernel.

    x: [T, B, H, W, C] spike input; ``flat_params``: per layer
    (w_kernel, scale, bias) flattened — normal layers pass the
    canonical-padded [Kp, N] weight matrix, depthwise layers the
    [taps, C] tap matrix (see ``repro.kernels.ops._seg_prep``) ->
    spikes [T, B, Hf, Wf, Cf] after the segment's last layer (pooling
    absorbed).

    Grid is one program per batch element; per layer the program holds
    patch matrix, accumulator, spike block, and membrane file in VMEM
    and chains directly into the next layer's im2col.  ``gate``:
    "inline" (per-MAC-tile ``jnp.any`` activity gate) or "none"
    (dense); ``bm`` is the row chunk of the MAC loops — both are
    autotuner decisions (op ``"backbone_seg"``).  Forward only; the
    surrogate-gradient custom VJP (per-segment rematerialisation)
    lives in ``repro.kernels.ops.backbone_segment_op``."""
    if gate not in ("inline", "none"):
        raise ValueError(f"backbone segment gate must be 'inline' or "
                         f"'none', got {gate!r}")
    T, B, H, W, C = x.shape
    if not specs:
        raise ValueError("empty segment")
    if len(flat_params) != 3 * len(specs):
        raise ValueError("flat_params must hold (w, scale, bias) per layer")
    xb = jnp.swapaxes(x, 0, 1)              # [B, T, H, W, C]

    in_specs = [pl.BlockSpec((1, T, H, W, C), lambda b: (b, 0, 0, 0, 0))]
    scratch = []
    h, w = H, W
    for i, s in enumerate(specs):
        wk, scale, bias = flat_params[3 * i:3 * i + 3]
        in_specs += [
            pl.BlockSpec(wk.shape, lambda b, nd=wk.ndim: (0,) * nd),
            pl.BlockSpec(scale.shape, lambda b: (0,)),
            pl.BlockSpec(bias.shape, lambda b: (0,)),
        ]
        _, _, ho = _same_pads(h, s.kernel, s.stride)
        _, _, wo = _same_pads(w, s.kernel, s.stride)
        n = s.cin if s.depthwise else s.cout
        scratch += [pltpu.VMEM((T * ho * wo, n), jnp.float32),
                    pltpu.VMEM((T, 1, ho * wo, n), jnp.float32),
                    pltpu.VMEM((1, ho * wo, n), jnp.float32)]
        h, w = layer_out_hw(s, h, w)
    cf = specs[-1].cin if specs[-1].depthwise else specs[-1].cout

    return pl.pallas_call(
        functools.partial(_segment_kernel, specs=tuple(specs), T=T, H=H,
                          W=W, bm=bm, inline=(gate == "inline"), tau=tau,
                          v_th=v_th, v_reset=v_reset, eps=eps,
                          precision=matmul_precision()),
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((T, 1, h, w, cf),
                               lambda b: (0, b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, h, w, cf), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xb, *flat_params)


# ---------------------------------------------------------------------------
# Gated spike max-pool (the standalone pooling kernel)
# ---------------------------------------------------------------------------

def _pool_block(x_ref, window: int, c: int):
    """Max-pool of one frame laid out ``[1, ho, window, wo, window·C]``
    (a free reshape of ``[H, W, C]``): the H offset is a static index
    on a leading dim and the W offset a static lane slice, so the
    window's values come out of VMEM without strided reads or gathers.
    Max has no rounding: the result is ``lax.reduce_window``'s."""
    out = None
    for di in range(window):
        row = x_ref[0, :, di]               # [ho, wo, window·C]
        for dj in range(window):
            s = row[:, :, dj * c:(dj + 1) * c]
            out = s if out is None else jnp.maximum(out, s)
    return out[None]


def _max_pool_kernel(x_ref, y_ref, *, window: int, gated: bool):
    c = y_ref.shape[-1]
    if gated:
        live = jnp.any(x_ref[...] != 0)

        @pl.when(live)
        def _pool():
            y_ref[...] = _pool_block(x_ref, window, c)

        @pl.when(jnp.logical_not(live))
        def _zero():
            # all-silent frame: max of zeros is zeros (spike tensors
            # are non-negative — see max_pool_pallas docstring)
            y_ref[...] = jnp.zeros_like(y_ref)
    else:
        y_ref[...] = _pool_block(x_ref, window, c)


def max_pool_pallas(xf, *, window: int = 2, gated: bool = True,
                    interpret: bool):
    """Gated spike max-pool.  xf: [N, H, W, C] folded SPIKE tensor ->
    [N, H//window, W//window, C], bit-exact vs ``lax.reduce_window``
    (max, VALID, stride = window).

    Grid is one program per frame; ``gated=True`` skips the reduction
    for an all-silent frame and writes zeros instead — exact ONLY for
    non-negative inputs (spikes), which is the sole tensor this pools.
    Inside a fused backbone segment pooling is absorbed as an epilogue
    reduction (``_pool_slices``) and never launches at all; this
    standalone kernel serves the unfused path on compiled backends."""
    N, H, W, C = xf.shape
    ho, wo = H // window, W // window
    x5 = xf[:, :ho * window, :wo * window].reshape(N, ho, window, wo,
                                                   window * C)

    return pl.pallas_call(
        functools.partial(_max_pool_kernel, window=window, gated=gated),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, ho, window, wo, window * C),
                               lambda i: (i, 0, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, ho, wo, C), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, ho, wo, C), xf.dtype),
        interpret=interpret,
    )(x5)
