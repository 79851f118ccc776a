"""Pallas TPU kernels: activity-gated spike convolution (event-driven
conv on the MXU via spike-im2col).

The FPGA's event-driven datapath only clocks MAC arrays for neurons
that actually fired; a systolic MXU cannot gate individual lanes, so —
as with ``spike_matmul`` — the TPU-native granularity of "silent
neurons cost nothing" is the VMEM tile.  The conv hot path reaches
that granularity through spike-im2col: the folded ``[B·T, H, W, C]``
spike tensor is lowered to a patch matrix ``[B·T·Ho·Wo, kh·kw·C]``
(see ``repro.core.layers.spike_im2col``) and the conv becomes a tiled
matmul whose LHS inherits the activation sparsity.

What this module adds over ``spike_matmul``'s inline ``jnp.any`` check:
the per-tile spike *occupancy mask* is computed ONCE per call (one
cheap XLA reduction over the patch matrix — the software analogue of
the event list the FPGA datapath is driven by) and enters the kernel
as a scalar side input, so every K-step of the matmul grid consults a
precomputed bit instead of re-reducing its activation tile.  On real
hardware the same mask can feed a scalar-prefetch grid that skips the
tile's DMA as well as its MXU pass; in interpret mode the ``pl.when``
still skips the dot, which is what the dense-vs-gated rows in
``benchmarks/npu_bench.py`` measure.

Two kernels:

``spike_conv_pallas`` — gated ``patches @ wmat`` for normal / strided /
1x1 convs (depthwise uses the block-diagonal-free kernel below).
Grid (M/bm, N/bn, K/bk), fp32 accumulation in VMEM scratch; a K-step
whose ``occ[i, k]`` bit is clear contributes nothing.

``spike_dwconv_pallas`` — depthwise conv as a gated tap loop: patches
``[M, taps, C]`` stay in their per-channel form (a block-diagonal
matmul would waste C× MACs on structural zeros), each program owns a
row block, and the K-loop over taps skips tap slabs whose occupancy
bit is clear.  Pure VPU work — depthwise is memory-bound, so the win
is skipped loads-from-VMEM, not MXU passes.

Bit-exactness contract (tests/test_spike_conv.py, tests/test_tune.py):
every matmul kernel accumulates K in CANONICAL sub-blocks
(``repro.kernels.blocks.CANONICAL_K_BLOCK``) regardless of the launch
``bk`` the autotuner picked — a launch K-step of width ``bk`` walks its
canonical sub-blocks sequentially (``canonical_k_slices``), so the jnp
reference path (``repro.core.layers.spike_conv_jnp``) computes the SAME
blocked accumulation for EVERY legal launch config.  Sweeping block
shapes changes the grid/gating granularity, never the float rounding —
exactly like the norm reduce shape in ``lif_scan.py``.  A skipped
tile's would-be contribution is exact zeros, so gating never changes
the result either.

Tuning & fusion notes (ISSUE 8): launch shapes (``bm``/``bn``/``bk``),
the gate mode, and the conv→LIF fusion boundary are per-(op, shape)
decisions made by ``repro.kernels.tune`` and cached in a persistent
tuning table; ``repro.kernels.ops`` resolves them at dispatch time.
``spike_conv_lif_pallas`` below is the deepest fusion rung: the im2col
conv output never leaves VMEM before the norm+affine+T-step LIF
epilogue fires, collapsing three HBM round-trips (conv out, normed
currents, spikes in / spikes out) into one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import (CANONICAL_K_BLOCK, DEFAULT_BK,
                                  DEFAULT_BM, DEFAULT_BN,
                                  canonical_k_slices, matmul_precision)
from repro.kernels.lif_scan import norm_affine_lif_epilogue
from repro.launch.roofline import VMEM_LIMIT_BYTES

# Default MXU tile sizes (re-exported from repro.kernels.blocks — the
# single source of truth shared with the jnp reference's K-block,
# repro.core.layers.SPIKE_CONV_BLOCK).
BM, BK, BN = DEFAULT_BM, DEFAULT_BK, DEFAULT_BN


def occupancy_mask(patches, *, bm: int = BM, bk: int = BK):
    """Per-(row-block, K-block) spike occupancy of a patch matrix:
    int32 [ceil(M/bm), ceil(K/bk)], 1 where the tile holds at least one
    live (non-zero) activation.  ONE reduction over the patch matrix,
    amortised across the whole (M/bm, N/bn, K/bk) matmul grid."""
    M, K = patches.shape
    pm, pk = (-M) % bm, (-K) % bk
    if pm or pk:
        patches = jnp.pad(patches, ((0, pm), (0, pk)))
    t = patches.reshape((M + pm) // bm, bm, (K + pk) // bk, bk)
    return jnp.any(t != 0, axis=(1, 3)).astype(jnp.int32)


def tap_occupancy_mask(patches3, *, bm: int = BM):
    """Depthwise analogue: int32 [ceil(M/bm), taps], 1 where the row
    block has any live activation under tap t (any channel)."""
    M, taps, C = patches3.shape
    pm = (-M) % bm
    if pm:
        patches3 = jnp.pad(patches3, ((0, pm), (0, 0), (0, 0)))
    t = patches3.reshape((M + pm) // bm, bm, taps, C)
    return jnp.any(t != 0, axis=(1, 3)).astype(jnp.int32)


def _conv_kernel(occ_ref, x_ref, w_ref, y_ref, acc_ref, *, k_steps: int,
                 bk: int, precision):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # activity gate: the tile's precomputed occupancy bit, read from the
    # scalar-prefetched (SMEM) mask
    @pl.when(occ_ref[i * k_steps + k] != 0)
    def _mac():
        # accumulate the launch K-step in canonical sub-blocks so any
        # tuned bk reproduces the reference accumulation order (repro.
        # kernels.blocks — the parity contract)
        for c0, c1 in canonical_k_slices(bk):
            acc_ref[...] += jnp.dot(x_ref[:, c0:c1].astype(jnp.float32),
                                    w_ref[c0:c1, :].astype(jnp.float32),
                                    precision=precision,
                                    preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def spike_conv_pallas(patches, wmat, *, gated: bool = True, bm: int = BM,
                      bk: int = BK, bn: int = BN, interpret: bool):
    """patches: [M, K] spike patch matrix, wmat: [K, N] -> patches @ wmat
    with occupancy-gated K-steps.  ``gated=False`` runs the identical
    kernel with an all-ones mask — the dense baseline the benchmark
    sweep compares against.  ``bm``/``bk``/``bn`` are the (autotunable)
    launch tile shapes; canonical-multiple ``bk`` (what the tuner
    sweeps) is bit-exact vs the jnp reference, other widths are merely
    numerically close (short tail slice — see blocks.py)."""
    M, K = patches.shape
    _, N = wmat.shape
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    x = jnp.pad(patches, ((0, pm), (0, pk))) if pm or pk else patches
    w = jnp.pad(wmat, ((0, pk), (0, pn))) if pk or pn else wmat
    Mp, Kp, Np = M + pm, K + pk, N + pn
    k_steps = Kp // bk
    if gated:
        occ = occupancy_mask(patches, bm=bm, bk=bk)
    else:
        occ = jnp.ones((Mp // bm, k_steps), jnp.int32)

    y = pl.pallas_call(
        functools.partial(_conv_kernel, k_steps=k_steps, bk=bk,
                          precision=matmul_precision()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm, Np // bn, k_steps),
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k, occ: (i, k)),
                      pl.BlockSpec((bk, bn), lambda i, j, k, occ: (k, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, occ: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), wmat.dtype),
        interpret=interpret,
    )(occ.reshape(-1), x, w)
    return y[:M, :N]


def _dwconv_kernel(occ_ref, x_ref, w_ref, y_ref, acc_ref, *, taps: int):
    i = pl.program_id(0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for t in range(taps):                  # static K-loop over taps

        @pl.when(occ_ref[i * taps + t] != 0)   # skip silent tap slabs
        def _mac(t=t):
            acc_ref[...] += x_ref[t] * w_ref[t:t + 1, :]

    y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def spike_dwconv_pallas(patches3, wflat, *, gated: bool = True,
                        bm: int = BM, lane: int = 128,
                        interpret: bool):
    """patches3: [M, taps, C] per-channel spike patches, wflat: [taps, C]
    -> [M, C] depthwise conv output (sum over taps of x[:, t, :] * w[t]).
    Accumulates taps in the same order as the jnp tap loop
    (``repro.core.layers.spike_conv_jnp``) — elementwise VPU work, so
    row/lane blocking cannot perturb bits.  The kernel reads the patches
    tap-major (``[taps, M, C]``), so each tap slab is a whole
    ``(bm, C)`` tile."""
    M, taps, C = patches3.shape
    pm, pc = (-M) % bm, (-C) % lane
    x = jnp.swapaxes(patches3, 0, 1)
    if pm or pc:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pc)))
    w = jnp.pad(wflat, ((0, 0), (0, pc))) if pc else wflat
    Mp, Cp = M + pm, C + pc
    if gated:
        occ = tap_occupancy_mask(patches3, bm=bm)
    else:
        occ = jnp.ones((Mp // bm, taps), jnp.int32)

    y = pl.pallas_call(
        functools.partial(_dwconv_kernel, taps=taps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm,),
            in_specs=[pl.BlockSpec((taps, bm, Cp), lambda i, occ: (0, i, 0)),
                      pl.BlockSpec((taps, Cp), lambda i, occ: (0, 0))],
            out_specs=pl.BlockSpec((bm, Cp), lambda i, occ: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bm, Cp), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Mp, Cp), wflat.dtype),
        interpret=interpret,
    )(occ.reshape(-1), x, w)
    return y[:M, :C]


# ---------------------------------------------------------------------------
# Fused conv→LIF epilogue: the whole spiking-conv layer in one kernel
# ---------------------------------------------------------------------------

def slab_occupancy_mask(x3, *, bm: int):
    """Per-(batch, row-chunk, canonical-K-block) spike occupancy of the
    batched patch slab x3 [B, T·HW, Kp] (Kp already canonical-padded):
    int32 [B, ceil(T·HW/bm), Kp/CANONICAL_K_BLOCK], 1 where the tile
    holds at least one live activation.  One reduction, amortised over
    every gated MAC of the fused kernel."""
    B, THW, Kp = x3.shape
    pr = (-THW) % bm
    if pr:
        x3 = jnp.pad(x3, ((0, 0), (0, pr), (0, 0)))
    n_rc = (THW + pr) // bm
    t = x3.reshape(B, n_rc, bm, Kp // CANONICAL_K_BLOCK,
                   CANONICAL_K_BLOCK)
    return jnp.any(t != 0, axis=(2, 4)).astype(jnp.int32)


def _conv_lif_kernel(occ_ref, x_ref, w_ref, scale_ref, bias_ref, s_ref,
                     acc_ref, u_ref, *, T: int, HW: int, k_steps: int,
                     bm: int, inline: bool, tau: float, v_th: float,
                     v_reset: float, eps: float, precision):
    THW = T * HW
    n_rc = -(-THW // bm)
    b = pl.program_id(0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for rc in range(n_rc):
        r0, r1 = rc * bm, min((rc + 1) * bm, THW)
        for k in range(k_steps):
            c0 = k * CANONICAL_K_BLOCK
            c1 = c0 + CANONICAL_K_BLOCK
            if inline:
                # in-kernel re-reduction of the activation tile (the
                # spike_matmul-style gate the tuner can pick when the
                # one-shot mask pass doesn't pay for itself)
                cond = jnp.any(x_ref[0, r0:r1, c0:c1] != 0)
            else:
                cond = occ_ref[(b * n_rc + rc) * k_steps + k] != 0

            @pl.when(cond)                 # activity gate per MAC tile
            def _mac(r0=r0, r1=r1, c0=c0, c1=c1):
                acc_ref[r0:r1, :] += jnp.dot(
                    x_ref[0, r0:r1, c0:c1].astype(jnp.float32),
                    w_ref[c0:c1, :].astype(jnp.float32), precision=precision,
                    preferred_element_type=jnp.float32)
    # the conv output never leaves VMEM: run the exact shared epilogue
    # (instance-norm + affine + T-step LIF) on the resident accumulator
    y = acc_ref[...].reshape(T, 1, HW, acc_ref.shape[-1])
    norm_affine_lif_epilogue(y, scale_ref[...], bias_ref[...], s_ref,
                             u_ref, tau=tau, v_th=v_th, v_reset=v_reset,
                             eps=eps, T=T)


def spike_conv_lif_pallas(patches, wmat, scale, bias, *, T: int, B: int,
                          HW: int, tau: float, v_th: float,
                          v_reset: float, eps: float, gate: str = "mask",
                          bm: int = BM, interpret: bool):
    """The fused spiking-conv layer: ``patches @ wmat`` + instance-norm
    + affine + T-step LIF in ONE kernel pass.

    patches: [B·T·HW, K] spike patch matrix in the batch-major row
    order ``spike_im2col`` produces on the folded [B·T, H, W, C]
    activation (HW = Ho·Wo output pixels); wmat: [K, N]; scale, bias:
    [N] -> spikes [T, B, HW, N].

    Grid is one program per batch element — each program owns its full
    [T·HW, K] patch slab and [T·HW, N] accumulator, MACs in canonical
    K sub-blocks gated per (row-chunk, K-block) activity (``gate``:
    "mask" one-shot precomputed occupancy / "inline" in-kernel
    ``jnp.any`` / "none" dense), then runs the SHARED
    ``norm_affine_lif_epilogue`` on the resident accumulator.  Against
    the per-op path that is one HBM round-trip instead of three: the
    conv output, the normed currents, and the spike input of the
    separate epilogue kernel never exist in HBM.

    Bit-exactness: canonical-block accumulation order identical to the
    jnp reference and the unfused kernel; the epilogue is the same
    function ``norm_affine_lif_pallas`` runs.  Forward only — the
    surrogate-gradient custom VJP lives in
    ``repro.kernels.ops.spike_conv_lif_op``.

    Shape note: slabs are left lane-unpadded (Mosaic pads N/K to the
    128-lane register file), and a program holds its whole slab, so
    the kernel asks for ``VMEM_LIMIT_BYTES`` of scoped VMEM.
    """
    M, K = patches.shape
    N = wmat.shape[1]
    if M != B * T * HW:
        raise ValueError(f"patches rows {M} != B*T*HW = {B * T * HW}")
    pk = (-K) % CANONICAL_K_BLOCK
    x3 = patches.reshape(B, T * HW, K)
    if pk:
        x3 = jnp.pad(x3, ((0, 0), (0, 0), (0, pk)))
    w = jnp.pad(wmat, ((0, pk), (0, 0))) if pk else wmat
    Kp = K + pk
    k_steps = Kp // CANONICAL_K_BLOCK
    n_rc = -(-(T * HW) // bm)
    if gate == "mask":
        occ = slab_occupancy_mask(x3, bm=bm)
    else:
        occ = jnp.ones((B, n_rc, k_steps), jnp.int32)

    return pl.pallas_call(
        functools.partial(_conv_lif_kernel, T=T, HW=HW, k_steps=k_steps,
                          bm=bm, inline=(gate == "inline"), tau=tau,
                          v_th=v_th, v_reset=v_reset, eps=eps,
                          precision=matmul_precision()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, T * HW, Kp),
                                   lambda b, occ: (b, 0, 0)),
                      pl.BlockSpec((Kp, N), lambda b, occ: (0, 0)),
                      pl.BlockSpec((N,), lambda b, occ: (0,)),
                      pl.BlockSpec((N,), lambda b, occ: (0,))],
            out_specs=pl.BlockSpec((T, 1, HW, N),
                                   lambda b, occ: (0, b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((T * HW, N), jnp.float32),
                            pltpu.VMEM((1, HW, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, B, HW, N), wmat.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(occ.reshape(-1), x3, w, scale, bias)
