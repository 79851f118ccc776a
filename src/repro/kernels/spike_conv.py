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

The per-op kernels:

``spike_conv_direct_pallas`` — the normal / strided / 1x1 conv with no
patch matrix in HBM.  Each program takes whole folded frames of the
SAME-padded activation (a strided conv's split into its stride phases,
so every tap window is a unit-stride slice) and builds each canonical
128-wide K block of the patch matrix in VMEM: tap-major,
channel-minor, as ``spike_im2col`` orders K, each tap window stored
into its lane range, the K tail zero.  It then runs ``_conv_kernel``'s
dot on the block and writes ``[rows, N]`` at the output's own width.
``repro.kernels.ops`` sends a conv here by its shape
(``direct_conv_frames``: ``Wo % 8 == 0``, channels that tile 128
lanes, one frame's blocks within a VMEM budget).

``spike_conv_pallas`` — gated ``patches @ wmat`` for the convs the
direct kernel does not take (depthwise uses the block-diagonal-free
kernel below).  Grid (M/bm, N/bn, K/bk), fp32 accumulation in VMEM
scratch; a K-step whose ``occ[i, k]`` bit is clear contributes nothing.

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
the result either.  The direct kernel's dots take the very operands of
the patch kernel's, in the same order, so the two lowerings are
bit-identical.

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

from repro.core.layers import _same_pads
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


# ---------------------------------------------------------------------------
# Direct spike conv: canonical K blocks built in VMEM from the activation
# ---------------------------------------------------------------------------

# Rows a direct-conv program covers at most (whole frames per program,
# as many as fit, so that a small layer still has several grid steps
# for its input DMA to overlap).
DIRECT_MAX_ROWS = 1024
# VMEM a direct-conv program may hold, double-buffered blocks included
# (Mosaic's default scoped limit on a v5e is 16 MiB).
DIRECT_VMEM_BUDGET = 12 * 2 ** 20


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _direct_geometry(H: int, W: int, kh: int, kw: int, stride: int):
    """SAME geometry of the direct conv's phase-split input: (Ho, Wo,
    (lo_h, hi_h), (lo_w, hi_w), Hs, Ws).  The activation is padded to
    ``[stride·Hs, stride·Ws]`` (``hi`` may be negative: rows no tap
    reads are cut) and split into ``stride²`` phases of ``[Hs, Ws]``,
    so tap (i, j) reads phase ``(i % s, j % s)`` at the unit-stride
    offset ``(i // s, j // s)``."""
    lo_h, _, Ho = _same_pads(H, kh, stride)
    lo_w, _, Wo = _same_pads(W, kw, stride)
    Hs, Ws = (kh - 1) // stride + Ho, (kw - 1) // stride + Wo
    return (Ho, Wo, (lo_h, stride * Hs - lo_h - H),
            (lo_w, stride * Ws - lo_w - W), Hs, Ws)


def _direct_vmem_bytes(f: int, *, s2: int, Hs: int, Ws: int, C: int,
                       rows: int, Kp: int, N: int) -> int:
    """VMEM one direct-conv program holds: its double-buffered input,
    weight and output blocks (lane- and sublane-padded f32) plus the
    K-block and accumulator scratch (a row chunk: at most
    ``DIRECT_MAX_ROWS`` rows for any launch ``bm`` up to it)."""
    lanes_c, lanes_n = _ceil_to(C, 128), _ceil_to(N, 128)
    x = f * s2 * Hs * _ceil_to(Ws, 8) * lanes_c
    w = Kp * lanes_n
    y = f * rows * lanes_n
    scratch = min(f * rows, DIRECT_MAX_ROWS) * (CANONICAL_K_BLOCK
                                                + lanes_n)
    return 4 * (2 * (x + w + y) + scratch)


def direct_conv_frames(xshape, wshape, stride: int) -> int:
    """Frames per program of the direct conv for input ``[N, H, W, C]``
    and HWIO weights, or 0 where the shape keeps the patch path.

    The rule reads shapes only:

    * ``Wo % 8 == 0``: a tap window ``[frames, rows, Wo, C]`` collapses
      into matmul rows without crossing the sublane tiling;
    * ``128 % C == 0`` or ``C % 128 == 0``: every tap lies whole inside
      one canonical K block, or every K block inside one tap, so no
      block takes a channel sub-range at a lane offset;
    * one frame's program fits ``DIRECT_VMEM_BUDGET``.

    Frames per program: the largest divisor of N whose rows stay within
    ``DIRECT_MAX_ROWS`` (at least one frame) and whose blocks fit the
    budget.  Depthwise convs never ask (they have their own kernel)."""
    N, H, W, C = xshape
    kh, kw, _, cout = wshape
    Ho, Wo, _, _, Hs, Ws = _direct_geometry(H, W, kh, kw, stride)
    if Wo % 8 or not (CANONICAL_K_BLOCK % C == 0
                      or C % CANONICAL_K_BLOCK == 0):
        return 0
    Kp = _ceil_to(kh * kw * C, CANONICAL_K_BLOCK)
    rows = Ho * Wo
    best = 0
    for f in range(1, N + 1):
        if N % f or (f > 1 and f * rows > DIRECT_MAX_ROWS):
            continue
        if _direct_vmem_bytes(f, s2=stride * stride, Hs=Hs, Ws=Ws, C=C,
                              rows=rows, Kp=Kp,
                              N=cout) > DIRECT_VMEM_BUDGET:
            continue
        best = f
    return best


def _direct_k_blocks(kh: int, kw: int, C: int, stride: int):
    """Per canonical K block (tap-major, channel-minor — the order of
    ``spike_im2col``): the tap windows it holds, each ``(phase, oi, oj,
    c0, c1, lane)``, and the first lane no tap fills (zeros from there
    on, as the patch path's K padding)."""
    K = kh * kw * C
    blocks = []
    for k0 in range(0, K, CANONICAL_K_BLOCK):
        k1 = min(k0 + CANONICAL_K_BLOCK, K)
        parts = []
        for t in range(kh * kw):
            lo, hi = max(k0, t * C), min(k1, (t + 1) * C)
            if lo >= hi:
                continue
            i, j = divmod(t, kw)
            parts.append(((i % stride) * stride + j % stride,
                          i // stride, j // stride,
                          lo - t * C, hi - t * C, lo - k0))
        blocks.append((tuple(parts), k1 - k0))
    return tuple(blocks)


def _largest_divisor(n: int, at_most: int) -> int:
    return max(d for d in range(1, max(1, min(n, at_most)) + 1)
               if n % d == 0)


def _direct_row_chunks(f: int, Ho: int, Wo: int, bm: int):
    """The program's row chunk ``(fc, hc, nh)``: ``fc`` frames × ``hc``
    output rows, about ``bm`` matmul rows — a band of one frame (``nh``
    bands a frame), or whole frames where a frame has fewer than ``bm``
    rows.  Chunks are uniform, so the kernel loops over them rather
    than unrolling each (its trace stays one chunk long); a chunk is
    the gate's and the dot's granularity, as a ``bm`` row tile is the
    patch kernel's."""
    if Ho * Wo >= bm:
        hc = _largest_divisor(Ho, bm // Wo)
        return 1, hc, Ho // hc
    return _largest_divisor(f, bm // (Ho * Wo)), Ho, 1


def _direct_conv_kernel(x_ref, w_ref, y_ref, blk_ref, acc_ref, *, fc: int,
                        hc: int, nh: int, kblocks, Wo: int, gated: bool,
                        precision):
    rows = fc * hc * Wo

    def chunk(c, carry):
        f0, h0 = (c // nh) * fc, (c % nh) * hc
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for kb, (parts, width) in enumerate(kblocks):
            def window(p, oi, oj, c0, c1):
                v = x_ref[pl.ds(f0, fc), p, pl.ds(h0 + oi, hc),
                          oj:oj + Wo, c0:c1]
                return v.reshape(rows, c1 - c0)

            if len(parts) == 1 and width == CANONICAL_K_BLOCK:
                # the block is one tap's aligned channel range
                blk = window(*parts[0][:5])
            else:
                # assemble it lane range by lane range; the tail no tap
                # fills is zero, as the patch path's K padding
                for p, oi, oj, c0, c1, lane in parts:
                    blk_ref[:, lane:lane + c1 - c0] = \
                        window(p, oi, oj, c0, c1)
                if width < CANONICAL_K_BLOCK:
                    blk_ref[:, width:] = jnp.zeros(
                        (rows, CANONICAL_K_BLOCK - width), blk_ref.dtype)
                blk = blk_ref[...]

            def mac(blk=blk, k0=kb * CANONICAL_K_BLOCK):
                acc_ref[...] += jnp.dot(
                    blk.astype(jnp.float32),
                    w_ref[k0:k0 + CANONICAL_K_BLOCK, :].astype(jnp.float32),
                    precision=precision, preferred_element_type=jnp.float32)

            if gated:
                # the activity gate, on the block already in VMEM
                pl.when(jnp.any(blk != 0))(mac)
            else:
                mac()
        r0 = pl.multiple_of(c * rows, 8)
        y_ref[pl.ds(r0, rows), :] = acc_ref[...].astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // rows, chunk, 0)


def spike_conv_direct_pallas(xf, w, *, stride: int = 1, gated: bool = False,
                             bm: int = BM, interpret: bool):
    """SAME conv of the folded spike tensor xf [N, H, W, C] with HWIO
    weights w [kh, kw, C, cout] -> [N·Ho·Wo, cout], without a patch
    matrix in HBM.

    The wrapper pads xf once (SAME, plus the rows that make the extent
    a stride multiple) and, for ``stride > 1``, splits it into its
    ``stride²`` phases in the same copy, so every tap window is a
    unit-stride slice of one phase (Mosaic refuses strided value
    slices).  Each program takes whole frames (``direct_conv_frames``)
    and, per row chunk of about ``bm`` rows, builds every canonical K
    block of the patch matrix in VMEM — the tap windows stored into
    their lane ranges, the K tail zero — and runs the same dot as
    ``_conv_kernel`` on it.  The dots' operands and the order the
    blocks are added in are the patch path's and ``blocked_matmul``'s,
    so the output is bit-identical to theirs.  The output is written at
    its own width, ``cout`` lanes.

    ``gated``: skip the dot of a (chunk, block) whose assembled block is
    all zero (``jnp.any`` on the block in VMEM; its contribution is
    exact zeros, so the gate changes no value).  Dense is the default:
    the blocks' assembly, not the dot, is the kernel's work, and a gate
    branch stalls it (on a TPU v5e the gated kernel was slower in every
    cell of the chip benchmark, the sparse one included)."""
    N, H, W, C = xf.shape
    kh, kw, _, cout = w.shape
    f = direct_conv_frames(xf.shape, w.shape, stride)
    if not f:
        raise ValueError(f"no direct conv for input {xf.shape}, weights "
                         f"{w.shape}, stride {stride}")
    Ho, Wo, (lo_h, hi_h), (lo_w, hi_w), Hs, Ws = _direct_geometry(
        H, W, kh, kw, stride)
    s = stride
    xp = jax.lax.pad(xf, jnp.zeros((), xf.dtype),
                     ((0, 0, 0), (lo_h, hi_h, 0), (lo_w, hi_w, 0),
                      (0, 0, 0)))
    xp = xp.reshape(N, Hs, s, Ws, s, C).transpose(0, 2, 4, 1, 3, 5)
    xp = xp.reshape(N, s * s, Hs, Ws, C)
    K = kh * kw * C
    pk = (-K) % CANONICAL_K_BLOCK
    wmat = w.reshape(K, cout)
    if pk:
        wmat = jnp.pad(wmat, ((0, pk), (0, 0)))
    rows = f * Ho * Wo
    fc, hc, nh = _direct_row_chunks(f, Ho, Wo, bm)
    chunk_rows = fc * hc * Wo
    return pl.pallas_call(
        functools.partial(_direct_conv_kernel, fc=fc, hc=hc, nh=nh,
                          kblocks=_direct_k_blocks(kh, kw, C, s), Wo=Wo,
                          gated=gated, precision=matmul_precision()),
        grid=(N // f,),
        in_specs=[pl.BlockSpec((f, s * s, Hs, Ws, C),
                               lambda n: (n, 0, 0, 0, 0)),
                  pl.BlockSpec((K + pk, cout), lambda n: (0, 0))],
        out_specs=pl.BlockSpec((rows, cout), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((N * Ho * Wo, cout), w.dtype),
        scratch_shapes=[pltpu.VMEM((chunk_rows, CANONICAL_K_BLOCK),
                                   xf.dtype),
                        pltpu.VMEM((chunk_rows, cout), jnp.float32)],
        interpret=interpret,
    )(xp, wmat)


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
