"""Pallas megakernels for the fused Cognitive-ISP streaming path.

The paper's ISP (§V) is a line-buffered streaming datapath: every pixel
flows through the whole stage chain in one pass and never revisits
external memory between stages.  The registry's per-stage backends
instead launch one whole-image op per stage — O(#stages) HBM round
trips per frame.  These kernels are the software analogue of the
FPGA's stream residency: the fusion planner (``repro.isp.fuse``)
segments a stage ordering, and each segment executes as ONE tiled
kernel whose VMEM-resident tile runs the entire segment chain before
touching memory again.

Two kernel shapes cover every segment:

  * ``pointwise_segment_pallas`` — a run of pointwise stages (plus an
    optional leading reduce-stage *apply*).  Blocked in/out specs; the
    tile is loaded once, the whole chain applied, the tile stored once.
  * ``stencil_segment_pallas`` — the same pointwise prologue fused into
    a stencil stage's halo'd window: the window (``[bh+2r, bw+2r]``) is
    sliced from the padded frame, the prologue recomputed on the halo
    (the classic overlapped-tile trade — a few redundant halo pixels
    instead of a full materialised intermediate), then the stage's
    ``window_fn`` emits the output tile.

Stage parameters arrive as ONE packed f32 vector (``pvec``) laid out by
the planner, and global statistics (AWB grey-world gains) as a second
small vector — both traced values, so a single compiled executable
serves every NPU control setting (the FPGA reconfigure-without-
resynthesis discipline).  Halo fill replays each stage's reference
semantics: ``pad="wrap"`` for ``jnp.roll``-style cyclic references,
``pad="zero"`` for SAME-conv references, with the zero halo re-asserted
*after* the prologue so fused output stays bit-identical to running the
stages one by one.

The stencil kernel reads overlapping halo'd windows, one per output
tile, that the wrapper cuts from the halo-padded frame in the same XLA
pass that pads it: every kernel block is then a whole window, so the
kernel needs no dynamic slicing, and a frame of any size tiles through
VMEM.  What the fusion buys is the pass count: per segment the frame
is read and written ONCE (plus the halo overlap), with the whole stage
chain applied per tile in between.

Parameter and statistics vectors enter as ``[1, n]`` rows, so under
``jax.vmap`` (the engine batches frames that way) each frame's row is
still a whole-array block.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.launch.roofline import VMEM_LIMIT_BYTES

BH, BW = 128, 128   # default tile
# A tile with a channel axis ([h, w, 3]) pads each pixel row's 3
# channels to 128 lanes, so it holds ~40x the bytes of its data: such
# tiles take at most one sublane tile of rows, which bounds both the
# VMEM the unrolled stage chain needs and the kernel body the compiler
# has to schedule.
CHANNEL_TILE_ROWS = 8
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


class ChainStep(NamedTuple):
    """One fused stage application inside a segment kernel: ``fn`` is
    the stage's pointwise impl (``(x, params)``; ``(x, params, stats)``
    for a reduce-stage apply; ``(x, params, consts)`` for a tile_fn
    that needs array constants), with its params living at
    ``pvec[offset : offset + len(names)]`` and its constants at
    ``consts[c_offset : c_offset + n_consts]``."""
    fn: Callable
    names: Tuple[str, ...]
    offset: int
    uses_stats: bool = False
    uses_consts: bool = False       # fn is a tile_fn: (x, params, consts)
    c_offset: int = 0
    n_consts: int = 0


def _step_params(step: ChainStep, pv):
    """``pv``: the ``[1, n]`` packed parameter row."""
    return {n: pv[0, step.offset + k] for k, n in enumerate(step.names)}


def _step_consts(step: ChainStep, cv):
    return tuple(cv[step.c_offset:step.c_offset + step.n_consts])


def _apply_chain(x, chain, pv, sv, cv):
    for step in chain:
        p = _step_params(step, pv)
        if step.uses_stats:
            x = step.fn(x, p, sv)
        elif step.uses_consts:
            x = step.fn(x, p, _step_consts(step, cv))
        else:
            x = step.fn(x, p)
    return x


def _tile_geometry(H, W, bh, bw, channels: bool):
    """Clamp the tile to the frame and round the grid up: non-multiple
    H x W runs with a zero-padded fringe that is cropped after the
    call (the fringe feeds no valid output pixel).  ``channels``: the
    tile carries a channel axis (see ``CHANNEL_TILE_ROWS``)."""
    if channels:
        bh = min(bh, CHANNEL_TILE_ROWS)
    bh, bw = min(bh, H), min(bw, W)
    Hp = -(-H // bh) * bh
    Wp = -(-W // bw) * bw
    return bh, bw, Hp, Wp


def _full_spec(shape):
    return pl.BlockSpec(shape, lambda i, j, z=(0,) * len(shape): z)


def _axis_windows(x, b: int, radius: int, axis: int):
    """The overlapping ``b + 2r`` windows at stride ``b`` along ``axis``
    (of length ``n·b + 2r``), as a new tile axis before it.  Window
    ``i`` is block ``i`` plus the head of the blocks after it, so the
    windows come from a reshape and ``ceil(2r / b) + 1`` shifted block
    slices, however many tiles there are."""
    r2 = 2 * radius
    n = (x.shape[axis] - r2) // b
    k = -(-r2 // b)                       # blocks a halo reaches into
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, k * b - r2)
    blocks = jnp.pad(x, pad).reshape(
        x.shape[:axis] + (n + k, b) + x.shape[axis + 1:])
    win = jnp.concatenate(
        [jax.lax.slice_in_dim(blocks, m, m + n, axis=axis)
         for m in range(k + 1)], axis=axis + 1)
    return jax.lax.slice_in_dim(win, 0, b + r2, axis=axis + 1)


def halo_windows(xp, bh: int, bw: int, radius: int):
    """The overlapping ``[bh + 2r, bw + 2r]`` windows of a frame padded
    by ``radius`` on each side, one per output tile:
    ``[Hp + 2r, Wp + 2r, ...] -> [Hp/bh, Wp/bw, bh + 2r, bw + 2r, ...]``.
    A kernel block is then a whole window (no dynamic slicing in the
    kernel), at the cost of re-reading the halo overlap.  (Mosaic
    refuses the alternative, element-indexed blocks read straight from
    the padded frame: their last two dims must be 8/128-aligned or
    whole, and their offsets provably tile-aligned.)"""
    rows = _axis_windows(xp, bh, radius, 0)       # [nty, bh+2r, W', ...]
    return jnp.moveaxis(_axis_windows(rows, bw, radius, 2), 2, 1)


def _rows(pvec, stats):
    """Parameter and statistics vectors as ``[1, n]`` rows."""
    return pvec.reshape(1, -1), stats.reshape(1, -1)


def pointwise_segment_pallas(x, pvec, stats, *, chain: Tuple[ChainStep, ...],
                             consts: Tuple = (), bh: int = BH, bw: int = BW,
                             interpret: bool):
    """x: [H, W] or [H, W, C] -> same shape; ``chain`` applied per
    VMEM-resident tile (one memory pass for the whole pointwise run).
    ``consts``: array constants chain steps need (kernels cannot close
    over non-scalar constants, so they ride along as extra inputs)."""
    H, W = x.shape[:2]
    tail = x.shape[2:]
    bh, bw, Hp, Wp = _tile_geometry(H, W, bh, bw, bool(tail))
    if (Hp, Wp) != (H, W):
        x = jnp.pad(x, ((0, Hp - H), (0, Wp - W)) + ((0, 0),) * len(tail))
    consts = tuple(jnp.asarray(c) for c in consts)
    pvec, stats = _rows(pvec, stats)

    def kernel(x_ref, p_ref, s_ref, *rest):
        c_refs, o_ref = rest[:-1], rest[-1]
        cv = tuple(c[...] for c in c_refs)
        out = _apply_chain(x_ref[...], chain, p_ref[...], s_ref[...][0],
                           cv)
        o_ref[...] = out.astype(o_ref.dtype)

    zeros_tail = (0,) * len(tail)
    block = (bh, bw) + tail
    out = pl.pallas_call(
        kernel,
        grid=(Hp // bh, Wp // bw),
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j) + zeros_tail),
                  _full_spec(pvec.shape), _full_spec(stats.shape)]
                 + [_full_spec(c.shape) for c in consts],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j) + zeros_tail),
        out_shape=jax.ShapeDtypeStruct((Hp, Wp) + tail, x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(x, pvec, stats, *consts)
    return out[:H, :W]


def stencil_segment_pallas(x, pvec, stats, *,
                           prologue: Tuple[ChainStep, ...],
                           window_fn: Callable, wstep: ChainStep,
                           radius: int, pad: str, out_tail: Tuple[int, ...],
                           consts: Tuple = (), bh: int = BH, bw: int = BW,
                           interpret: bool):
    """x: [H, W] or [H, W, C] -> [H, W] + out_tail.  The frame is
    halo-padded ONCE outside the kernel (``pad="wrap"`` replays the
    reference's cyclic ``jnp.roll``; ``pad="zero"`` its SAME-conv
    padding) and cut into overlapping ``[bh+2r, bw+2r]`` windows, one
    per output tile; each grid step recomputes the pointwise
    ``prologue`` on its window and hands it to the stage's
    ``window_fn``.  ``consts``: array constants the window_fn needs (a
    kernel cannot close over non-scalar constants, so they ride along
    as extra inputs)."""
    H, W = x.shape[:2]
    tail = x.shape[2:]
    r = radius
    bh, bw, Hp, Wp = _tile_geometry(H, W, bh, bw,
                                    bool(tail) or bool(out_tail))
    ctail = ((0, 0),) * len(tail)
    xp = jnp.pad(x, ((r, r), (r, r)) + ctail,
                 mode="wrap" if pad == "wrap" else "constant")
    if (Hp, Wp) != (H, W):
        # zero fringe beyond the halo'd frame: it only ever feeds the
        # cropped fringe of the output
        xp = jnp.pad(xp, ((0, Hp - H), (0, Wp - W)) + ctail)
    wshape = (bh + 2 * r, bw + 2 * r)
    wins = halo_windows(xp, bh, bw, r)
    zero_mask = pad == "zero" and bool(prologue)
    consts = tuple(jnp.asarray(c) for c in consts)
    pvec, stats = _rows(pvec, stats)

    def kernel(x_ref, p_ref, s_ref, *rest):
        c_refs, o_ref = rest[:-1], rest[-1]
        cv = tuple(c[...] for c in c_refs)
        i, j = pl.program_id(0), pl.program_id(1)
        y0, x0 = i * bh, j * bw
        win = x_ref[...]
        pv, sv = p_ref[...], s_ref[...][0]
        if prologue:
            win = _apply_chain(win, prologue, pv, sv, cv)
        if zero_mask:
            # re-assert the zero halo AFTER the prologue: the per-stage
            # path zero-pads the prologue's OUTPUT, so halo pixels must
            # read 0, not prologue(0)
            yy = y0 - r + jax.lax.broadcasted_iota(jnp.int32, wshape, 0)
            xx = x0 - r + jax.lax.broadcasted_iota(jnp.int32, wshape, 1)
            ok = (yy >= 0) & (yy < H) & (xx >= 0) & (xx < W)
            ok = ok.reshape(wshape + (1,) * len(tail))
            win = jnp.where(ok, win, 0.0)
        ctx = dict(y0=y0, x0=x0, bh=bh, bw=bw)
        if wstep.n_consts:
            ctx["consts"] = _step_consts(wstep, cv)
        tile = window_fn(win, _step_params(wstep, pv), **ctx)
        o_ref[...] = tile.astype(o_ref.dtype)

    in_zeros = (0,) * len(tail)
    out_zeros = (0,) * len(out_tail)
    out = pl.pallas_call(
        kernel,
        grid=wins.shape[:2],
        in_specs=[pl.BlockSpec((None, None) + wshape + tail,
                               lambda i, j: (i, j, 0, 0) + in_zeros),
                  _full_spec(pvec.shape), _full_spec(stats.shape)]
                 + [_full_spec(c.shape) for c in consts],
        out_specs=pl.BlockSpec((bh, bw) + out_tail,
                               lambda i, j: (i, j) + out_zeros),
        out_shape=jax.ShapeDtypeStruct((Hp, Wp) + out_tail, x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(wins, pvec, stats, *consts)
    return out[:H, :W]
