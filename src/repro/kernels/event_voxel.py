"""Pallas TPU kernel: batched DVS event voxelization (paper §IV-A).

FPGA insight -> TPU mapping: the FPGA front-end drains the event FIFO
into BRAM-resident time-surface bins as events arrive; the TPU
equivalent keeps the voxel block for a ``time_steps`` slice resident in
VMEM and streams the (bounded) event buffer past it, so the scatter
never round-trips HBM per event.

Grid: ``(batch, ceil(T / block_t))`` — one program owns the voxel
slab of ``block_t`` time bins in VMEM plus the sample's event buffer in
SMEM, loops the events once, and adds each live event into its slab
tile.  The binning arithmetic (time bin, bounds checks, flat voxel
index) is the reference's own, evaluated as one XLA pass before the
kernel; the kernel sees one int32 code per event.

Layout: the slab is the flat ``[block_t, H, W, 2]`` grid padded to
whole ``(8, 128)`` tiles, viewed as ``[tiles, 8, 128]``.  An event's
flat index picks its tile through the LEADING dim (a dynamic index the
TPU handles as an address offset) and its sublane/lane through a
one-hot mask, so every update is one aligned vector read-modify-write.
Mode post-processing happens on the slab while it is still in VMEM:
"binary" thresholds it, "signed" is folded into the per-event weights
(an ON event adds (+1, +1) to (net, total), an OFF event (-1, +1)).

Semantics are defined by the jnp twin ``repro.core.encoding
.events_to_voxel`` and must stay BIT-IDENTICAL to it (differential
tests in tests/test_event_voxel.py):

- invalid events and out-of-bounds ``x``/``y``/``p`` are dropped;
- timestamps are binned by ``floor(t / window * T)``; out-of-range bins
  follow ``oob``: "clip" aliases them into the edge bins, "drop"
  discards the event;
- ``mode``: "count" accumulates per-polarity counts, "binary"
  thresholds occupancy to {0, 1}, "signed" rewrites the polarity axis
  to (ON - OFF, ON + OFF).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one source of truth with the jnp twin — the parity contract covers
# the accepted configuration space too
from repro.core.encoding import OOB_POLICIES, VOXEL_MODES as MODES


_TILE = 8 * 128          # elements per (8, 128) slab tile


def _voxel_kernel(code_ref, o_ref, *, n_events: int, slab: int, mode: str):
    o_ref[...] = jnp.zeros_like(o_ref)
    lo = pl.program_id(1) * slab             # this program's slab offset
    sub_ids = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def body(e, _):
        c = code_ref[e] - lo                 # dead events carry code -1

        @pl.when((c >= 0) & (c < slab))
        def _add():
            row = c >> 10
            sub = (c >> 7) & 7
            lane = c & 127
            here = sub_ids == sub
            if mode == "signed":
                # (net, total) slots sit on the even/odd lane pair
                base = lane & ~1
                on = (lane & 1).astype(jnp.float32)
                w = (jnp.where(here & (lane_ids == base), 2.0 * on - 1.0,
                               0.0)
                     + jnp.where(here & (lane_ids == base + 1), 1.0, 0.0))
            else:
                w = jnp.where(here & (lane_ids == lane), 1.0, 0.0)
            o_ref[0, 0, row] += w
        return 0

    jax.lax.fori_loop(0, n_events, body, 0)

    if mode == "binary":
        o_ref[...] = (o_ref[...] > 0).astype(jnp.float32)


def event_codes(t, x, y, p, valid, *, time_steps: int, height: int,
                width: int, window: float, oob: str, block_t: int,
                slab: int):
    """Per-event int32 code: the event's index in the padded slab
    layout (``time block * slab + flat index inside the block``), or
    -1 for a dropped event.  The binning, bounds checks and clip/drop
    policy are ``repro.core.encoding.events_to_voxel``'s own."""
    tbin = jnp.floor(t / window * time_steps).astype(jnp.int32)
    ok = ((valid != 0)
          & (x >= 0) & (x < width)
          & (y >= 0) & (y < height)
          & (p >= 0) & (p < 2))
    if oob == "drop":
        ok = ok & (tbin >= 0) & (tbin < time_steps)
    tbin = jnp.clip(tbin, 0, time_steps - 1)
    blk = tbin // block_t
    local = (((tbin - blk * block_t) * height + y) * width + x) * 2 + p
    return jnp.where(ok, blk * slab + local, -1).astype(jnp.int32)


def event_voxel_pallas(t, x, y, p, valid, *, time_steps: int, height: int,
                       width: int, window: float = 1.0,
                       mode: str = "binary", oob: str = "clip",
                       block_t: int = 0, interpret: bool):
    """Batched event buffers -> voxel grids [B, T, H, W, 2].

    ``t``: [B, N] float32; ``x``/``y``/``p``/``valid``: [B, N] int32
    (``valid`` nonzero = live event).  ``block_t`` = time-bins per VMEM
    slab (0 picks ``min(T, 8)``).
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if oob not in OOB_POLICIES:
        raise ValueError(f"oob must be one of {OOB_POLICIES}, got {oob!r}")
    B, N = t.shape
    bt = block_t or min(time_steps, 8)
    bt = min(bt, time_steps)
    n_blk = pl.cdiv(time_steps, bt)
    used = bt * height * width * 2
    slab = pl.cdiv(used, _TILE) * _TILE
    codes = event_codes(t, x, y, p, valid, time_steps=time_steps,
                        height=height, width=width, window=window, oob=oob,
                        block_t=bt, slab=slab)
    # the per-sample SMEM block must be lane-aligned: pad the buffer
    # with dead events
    n_pad = pl.cdiv(N, 128) * 128
    if n_pad != N:
        codes = jnp.pad(codes, ((0, 0), (0, n_pad - N)), constant_values=-1)
    rows = slab // _TILE
    out = pl.pallas_call(
        functools.partial(_voxel_kernel, n_events=n_pad, slab=slab,
                          mode=mode),
        grid=(B, n_blk),
        in_specs=[pl.BlockSpec((n_pad,), lambda b, i: (b,),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, 1, rows, 8, 128),
                               lambda b, i: (b, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_blk, rows, 8, 128),
                                       jnp.float32),
        interpret=interpret,
    )(codes.reshape(B * n_pad))
    out = out.reshape(B, n_blk, slab)[:, :, :used]
    out = out.reshape(B, n_blk * bt, height, width, 2)
    return out[:, :time_steps]
