"""Pallas TPU kernel: Malvar-He-Cutler demosaic, tiled with VMEM halos.

The FPGA streams rows through 5-line buffers; each grid step reads one
(bh+4, bw+4) halo'd window of the zero-padded mosaic (cut by the
wrapper, ``isp_fused.halo_windows``) and emits a (bh, bw, 3) RGB tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.isp.demosaic import _F_G, _F_RB_COL, _F_RB_DIAG, _F_RB_ROW
from repro.kernels.isp_fused import halo_windows

BH, BW = 128, 128


def _demosaic_kernel(raw_ref, out_ref, *, bh: int, bw: int):
    y0 = pl.program_id(0) * bh
    x0 = pl.program_id(1) * bw
    win = raw_ref[...]                 # zero halo: SAME conv padding

    def conv5(kern):
        acc = jnp.zeros((bh, bw), jnp.float32)
        for dy in range(5):
            for dx in range(5):
                kv = float(kern[dy, dx])
                if kv == 0.0:
                    continue
                acc += kv * win[dy:dy + bh, dx:dx + bw]
        return acc

    g_i = conv5(_F_G)
    rb_row = conv5(_F_RB_ROW)
    rb_col = conv5(_F_RB_COL)
    rb_diag = conv5(_F_RB_DIAG)
    center = win[2:2 + bh, 2:2 + bw]

    yy = y0 + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
    xx = x0 + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
    ey, ex = (yy % 2 == 0), (xx % 2 == 0)
    is_r, is_g1 = ey & ex, ey & ~ex
    is_g2, is_b = ~ey & ex, ~ey & ~ex

    g = jnp.where(is_r | is_b, g_i, center)
    r = jnp.where(is_r, center,
                  jnp.where(is_g1, rb_row,
                            jnp.where(is_g2, rb_col, rb_diag)))
    b = jnp.where(is_b, center,
                  jnp.where(is_g2, rb_row,
                            jnp.where(is_g1, rb_col, rb_diag)))
    rgb = jnp.stack([r, g, b], axis=-1)
    out_ref[...] = jnp.clip(rgb, 0.0, 1.0).astype(out_ref.dtype)


def demosaic_pallas(raw, *, bh: int = BH, bw: int = BW,
                    interpret: bool):
    """raw: [H, W] RGGB in [0,1] -> RGB [H, W, 3]."""
    H, W = raw.shape
    ph, pw = (-H) % bh, (-W) % bw
    rp = jnp.pad(raw, ((0, ph), (0, pw))) if (ph or pw) else raw
    Hp, Wp = H + ph, W + pw

    wins = halo_windows(jnp.pad(rp, ((2, 2), (2, 2))), bh, bw, 2)
    out = pl.pallas_call(
        functools.partial(_demosaic_kernel, bh=bh, bw=bw),
        grid=(Hp // bh, Wp // bw),
        in_specs=[pl.BlockSpec((None, None, bh + 4, bw + 4),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((bh, bw, 3), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((Hp, Wp, 3), raw.dtype),
        interpret=interpret,
    )(wins)
    return out[:H, :W]
