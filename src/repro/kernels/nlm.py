"""Pallas TPU kernel: FPGA-adapted Non-Local Means (7x7 search, 3x3
patches), tiled with VMEM halos.

The Koizumi-Maruyama FPGA design bounds the search window so the whole
working set sits in line buffers; the TPU tile reads a halo of
``r_search + r_patch`` = 4 pixels and evaluates all 49 candidate shifts
with shifted-difference + separable box-filter algebra (VPU-only, no
gathers).  Patch distances come from the luminance plane (shared across
channels, as in repro.isp.nlm); halos wrap to match the reference's
cyclic jnp.roll.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.isp_fused import halo_windows

HALO = 4   # 3 (search radius) + 1 (patch radius)


def _nlm_kernel(lum_ref, chan_ref, h_ref, out_ref, *, bh: int, bw: int):
    hp = h_ref[0]
    lwin, cwin = lum_ref[...], chan_ref[...]     # wrap-halo'd windows

    def box3(x):
        s = x[0:-2] + x[1:-1] + x[2:]
        s = s[:, 0:-2] + s[:, 1:-1] + s[:, 2:]
        return s / 9.0

    centre_l = lwin[HALO - 1:HALO + bh + 1, HALO - 1:HALO + bw + 1]
    wsum = jnp.zeros((bh, bw), jnp.float32)
    acc = jnp.zeros((bh, bw), jnp.float32)
    for dy in range(-3, 4):
        for dx in range(-3, 4):
            sh_l = lwin[HALO + dy - 1:HALO + dy + bh + 1,
                        HALO + dx - 1:HALO + dx + bw + 1]
            d2 = box3((centre_l - sh_l) ** 2)
            w = jnp.exp(-d2 / (hp * hp))
            wsum += w
            acc += w * cwin[HALO + dy:HALO + dy + bh,
                            HALO + dx:HALO + dx + bw]
    out_ref[...] = (acc / jnp.maximum(wsum, 1e-9)).astype(out_ref.dtype)


def nlm_pallas(img, strength, *, bh: int = 128, bw: int = 128,
               interpret: bool):
    """img: [H, W] or [H, W, C] in [0,1]; strength scalar in [0,1].
    Requires H % bh == W % bw == 0 (wrap halo must wrap the true image).
    """
    single = img.ndim == 2
    chans = img[..., None] if single else img
    H, W, C = chans.shape
    bh, bw = min(bh, H), min(bw, W)
    assert H % bh == 0 and W % bw == 0, "NLM kernel needs divisible tiles"
    lum = jnp.mean(chans, axis=-1)
    h = jnp.atleast_1d(1e-3 + 0.2 * jnp.asarray(strength, jnp.float32))

    def windows(plane):
        return halo_windows(jnp.pad(plane, HALO, mode="wrap"), bh, bw,
                            HALO)

    win_spec = pl.BlockSpec((None, None, bh + 2 * HALO, bw + 2 * HALO),
                            lambda i, j: (i, j, 0, 0))
    call = pl.pallas_call(
        functools.partial(_nlm_kernel, bh=bh, bw=bw),
        grid=(H // bh, W // bw),
        in_specs=[win_spec, win_spec,
                  pl.BlockSpec((1,), lambda i, j: (0,))],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((H, W), img.dtype),
        interpret=interpret,
    )
    lwins = windows(lum)
    out = jnp.stack([call(lwins, windows(chans[..., c]), h)
                     for c in range(C)], -1)
    return out[..., 0] if single else out
