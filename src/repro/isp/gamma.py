"""Gamma correction via LUT + RGB->YCbCr conversion (paper §V-B.5).

The FPGA uses a custom LUT and fixed-point matrix arithmetic; we keep the
LUT (256 entries, jnp.take — a VMEM table lookup on TPU) so the NPU can
reshape the curve at runtime without recompilation, and the BT.601
matrix in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.blocks import matmul_precision

LUT_SIZE = 256


def gamma_lut(gamma) -> jax.Array:
    """Build a LUT for out = in^(1/gamma). gamma may be a traced scalar."""
    x = jnp.linspace(0.0, 1.0, LUT_SIZE)
    return x ** (1.0 / jnp.maximum(gamma, 1e-3))


def apply_gamma(rgb, lut: jax.Array) -> jax.Array:
    idx = jnp.clip((rgb * (LUT_SIZE - 1)).astype(jnp.int32), 0, LUT_SIZE - 1)
    frac = rgb * (LUT_SIZE - 1) - idx
    lo = jnp.take(lut, idx)
    hi = jnp.take(lut, jnp.minimum(idx + 1, LUT_SIZE - 1))
    return lo + frac * (hi - lo)          # linear-interp LUT, like the HDL


def gamma_tile(rgb, p, consts=()):
    """Fused-ISP form of ``apply_gamma(rgb, gamma_lut(p["gamma"]))``.
    A Pallas TPU kernel cannot gather from a table, so the two LUT
    entries around each pixel are evaluated in place:
    ``linspace(0, 1, LUT_SIZE)[i]`` is exactly ``i / (LUT_SIZE - 1)``
    in float32, raised to the same power, then interpolated in the
    reference's order."""
    inv = 1.0 / jnp.maximum(p["gamma"], 1e-3)
    idx = jnp.clip((rgb * (LUT_SIZE - 1)).astype(jnp.int32), 0, LUT_SIZE - 1)
    frac = rgb * (LUT_SIZE - 1) - idx
    nxt = jnp.minimum(idx + 1, LUT_SIZE - 1)
    lo = (idx.astype(jnp.float32) / (LUT_SIZE - 1)) ** inv
    hi = (nxt.astype(jnp.float32) / (LUT_SIZE - 1)) ** inv
    return lo + frac * (hi - lo)


_RGB2YCBCR = jnp.array([[0.299, 0.587, 0.114],
                        [-0.168736, -0.331264, 0.5],
                        [0.5, -0.418688, -0.081312]], jnp.float32)


def rgb_to_ycbcr(rgb) -> jax.Array:
    ycc = jnp.einsum("...c,dc->...d", rgb, _RGB2YCBCR)
    return ycc + jnp.array([0.0, 0.5, 0.5])


def ycbcr_to_rgb(ycc) -> jax.Array:
    ycc = ycc - jnp.array([0.0, 0.5, 0.5])
    inv = jnp.linalg.inv(_RGB2YCBCR)
    return jnp.clip(jnp.einsum("...c,dc->...d", ycc, inv), 0.0, 1.0)


def sharpen_luma(rgb, amount) -> jax.Array:
    """Independent luminance sharpening in YCbCr (paper §V-B.5)."""
    ycc = rgb_to_ycbcr(rgb)
    y = ycc[..., 0]
    blur = (y + jnp.roll(y, 1, 0) + jnp.roll(y, -1, 0)
            + jnp.roll(y, 1, 1) + jnp.roll(y, -1, 1)) / 5.0
    y2 = jnp.clip(y + amount * (y - blur), 0.0, 1.0)
    ycc = ycc.at[..., 0].set(y2)
    return ycbcr_to_rgb(ycc)


SHARPEN_RADIUS = 1   # 5-point cross blur on the luma plane

# Array constants the windowed form needs inside a Pallas kernel (a
# kernel body cannot close over non-scalar constants): the BT.601
# matrix, the chroma offset, and the precomputed inverse matrix —
# the same ``jnp.linalg.inv`` the reference folds at trace time.
_YCC_OFFSET = jnp.array([0.0, 0.5, 0.5], jnp.float32)
SHARPEN_CONSTS = (_RGB2YCBCR, _YCC_OFFSET, jnp.linalg.inv(_RGB2YCBCR))


def sharpen_window(win, p, *, bh: int, bw: int, consts=SHARPEN_CONSTS,
                   **_):
    """Tile-resident form for the fused ISP path: ``win`` is a
    ``[bh+2, bw+2, 3]`` halo'd window (wrap-padded, matching the
    reference's cyclic ``jnp.roll``); returns the sharpened
    ``[bh, bw, 3]`` tile.  The colour-space round trip runs on the
    whole window (halo pixels are copies of real pixels, so this is
    exact) and the cross blur replays the reference's summation
    order — bit-identical to :func:`sharpen_luma`."""
    mat, off, inv = consts
    prec = matmul_precision()     # a TPU kernel's default is one bf16 pass
    ycc = jnp.einsum("...c,dc->...d", win, mat, precision=prec) + off
    y = ycc[..., 0]
    # roll(y, 1, 0)[i] == y[i - 1]: same up/down/left/right fold order
    y_c = y[1:-1, 1:-1]
    blur = (y_c + y[0:-2, 1:-1] + y[2:, 1:-1]
            + y[1:-1, 0:-2] + y[1:-1, 2:]) / 5.0
    y2 = jnp.clip(y_c + p["amount"] * (y_c - blur), 0.0, 1.0)
    ycc_c = ycc[1:-1, 1:-1]
    # replace the luma channel (a stack, not a scatter: a TPU kernel
    # has no scatter)
    ycc_c = jnp.stack([y2, ycc_c[..., 1], ycc_c[..., 2]], axis=-1) - off
    return jnp.clip(jnp.einsum("...c,dc->...d", ycc_c, inv,
                               precision=prec), 0.0, 1.0)
