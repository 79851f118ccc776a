"""Tone-mapping and colour-matrix stages (registry extensions).

Neither exists on the paper's FPGA; they are the first stages added
*through* the registry rather than into the fixed pipeline, and show the
pattern for growing the ISP (HDR capture, colour-accurate crops) without
touching the pipeline core.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.isp.gamma import _RGB2YCBCR
from repro.kernels.blocks import matmul_precision

_LUMA = _RGB2YCBCR[0]                                    # BT.601 luma row


def reinhard_tonemap(rgb, strength) -> jax.Array:
    """Global Reinhard operator ``y = x (1+k) / (x+k)`` with the knee
    ``k`` driven by ``strength`` in [0, 1]: strength 0 gives k >> 1
    (near-identity), strength 1 compresses highlights hard.  Normalised
    so y(1) = 1 — the output stays in [0, 1]."""
    k = 1.0 / (1e-3 + 4.0 * strength)
    return jnp.clip(rgb * (1.0 + k) / (rgb + k), 0.0, 1.0)


def apply_saturation(rgb, saturation) -> jax.Array:
    """Luma-preserving saturation: blend each pixel toward/away from its
    BT.601 luma.  saturation 1 is identity, 0 is greyscale, 2 doubles
    chroma — a rank-1 colour-correction matrix the NPU can steer."""
    lum = jnp.einsum("...c,c->...", rgb, _LUMA)[..., None]
    return jnp.clip(lum + saturation * (rgb - lum), 0.0, 1.0)


# Tile-resident form for the fused ISP path: the luma row is an array
# constant a Pallas kernel cannot close over, so it rides in as a
# kernel input (``fuse_consts``).  Same op order as apply_saturation —
# fused and per-stage outputs stay bit-identical.
CCM_CONSTS = (_LUMA,)


def apply_saturation_tile(rgb, p, consts=CCM_CONSTS) -> jax.Array:
    # a TPU kernel's default contraction is one bf16 pass
    lum = jnp.einsum("...c,c->...", rgb, consts[0],
                     precision=matmul_precision())[..., None]
    return jnp.clip(lum + p["saturation"] * (rgb - lum), 0.0, 1.0)
