"""Pallas TPU kernels: fused multi-step LIF scan and the fused
instance-norm + affine + LIF pass (the NPU hot loop).

FPGA insight -> TPU mapping (DESIGN.md §2): the FPGA updates membrane
potentials in registers as events arrive; the TPU equivalent keeps the
membrane-potential vector resident in VMEM across all T timesteps, so
the recurrence costs ONE HBM round-trip per neuron block for the whole
window instead of T round-trips (the naive lax.scan materialises u to
HBM every step).

Two kernels:

``lif_scan_pallas`` — flat [T, N] LIF recurrence; grid is one program
per neuron block, currents [T, BN] in VMEM, u in a VMEM scratch
register file.

``norm_affine_lif_pallas`` — the spiking-conv epilogue fused into one
VMEM-resident pass: per-channel instance-norm statistics over (T, H·W),
the tdBN-style affine, and the T-step LIF recurrence, on batched
[T, B·HW, C] slabs (grid over B; each program owns one batch element's
full [T, HW, C] slab so the statistics reduce entirely in VMEM).  The
FlashAttention discipline applied to the SNN epilogue: never let the
normalised pre-activations round-trip to HBM between norm and fire.

Bit-exactness contract: both kernels compute the decay constant, the
normalisation statistics, and the threshold comparison with the exact
formulations of the jnp reference path (``repro.core.lif.lif_scan`` and
``repro.core.layers.apply_spiking_conv``), so forward parity is
bit-for-bit, not allclose — asserted by tests/test_lif_backend.py.
In particular ``decay`` is evaluated as a float32 ``jnp.exp`` (NOT
``math.exp``'s float64, whose double rounding can flip the last bit)
and the fire condition is ``(u - v_th >= 0)`` exactly like the
surrogate ``spike(u - v_th)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import DEFAULT_LIF_BLOCK_N

BLOCK_N = DEFAULT_LIF_BLOCK_N


def _f32_decay(tau: float):
    """exp(-1/tau) traced as the float32 ``jnp.exp`` the reference path
    uses (``math.exp`` would round in float64 first — double rounding
    can flip the last mantissa bit and break bit-parity)."""
    return jnp.exp(-1.0 / tau).astype(jnp.float32)


def _lif_kernel(i_ref, s_ref, u_ref, *, tau: float, v_th: float,
                v_reset: float, T: int):
    decay = _f32_decay(tau)
    u_ref[...] = jnp.full_like(u_ref, v_reset)

    def step(t, _):
        u = decay * (u_ref[...] - v_reset) + v_reset + i_ref[t, :]
        s = ((u - v_th) >= 0).astype(u.dtype)
        u_ref[...] = u * (1.0 - s) + v_reset * s
        s_ref[t, :] = s
        return 0

    jax.lax.fori_loop(0, T, step, 0)


def lif_scan_pallas(currents, *, tau: float = 2.0, v_th: float = 1.0,
                    v_reset: float = 0.0, block_n: int = BLOCK_N,
                    interpret: bool):
    """currents: [T, N] -> spikes [T, N] (forward only; the custom-VJP
    wrapper ``repro.kernels.ops.lif_scan_op`` adds the surrogate-grad
    backward so this path is legal under BPTT training)."""
    T, N = currents.shape
    pad = (-N) % block_n
    if pad:
        currents = jnp.pad(currents, ((0, 0), (0, pad)))
    Np = N + pad

    out = pl.pallas_call(
        functools.partial(_lif_kernel, tau=tau, v_th=v_th,
                          v_reset=v_reset, T=T),
        grid=(Np // block_n,),
        in_specs=[pl.BlockSpec((T, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((T, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((T, Np), currents.dtype),
        scratch_shapes=[pltpu.VMEM((block_n,), jnp.float32)],
        interpret=interpret,
    )(currents)
    return out[:, :N]


def norm_affine_lif_epilogue(y, scale, bias, s_ref, u_ref, *,
                             tau: float, v_th: float, v_reset: float,
                             eps: float, T: int):
    """The VMEM-resident spiking-conv epilogue, shared verbatim by
    ``norm_affine_lif_pallas`` and the fused conv→LIF kernel
    (``repro.kernels.spike_conv.spike_conv_lif_pallas``): per-channel
    instance-norm statistics over (T, HW), the tdBN-style affine, and
    the T-step LIF recurrence.

    ``y``: resident values [T, 1, HW, C]; ``scale``/``bias``: [C]
    values; writes spikes into ``s_ref`` ([T, 1, HW, C] block) using
    ``u_ref`` ([1, HW, C]) as the membrane register file.  Because both
    kernels run this exact function, conv→LIF fusion cannot drift from
    the per-op path by construction — the bit-parity contract is shared
    code, not parallel implementations.
    """
    decay = _f32_decay(tau)
    # per-channel instance-norm statistics over (T, HW) — the whole
    # reduction extent is resident, so one pass, no cross-program
    # accumulation (which would also break bit-parity with the jnp
    # reference's single reduce)
    mu = jnp.mean(y, axis=(0, 2), keepdims=True)
    var = jnp.var(y, axis=(0, 2), keepdims=True)
    z = (y - mu) * jax.lax.rsqrt(var + eps)
    # the affine currents wait in the spike block: step t reads its
    # input from the ref at a leading-dim index (Mosaic cannot slice a
    # value at a dynamic index) and overwrites it with its spikes
    s_ref[...] = (z * scale + bias).astype(s_ref.dtype)

    u_ref[...] = jnp.full_like(u_ref, v_reset)

    def step(t, _):
        u = decay * (u_ref[...] - v_reset) + v_reset + s_ref[t]
        s = ((u - v_th) >= 0).astype(u.dtype)
        u_ref[...] = u * (1.0 - s) + v_reset * s
        s_ref[t] = s
        return 0

    jax.lax.fori_loop(0, T, step, 0)


def _norm_lif_kernel(y_ref, scale_ref, bias_ref, s_ref, u_ref, *,
                     tau: float, v_th: float, v_reset: float,
                     eps: float, T: int):
    norm_affine_lif_epilogue(y_ref[...], scale_ref[...], bias_ref[...],
                             s_ref, u_ref, tau=tau, v_th=v_th,
                             v_reset=v_reset, eps=eps, T=T)


def norm_affine_lif_pallas(y, scale, bias, *, tau: float = 2.0,
                           v_th: float = 1.0, v_reset: float = 0.0,
                           eps: float = 1e-6, interpret: bool):
    """Fused spiking-conv epilogue.  y: [T, B, HW, C] pre-norm currents;
    scale, bias: [C] -> spikes [T, B, HW, C].

    Grid is one program per batch element; each program's [T, HW, C]
    slab (statistics extent + recurrence state) stays VMEM-resident for
    the whole pass.  At this repo's reduced shapes a slab is well under
    VMEM; larger frames would block HW with a two-pass (stats, then
    fire) grid — deliberately not done here to keep the single-pass
    bit-parity contract.
    """
    T, B, HW, C = y.shape

    return pl.pallas_call(
        functools.partial(_norm_lif_kernel, tau=tau, v_th=v_th,
                          v_reset=v_reset, eps=eps, T=T),
        grid=(B,),
        in_specs=[pl.BlockSpec((T, 1, HW, C), lambda b: (0, b, 0, 0)),
                  pl.BlockSpec((C,), lambda b: (0,)),
                  pl.BlockSpec((C,), lambda b: (0,))],
        out_specs=pl.BlockSpec((T, 1, HW, C), lambda b: (0, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, HW, C), y.dtype),
        scratch_shapes=[pltpu.VMEM((1, HW, C), jnp.float32)],
        interpret=interpret,
    )(y, scale, bias)
