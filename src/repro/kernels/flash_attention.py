"""Pallas TPU kernel: blocked flash attention forward (beyond-paper
optimisation for the LM stack; the jnp flash-scan in models/attention.py
is the oracle and the autodiff path).

Grid (batch*heads, Sq/bq, Sk/bk); online-softmax state (m, l, acc) in
VMEM scratch, flushed on the final K step.  Causal tiles fully in the
future are masked to -inf (compute-skipped tiles would use
``pl.when`` + grid pruning on real hardware; kept simple here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bq: int, bk: int, k_steps: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale     # [bq, d]
    k = k_ref[0].astype(jnp.float32)             # [bk, d]
    v = v_ref[0].astype(jnp.float32)             # [bk, dv]
    s = q @ k.T                                  # [bq, bk]
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 128,
                           bk: int = 128, interpret: bool):
    """q: [BH, Sq, d]; k, v: [BH, Sk, d(v)] -> [BH, Sq, dv].

    Heads folded into the leading dim (GQA repeat handled by the ops
    wrapper).  Sq/Sk padded to block multiples internally.
    """
    BH, Sq, d = q.shape
    _, Sk, dv = v.shape
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # pad keys so padded scores never win the max: keep k values but
        # mask via causal/k_pos check — simplest is padding v with zeros
        # and masking padded keys inside the kernel via k_pos >= Sk.
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk
    k_steps = Skp // bk
    scale = d ** -0.5

    if not causal and pk > 0:
        # padded keys would receive weight in the non-causal case
        raise ValueError("non-causal flash kernel requires Sk % bk == 0")

    out = pl.pallas_call(
        functools.partial(_fa_kernel, bq=bq, bk=bk, k_steps=k_steps,
                          causal=causal, scale=scale),
        grid=(BH, Sqp // bq, k_steps),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sqp, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
