"""Pallas TPU kernel: block-sparse spike matmul (event-driven compute on
the MXU).

The FPGA skips MACs for silent neurons; a systolic MXU cannot gate
individual lanes, so the TPU-native granularity of "event-driven" is the
VMEM tile: spike activation blocks that are entirely zero skip their MXU
pass via ``@pl.when``.  With the paper's reported sparsity (48% neurons
silent, bursty spatially), tile-skip rates of 10-60% are observed on the
synthetic DVS data (see benchmarks/npu_bench.py).

x: [M, K] spikes (0/1), w: [K, N] weights -> y = x @ w.
Grid (M/bm, N/bn, K/bk); fp32 accumulation in VMEM scratch.

Tuning note: ``bm/bn/bk`` are swept by ``repro.kernels.tune``.  The
launch ``bk`` only sets the grid/gating granularity — inside a K-step
the accumulator is updated in sequential ``CANONICAL_K_BLOCK`` sub-block
dots (``canonical_k_slices``), so every swept block shape reproduces the
jnp reference's float accumulation order bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import canonical_k_slices, matmul_precision


def _kernel(x_ref, w_ref, y_ref, acc_ref, *, k_steps: int, bk: int,
            precision):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]

    @pl.when(jnp.any(x != 0))          # event-driven tile skip
    def _mac():
        for c0, c1 in canonical_k_slices(bk):
            acc_ref[...] += jnp.dot(x[:, c0:c1].astype(jnp.float32),
                                    w_ref[c0:c1, :].astype(jnp.float32),
                                    precision=precision,
                                    preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def spike_matmul_pallas(x, w, *, bm: int = 128, bk: int = 128,
                        bn: int = 128, interpret: bool):
    """x: [M, K] (spikes), w: [K, N] -> [M, N].  Canonical-multiple
    ``bk`` (the tuner's swept space) is bit-exact vs the blocked jnp
    reference; other widths remain legal with a short tail slice."""
    M, K = x.shape
    _, N = w.shape
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    k_steps = Kp // bk

    y = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, bk=bk,
                          precision=matmul_precision()),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), w.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return y[:M, :N]
