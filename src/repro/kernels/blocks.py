"""Shared kernel block constants — the single source of truth for the
tile shapes the Pallas kernels launch with AND the K-block the jnp
reference formulations accumulate in.

Why this module exists (ISSUE 8 satellite): ``SPIKE_CONV_BLOCK`` in
``repro.core.layers`` and ``BM/BK/BN`` in ``repro.kernels.spike_conv``
used to be two independent ``128`` literals.  The bit-parity contract
of the spike-conv path (tests/test_spike_conv.py) is that both backends
accumulate K in the SAME block size — with the autotuner now sweeping
launch block shapes, a tuned ``bk`` that silently diverged from the
reference K-block would break bit-exactness without any test naming
the culprit.  Centralising the constants makes that impossible:

* ``CANONICAL_K_BLOCK`` is the *accumulation* granularity.  Every
  matmul-style kernel accumulates K in canonical sub-blocks regardless
  of its launch ``bk`` (see ``canonical_k_slices``), and the jnp
  reference (``repro.core.layers.spike_conv_jnp`` /
  ``blocked_matmul``) sums the identical sub-blocks in the identical
  order.  Sweeping ``bk`` therefore only changes the *grid/gating*
  granularity, never the float accumulation order.
* ``validate_bk`` rejects launch ``bk`` values that cannot be tiled by
  canonical sub-blocks — the guard the autotuner's candidate space and
  the dispatch layer both run, so an illegal block shape fails loudly
  at config time instead of as a last-bit mismatch in a parity test.

The whole-backbone megakernel (ISSUE 9, ``kernels/backbone_fuse.py``)
leans on the same contract one level up: every layer of a fused
segment zero-pads its VMEM-resident patch matrix to canonical
sub-blocks and accumulates them in canonical order, so a *multi-layer*
fused forward stays bit-exact against the per-layer composition AND
the jnp reference — zero padding is exact, and the accumulation
order per layer is byte-for-byte the one this module pins.  Its
swept row-chunk sizes (``bm`` ∈ {128, 256, 512}) start from
``DEFAULT_BM`` below.

It also owns the two platform decisions every kernel takes at trace
time: whether Pallas runs compiled or interpreted, and the matmul
precision (``interpret_mode`` / ``matmul_precision``).

This module is import-light on purpose (no pallas; jax only inside the
platform functions): the pure-jnp reference path imports it without
pulling the kernel stack in.
"""
from __future__ import annotations

from typing import List, Tuple

# The accumulation K-block: the bit-parity contract between the Pallas
# kernels' K loops and the jnp reference formulation.  Changing this
# changes last-bit rounding of every spike conv/matmul — bump
# ``repro.kernels.tune.KERNELS_VERSION`` if you ever do.
CANONICAL_K_BLOCK = 128


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when JAX's
    default backend is not a TPU.  Called at trace time, never at
    import (importing must not touch device state)."""
    import jax
    return jax.default_backend() != "tpu"


def matmul_precision():
    """The one matmul precision of the serving path, for the Pallas
    kernels' dots and (through ``EngineCore``) the jnp backend alike, so
    the fallback ladder's rungs compute the same numbers.  On a TPU:
    full float32 (``"highest"``) — the default there is one bfloat16
    pass, which rounds every weight to 8 mantissa bits (spikes are
    exact in bf16, weights are not).  Elsewhere: ``None``, XLA's
    default, already full float32 on the CPU (asking for ``"highest"``
    there changes the CPU dot path and its summation order)."""
    return None if interpret_mode() else "highest"


# Default launch tile shapes (MXU-native 128x128) — what dispatch uses
# when no tuning-table entry covers a shape.
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = CANONICAL_K_BLOCK

# Default neuron block of the flat LIF scan kernel.
DEFAULT_LIF_BLOCK_N = 1024


def validate_bk(bk: int) -> int:
    """A launch ``bk`` is legal iff it is a positive multiple of the
    canonical accumulation block; returns it for chaining."""
    if bk <= 0 or bk % CANONICAL_K_BLOCK != 0:
        raise ValueError(
            f"bk={bk} must be a positive multiple of the canonical "
            f"K-block {CANONICAL_K_BLOCK} (the bit-parity accumulation "
            f"granularity shared with the jnp reference)")
    return bk


def canonical_k_slices(bk: int) -> List[Tuple[int, int]]:
    """The (start, stop) canonical sub-blocks a launch K-step of width
    ``bk`` must accumulate sequentially (kernel-side mirror of the jnp
    reference's K loop).

    Canonical-multiple ``bk`` (everything the autotuner sweeps — see
    ``validate_bk``) yields full 128-wide slices and the bit-parity
    guarantee.  Other widths remain legal at the raw kernel entrypoints
    (legacy callers launch e.g. ``bk=64`` on small shapes) and get a
    short tail slice — numerically fine, just not last-bit-identical
    to the reference accumulation order."""
    if bk <= 0:
        raise ValueError(f"bk={bk} must be positive")
    return [(k0, min(k0 + CANONICAL_K_BLOCK, bk))
            for k0 in range(0, bk, CANONICAL_K_BLOCK)]
