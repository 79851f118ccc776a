"""Pallas kernel timings (interpret mode on CPU — correctness-oriented;
real perf numbers come from the roofline analysis, not CPU wall time)
plus the jnp-reference timings the kernels are validated against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_us
from repro.kernels import blocks, ref
from repro.kernels.spike_matmul import spike_matmul_pallas

_time = functools.partial(time_us, reps=3)


def _tile_skip_rows(emit):
    """Timed spike_matmul on DVS-scenario spike matrices, with the
    kernel's ACHIEVED tile-skip fraction at its real (bm, bk) block
    shape.  Replaces the dead rows that emitted us_per_call=0.0 over
    i.i.d. uniform masks — uniform sparsity never empties a 128x128
    tile, so both the time and the skip read 0.000; scenario data is
    spatially coherent, which is where tile skip actually pays (same
    physics as the spike-conv sweep in npu_bench).  moving_bar keeps
    activity in a band (moderate skip), flicker is a point source
    (extreme skip — CI asserts >= 0.5), noise_burst is incoherent
    (~0 skip: the honest lower bound rides in the trajectory too)."""
    from benchmarks.common import smoke_reps
    from repro.core.encoding import events_to_voxel_batch
    from repro.data.synthetic import make_scenario_batch

    # the spike-dense layout the kernel serves in npu_forward:
    # [T*B, H*W*2] rows of flattened frames, so a (128, 128) k-tile is
    # a 64-pixel spatial chunk across the whole window — tile occupancy
    # tracks scene structure, not i.i.d. luck
    H, W, T, B = 64, 64, 5, 2
    bm = bk = 128
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 1, (H * W * 2, 128)).astype(np.float32))
    scen_kw = {"moving_bar": dict(noise_frac=0.0, vertical=False,
                                  bar_width=0.05),
               "flicker": dict(flicker_hz=0.5, source_radius=0.01),
               "noise_burst": {}}
    for name, kw in scen_kw.items():
        evs = make_scenario_batch(name, jax.random.PRNGKey(2), B,
                                  height=H, width=W, n_events=4096, **kw)
        vox = events_to_voxel_batch(evs, time_steps=T, height=H, width=W)
        x = np.asarray(vox).reshape(B * T, H * W * 2)  # [M, K] spikes
        M, K = x.shape
        xp = np.pad(x, ((0, (-M) % bm), (0, (-K) % bk)))
        tiles = xp.reshape(xp.shape[0] // bm, bm, xp.shape[1] // bk, bk)
        skip = float(np.mean(tiles.sum(axis=(1, 3)) == 0))
        t = time_us(lambda a: spike_matmul_pallas(
            a, w, bm=bm, bk=bk, interpret=blocks.interpret_mode()),
                    jnp.asarray(x), reps=smoke_reps(3, 1))
        emit(f"spike_matmul_tile_skip_{name}", t, f"skip{skip:.3f}")


def run(emit):
    rng = np.random.default_rng(0)

    # tile-skip effectiveness AND cost on scenario spike matrices (the
    # rows the CI bench-smoke lane asserts are nonzero)
    _tile_skip_rows(emit)

    t = _time(jax.jit(lambda a, b: ref.spike_matmul_ref(a, b)),
              jnp.asarray((rng.random((256, 256)) < 0.1).astype(np.float32)),
              jnp.asarray(rng.normal(0, 1, (256, 256)).astype(np.float32)))
    emit("spike_matmul_jnp_ref_256", t, "dense_path")

    q = jnp.asarray(rng.normal(0, 1, (8, 256, 64)).astype(np.float32))
    t = _time(jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v)),
              q, q, q)
    emit("flash_attention_jnp_ref", t, "BH8_S256_d64")

    cur = jnp.asarray(rng.normal(0.5, 1, (8, 16384)).astype(np.float32))
    t = _time(jax.jit(lambda c: ref.lif_scan_ref(c)), cur)
    emit("lif_scan_jnp_ref", t, "T8_N16384")
