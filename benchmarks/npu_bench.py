"""NPU throughput (paper §IV): event encoding rate across DVS scenarios
and voxelizer backends, LIF scan, end-to-end spiking inference latency,
the engine's raw-event ingestion path, spike-sparsity / tile-skip rates
that drive the event-driven compute saving, and the fleet-serving
latency/throughput envelope (benchmarks/serve_bench.py rides along so
the serving rows land in the same BENCH_<n>.json trajectory).

The backend sweep times every hot-path layer kind (LIF scan, spiking
dense matmul), every backbone, and the engine submit->result tick under
both ``SNNConfig.backend`` settings.  On this CPU container the pallas
rows run in interpret mode, so they are correctness/roofline anchors,
not speed claims — on a TPU the kernels run compiled.

The sparse-conv sweep (``_sparse_conv_sweep``) is the exception: it
compares the SAME interpreted kernel dense vs activity-gated across
DVS scenarios, so its speedup RATIOS measure what the occupancy mask
buys at each sparsity level (the ISSUE 5 acceptance axis), with the
achieved im2col tile-skip fraction in every row.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import is_smoke, smoke_reps, time_us
from repro.configs.base import EncodingConfig
from repro.configs.registry import SNN_ARCHS, reduced_snn
from repro.core.encoding import events_to_voxel_batch, voxel_batch
from repro.core.lif import lif_scan
from repro.core.npu import init_npu, npu_forward
from repro.data.synthetic import (SCENARIOS, make_scenario_batch,
                                  make_scene_batch)
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest


def _sparse_conv_sweep(emit):
    """Dense vs activity-gated spike-conv, parameterized by DVS
    scenario: moving_bar (clean ego-motion -> high sparsity), flicker
    (night point source -> extreme sparsity), noise_burst (rain storm
    -> ~79% zero voxels at this shape but spatially INCOHERENT, so ~0
    skippable tiles).  Each gated row reports the achieved im2col tile-skip
    fraction next to two speedups, so sparsity is a charted
    performance axis:

      x...    wall-clock ratio vs the SAME kernel ungated (interpret
              mode executes the pl.when, so skipped tiles skip their
              dot — but the interpreter's per-grid-step overhead, a
              cost that does not exist compiled, caps the measurable
              win; interleaved min-of-reps timing keeps it stable)
      mxu...  MXU-pass ratio: dense k-tile dots issued / gated dots
              issued = 1/(1-skip), deterministic from the occupancy
              mask of the real scenario data — the roofline-anchored
              speedup a compiled TPU kernel is bounded by (run on
              a TPU for compiled wall times).

    The jnp rows anchor the pure-XLA reference conv on the same data.
    """
    from repro.core.layers import init_spiking_conv, spike_conv_jnp
    from repro.kernels.ops import spike_conv_op, spike_conv_tile_skip

    # 32x32, T=3, batch 2: a 128-row patch-matrix tile spans 4 image
    # rows, fine enough that scene structure decides tile occupancy,
    # and small enough that the interpreter's per-step overhead stays
    # comparable to the per-tile dot.  The horizontal noise-free bar
    # keeps activity in a coherent row band; the slow-flicker point
    # source leaves most (frame, time-bin) slabs fully silent (>=0.9
    # skip — the CI-asserted regime).
    H, W, T, B, N_EV = 32, 32, 3, 2, 2048
    p = init_spiking_conv(jax.random.PRNGKey(0), 2, 16)
    scen_kw = {"moving_bar": dict(noise_frac=0.0, vertical=False,
                                  speed=0.25, bar_width=0.05),
               "flicker": dict(flicker_hz=0.5, source_radius=0.01),
               "noise_burst": {}}
    reps = smoke_reps(9, 7)    # min-of-reps needs >1 even under smoke
    for name, kw in scen_kw.items():
        evs = make_scenario_batch(name, jax.random.PRNGKey(2), B,
                                  height=H, width=W, n_events=N_EV, **kw)
        vox = events_to_voxel_batch(evs, time_steps=T, height=H, width=W)
        # fold [B, T, H, W, 2] -> [B*T, H, W, 2] (the conv layout)
        xf = vox.reshape(-1, H, W, 2)
        skip = float(spike_conv_tile_skip(xf, p["w"]))
        mxu = 1.0 / max(1.0 - skip, 1e-9)
        t_jnp = time_us(jax.jit(lambda x, w: spike_conv_jnp(x, w)),
                        xf, p["w"])
        emit(f"spike_conv_{name}_jnp", t_jnp, f"skip{skip:.2f}")
        fd = lambda x: spike_conv_op(x, p["w"], gate="none")
        fg = lambda x: spike_conv_op(x, p["w"], gate="mask")
        fd(xf), fg(xf)                     # warm both executables
        td = tg = float("inf")
        for _ in range(reps):              # interleaved min: the two
            t0 = time.perf_counter()       # paths see the same noise
            jax.block_until_ready(fd(xf))
            td = min(td, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fg(xf))
            tg = min(tg, time.perf_counter() - t0)
        emit(f"spike_conv_{name}_dense_pallas", td * 1e6,
             "skip0.00_mxu1.0")
        emit(f"spike_conv_{name}_gated_pallas", tg * 1e6,
             f"skip{skip:.2f}_x{td / tg:.2f}_mxu{mxu:.1f}")


def _tuned_backbone_sweep(emit):
    """Autotuned-vs-default pallas backbone forward on the >=90%-
    sparsity moving_bar scenario (ISSUE 8 acceptance axis).

    Three rows per backbone on the SAME voxels: the jnp reference, the
    pallas path with the untuned defaults (``tune.off()`` — PR 5's
    per-op composition at the stock 128 blocks), and the pallas path
    after a real autotuning sweep (fused conv->LIF + measured block /
    gate winners).  Each timed executable is a FRESH ``jax.jit``
    wrapper because launch configs resolve at trace time: reusing one
    wrapper across table swaps would silently time stale configs.  The
    three executables are timed INTERLEAVED, min-of-reps (the
    ``_sparse_conv_sweep`` discipline): single-shot timings of the
    same forward vary >10x with ambient process state, which would
    make the derived xdef/xjnp ratios meaningless.

    What the ratios mean on this CPU container: xdef (tuned vs the
    untuned default-block pallas path) is the number the autotuner
    earns and the one CI gates.  xjnp is reported against the pure-XLA
    reference for honesty: interpret mode executes one grid step at a
    time, so even the tuned, activity-gated kernel pays an interpreter
    tax XLA's single fused conv does not, and xjnp plateaus well below
    1.0 regardless of sparsity.  The ``tune_conv_lif_*`` rows carry
    the per-shape winner-vs-default margins; the compiled path on a
    TPU is where the <=jnp comparison is the roofline-fair one.

    ISSUE 9 adds a FOURTH executable per backbone: the whole-backbone
    megakernel (``npu_fwd_moving_bar_<name>_fused_backbone``), timed
    from the same tuned table with the ``backbone_seg`` entries forced
    ``fused=True`` while the ``_pallas_tuned`` row forces them
    ``fused=False`` — so xlayer isolates exactly what cross-layer VMEM
    residency buys over the best per-layer composition.  In interpret
    mode the win is launch-count collapse: L per-layer kernels x their
    grid steps become ONE kernel with B grid steps per segment (CI
    gates xlayer >= 1.5).

    Also emits one ``tune_<op>_<shape>`` row per tuned shape (winner
    us vs default-config us, both measured by the sweep on the live
    activations), and persists the table to TUNE_TABLE.json — the CI
    artifact that makes a tuning run reproducible/inspectable.
    """
    import copy

    from repro.configs.registry import get_tune_config
    from repro.kernels import tune

    H, W, T, B, N_EV = 32, 32, 3, 2, 2048
    evs = make_scenario_batch("moving_bar", jax.random.PRNGKey(2), B,
                              height=H, width=W, n_events=N_EV,
                              noise_frac=0.0, vertical=False,
                              speed=0.25, bar_width=0.05)
    vox = jnp.swapaxes(events_to_voxel_batch(
        evs, time_steps=T, height=H, width=W), 0, 1)  # [T, B, H, W, 2]
    sp = float(jnp.mean(vox == 0))
    # bounded sweep under either --smoke or --tune-smoke (the latter
    # sets REPRO_TUNE_SMOKE, which default_tune_config honors)
    tc = (get_tune_config("smoke") if is_smoke()
          else tune.default_tune_config())
    table = tune.TuningTable()
    reps = smoke_reps(5, 3)    # min-of-reps needs >1 even under smoke
    for name in ("spiking_vgg", "spiking_yolo"):
        cfg_j = reduced_snn(name)
        cfg_p = reduced_snn(name, backend="pallas")
        params = init_npu(jax.random.PRNGKey(1), cfg_j)
        f_j = jax.jit(lambda p, v, c=cfg_j: npu_forward(p, v, c))
        with tune.off():
            f_d = jax.jit(lambda p, v, c=cfg_p: npu_forward(p, v, c))
            jax.block_until_ready(f_d(params, vox))   # trace w/ defaults
        with tune.tuning(table, tc):
            npu_forward(params, vox, cfg_p)   # eager: sweeps each shape
        # per-layer-tuned vs whole-backbone-fused variants of the SAME
        # swept winners: only the backbone_seg routing flag differs
        seg_keys = [k for k in table.entries
                    if k.startswith("backbone_seg|")]
        t_layer = tune.TuningTable(copy.deepcopy(table.entries))
        t_fused = tune.TuningTable(copy.deepcopy(table.entries))
        for k in seg_keys:
            t_layer.entries[k]["fused"] = False
            t_fused.entries[k].update(fused=True, gate="inline")
        tune.set_table(t_layer)
        try:
            f_t = jax.jit(lambda p, v, c=cfg_p: npu_forward(p, v, c))
            jax.block_until_ready(f_t(params, vox))   # trace w/ winners
        finally:
            tune.set_table(None)
        tune.set_table(t_fused)
        try:
            f_f = jax.jit(lambda p, v, c=cfg_p: npu_forward(p, v, c))
            jax.block_until_ready(f_f(params, vox))   # trace megakernels
        finally:
            tune.set_table(None)
        jax.block_until_ready(f_j(params, vox))
        t_j = t_d = t_t = t_f = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f_j(params, vox))
            t1 = time.perf_counter()
            jax.block_until_ready(f_d(params, vox))
            t2 = time.perf_counter()
            jax.block_until_ready(f_t(params, vox))
            t3 = time.perf_counter()
            jax.block_until_ready(f_f(params, vox))
            t4 = time.perf_counter()
            t_j = min(t_j, (t1 - t0) * 1e6)
            t_d = min(t_d, (t2 - t1) * 1e6)
            t_t = min(t_t, (t3 - t2) * 1e6)
            t_f = min(t_f, (t4 - t3) * 1e6)
        emit(f"npu_fwd_moving_bar_{name}_jnp", t_j, f"sp{sp:.2f}")
        emit(f"npu_fwd_moving_bar_{name}_pallas_default", t_d,
             f"sp{sp:.2f}")
        emit(f"npu_fwd_moving_bar_{name}_pallas_tuned", t_t,
             f"xdef{t_d / t_t:.2f}_xjnp{t_j / t_t:.2f}")
        emit(f"npu_fwd_moving_bar_{name}_fused_backbone", t_f,
             f"seg{len(seg_keys)}_xlayer{t_t / t_f:.2f}"
             f"_xjnp{t_j / t_f:.2f}")
    for key in sorted(table.entries):
        e = table.entries[key]
        emit("tune_" + key.replace("|", "_").replace(",", "_"),
             e["us"],
             f"default{e['default_us']:.0f}us"
             f"_x{e['default_us'] / max(e['us'], 1e-9):.2f}")
    table.save(os.environ.get("REPRO_TUNE_TABLE_OUT", "TUNE_TABLE.json"))


def _backend_sweep(emit, rng):
    """jnp vs pallas per layer kind, per backbone, and engine tick."""
    from repro.kernels.ops import lif_scan_op, spike_matmul_op

    # layer kind: LIF scan (the recurrence epilogue)
    T, N = 5, 16384
    cur = jnp.asarray(rng.normal(0.5, 1, (T, N)).astype(np.float32))
    t_j = time_us(jax.jit(lambda c: lif_scan(c)), cur)
    emit(f"lif_T{T}_N{N}_jnp", t_j, f"{cur.size / t_j:.0f}Mns_s")
    t_p = time_us(lif_scan_op, cur, reps=2)
    emit(f"lif_T{T}_N{N}_pallas", t_p, f"{cur.size / t_p:.0f}Mns_s")

    # layer kind: spiking dense matmul on 0/1 activations (tile skip)
    M, K, Nw = 256, 256, 256
    x = jnp.asarray((rng.random((M, K)) < 0.1).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 1, (K, Nw)).astype(np.float32))
    t_j = time_us(jax.jit(lambda x, w: x @ w), x, w)
    emit(f"dense_{M}x{K}x{Nw}_jnp", t_j, "d0.1")
    t_p = time_us(spike_matmul_op, x, w, reps=2)
    emit(f"dense_{M}x{K}x{Nw}_pallas", t_p, "d0.1_tile_skip")

    # per backbone: full npu_forward under both backends; the tape
    # rides in the SAME jit'd forward (collect_sparsity), so every row
    # reports the achieved network sparsity next to its time
    for name in SNN_ARCHS:
        for backend in ("jnp", "pallas"):
            cfg = reduced_snn(name, backend=backend)
            params = init_npu(jax.random.PRNGKey(1), cfg)
            vox = jnp.asarray(
                (rng.random((cfg.time_steps, 2, cfg.height, cfg.width,
                             cfg.in_channels)) < 0.1).astype(np.float32))
            fwd = jax.jit(lambda p, v, c=cfg: npu_forward(
                p, v, c, collect_sparsity=True))
            t = time_us(fwd, params, vox, reps=2)
            sp = float(fwd(params, vox).layer_rates["network_sparsity"])
            emit(f"npu_fwd_{name}_{backend}", t, f"batch2_sp{sp:.3f}")


def _engine_tick_sweep(emit, rng):
    """Engine submit->result latency (voxel path) per NPU backend: the
    zero-copy tick — staged numpy slots, one device_put, one fetch.

    The ISSUE 9 ``engine_tick_pallas_fused`` row serves the SAME
    requests through an engine constructed under a tuned table whose
    ``backbone_seg`` entries are forced fused: the tick executable's
    backbone runs as whole-segment megakernels (the engine pins the
    table snapshot at construction, so one sweep prices the whole
    serving run).  Its derived field carries the speedup over the
    per-layer ``engine_tick_pallas`` row timed in the same process."""
    from repro.configs.registry import get_tune_config
    from repro.kernels import tune

    def _time_engine(eng, vox, bayer):
        def _drive():
            for i in range(4):
                eng.submit(PerceptionRequest(rid=i, voxels=vox[:, i],
                                             bayer=bayer[i]))
            return eng.tick()

        _drive()                               # warm the tick executable
        reps = smoke_reps(5)
        t0 = time.perf_counter()
        for _ in range(reps):
            done = _drive()
        jax.block_until_ready(done[-1].result.rgb)
        return (time.perf_counter() - t0) / reps * 1e6

    times = {}
    for backend in ("jnp", "pallas"):
        cfg = reduced_snn("spiking_yolo", backend=backend)
        params = init_npu(jax.random.PRNGKey(1), cfg)
        scene = make_scene_batch(jax.random.PRNGKey(3), batch=4,
                                 height=cfg.height, width=cfg.width,
                                 time_steps=cfg.time_steps)
        vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                          height=cfg.height, width=cfg.width)
        t_us = _time_engine(CognitiveEngine(params, cfg, batch=4),
                            vox, scene.bayer)
        times[backend] = t_us
        emit(f"engine_tick_{backend}", t_us,
             f"{4e6 / t_us:.1f}req_s")         # 4 requests per tick
        if backend != "pallas":
            continue
        # fused whole-backbone tick: sweep the batch-4 shapes once,
        # force the segment entries fused, pin via engine construction
        tc = (get_tune_config("smoke") if is_smoke()
              else tune.default_tune_config())
        table = tune.TuningTable()
        with tune.tuning(table, tc):
            npu_forward(params, vox, cfg)
        for k in table.entries:
            if k.startswith("backbone_seg|"):
                table.entries[k].update(fused=True, gate="inline")
        tune.set_table(table)
        try:
            eng_f = CognitiveEngine(params, cfg, batch=4)
        finally:
            tune.set_table(None)
        t_f = _time_engine(eng_f, vox, scene.bayer)
        emit("engine_tick_pallas_fused", t_f,
             f"x{times['pallas'] / t_f:.2f}_{4e6 / t_f:.1f}req_s")


def run(emit):
    rng = np.random.default_rng(0)
    cfg = reduced_snn("spiking_yolo")
    scene = make_scene_batch(jax.random.PRNGKey(0), batch=8,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps)

    enc = jax.jit(lambda ev: voxel_batch(ev, time_steps=cfg.time_steps,
                                         height=cfg.height,
                                         width=cfg.width))
    t_enc = time_us(enc, scene.events)
    n_events = int(np.prod(scene.events.x.shape))
    emit("npu_event_encoding", t_enc, f"{n_events / t_enc:.1f}Mev_s")

    cur = jnp.asarray(rng.normal(0.5, 1, (8, 65536)).astype(np.float32))
    t_lif = time_us(jax.jit(lambda c: lif_scan(c)), cur)
    emit("npu_lif_scan_jnp", t_lif, f"{cur.size / t_lif:.0f}Mneuron_steps_s")

    params = init_npu(jax.random.PRNGKey(1), cfg)
    vox = enc(scene.events)
    fwd = jax.jit(lambda p, v: npu_forward(p, v, cfg))
    t_fwd = time_us(fwd, params, vox)
    out = fwd(params, vox)
    emit("npu_inference", t_fwd, f"batch8_{cfg.height}x{cfg.width}")
    emit("npu_sparsity", t_fwd, f"{float(out.sparsity):.4f}")
    emit("npu_tile_skip", t_fwd, f"{float(out.tile_skip):.4f}")

    # event-driven saving estimate: dense MACs vs spike-driven MACs
    voxel_rate = float(jnp.mean(vox > 0))
    emit("npu_input_event_rate", 0.0, f"{voxel_rate:.4f}")

    # backend sweep: jnp vs pallas per layer kind / backbone / engine
    _backend_sweep(emit, rng)
    _engine_tick_sweep(emit, rng)

    # fleet-serving envelope: p50/p99 latency + sustained req/s under
    # 32 concurrent closed-loop streams through the continuous-batching
    # FleetEngine (sharded over the serving mesh when devices allow)
    from benchmarks import serve_bench
    serve_bench.run(emit)

    # dense vs activity-gated spike-conv across sparsity regimes
    _sparse_conv_sweep(emit)

    # autotuned vs default pallas backbone forward (ISSUE 8 axis)
    _tuned_backbone_sweep(emit)

    # ingestion sweep: events/sec per DVS scenario x voxelizer backend
    # (jnp scatter vs the Pallas event_voxel kernel; interpret mode on
    # CPU, so the pallas row is a correctness/roofline anchor, not a
    # speed claim — on a TPU the kernel runs compiled)
    B, N = 8, 1024
    enc_jnp = jax.jit(lambda ev: events_to_voxel_batch(
        ev, time_steps=cfg.time_steps, height=cfg.height, width=cfg.width))
    for name in SCENARIOS:
        evs = make_scenario_batch(name, jax.random.PRNGKey(2), B,
                                  height=cfg.height, width=cfg.width,
                                  n_events=N)
        live = int(np.sum(np.asarray(evs.valid)))
        t_us = time_us(enc_jnp, evs)
        emit(f"event_voxel_{name}_jnp", t_us, f"{live / t_us:.2f}Mev_s")
    from repro.kernels.ops import event_voxel_op
    enc_plls = jax.jit(lambda ev: event_voxel_op(
        ev, time_steps=cfg.time_steps, height=cfg.height, width=cfg.width))
    evs = make_scenario_batch("moving_bar", jax.random.PRNGKey(2), B,
                              height=cfg.height, width=cfg.width, n_events=N)
    live = int(np.sum(np.asarray(evs.valid)))
    t_us = time_us(enc_plls, evs, reps=2)
    emit("event_voxel_moving_bar_pallas", t_us, f"{live / t_us:.2f}Mev_s")

    # engine raw-event path: submit_events -> encode -> NPU -> ISP
    eng = CognitiveEngine(params, cfg, batch=4,
                          enc_cfg=EncodingConfig(event_capacity=N))
    bayer = make_scene_batch(jax.random.PRNGKey(3), batch=4,
                             height=cfg.height, width=cfg.width).bayer

    def _drive():
        for i in range(4):
            eng.submit_events(PerceptionRequest(
                rid=i, events=jax.tree_util.tree_map(lambda a: a[i], evs),
                bayer=bayer[i]))
        return eng.tick()

    _drive()                                   # warm the tick executable
    reps = smoke_reps(5)
    t0 = time.perf_counter()
    for _ in range(reps):
        done = _drive()
    jax.block_until_ready(done[-1].result.rgb)
    t_us = (time.perf_counter() - t0) / reps * 1e6
    emit("engine_submit_events_tick", t_us,
         f"{4 * (live / B) / t_us:.2f}Mev_s")   # aggregate over 4 slots
