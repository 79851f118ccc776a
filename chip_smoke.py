#!/usr/bin/env python3
"""Chip smoke test: the cognitive serving tick end to end on a TPU.

Serves seeded requests through ``FleetEngine`` — event encoding, the
spiking NPU, the control head and the fused ISP, with every Pallas
kernel compiled by Mosaic — at the registered width of the paper's
Spiking-YOLO backbone (``SNN_ARCHS["spiking_yolo"]``: 64x64, T=5,
base_channels 32, 4 stages; random weights from ``--seed``), and checks
each result against the same requests served by the pure-jnp engine on
the same chip at the highest matmul precision.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded fleet on a 4-chip
                                      # mesh against one chip of it

Everything runs in this one process.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {...}}`` when every phase passed
on a TPU, otherwise ``{"ok": false, "error": ...}`` with a non-zero exit
code.  It never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

N_REQUESTS = 16
BATCH = 8
# Kernels the compiled tick must hold as tpu_custom_call: the event
# voxelizer; per backbone layer (8) the gated spike conv and the fused
# norm+affine+LIF; the head's 3x3 conv (2) and 1x1 readout conv (1);
# the control head's LIF scan and spike matmul; the 4 fused ISP
# segments.  1 + 16 + 2 + 1 + 2 + 4.
EXPECTED_KERNELS = 26

# Tolerances against the highest-precision jnp engine.  Voxels are
# integer counts: exact.  Mosaic and XLA accumulate the conv and norm
# reductions in different orders, so a membrane potential within float
# rounding of the threshold can fire on one path and not the other;
# such a spike changes the readout by one weight / T.  The limits bound
# the share of such flips, and the outputs they perturb.
MAX_SPIKE_FLIP_SHARE = 1e-3
CONTROL_ATOL = 2e-3         # sigmoid outputs in [0, 1]
RAW_PRED_ATOL = 5e-2        # detection logits, |values| ~ 1
RGB_ATOL = 2e-3             # rendered frames in [0, 1]


class SmokeFailure(AssertionError):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _finite(name, *arrays):
    import numpy as np
    for a in arrays:
        _check(np.isfinite(np.asarray(a)).all(),
               f"{name}: non-finite values in the output")


def _entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _setup(seed: int):
    """The served configuration, seeded weights and seeded requests."""
    import jax
    import numpy as np

    from repro.configs.base import EncodingConfig, FleetConfig
    from repro.configs.registry import ISP_CONFIGS, SNN_ARCHS
    from repro.core.npu import configure_for_isp, init_npu
    from repro.data.synthetic import make_scenario
    from repro.kernels import tune
    from repro.serve.cognitive_engine import PerceptionRequest

    # launch configs come from the committed tuning table only, whatever
    # REPRO_TUNE_TABLE or a local TUNE_TABLE.json say
    tune.set_table(tune.TuningTable.load(tune.DEFAULT_TABLE_PATH))
    isp = ISP_CONFIGS["fused"]
    cfg = configure_for_isp(
        dataclasses.replace(SNN_ARCHS["spiking_yolo"], backend="pallas"),
        isp)
    params = init_npu(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), N_REQUESTS)
    reqs = []
    for i in range(N_REQUESTS):
        ev = make_scenario("moving_bar", keys[i], height=cfg.height,
                           width=cfg.width, n_events=2048)
        ev = jax.tree_util.tree_map(np.asarray, ev)
        bayer = rng.random((cfg.height, cfg.width)).astype(np.float32)
        reqs.append(PerceptionRequest(rid=i, events=ev, bayer=bayer))
    return dict(cfg=cfg, isp=isp, params=params, reqs=reqs,
                enc=EncodingConfig(backend="pallas"),
                fleet=FleetConfig(batch=BATCH))


def _fresh(reqs):
    from repro.serve.cognitive_engine import PerceptionRequest
    return [PerceptionRequest(rid=r.rid, events=r.events, bayer=r.bayer)
            for r in reqs]


def _serve(engine, reqs):
    """Submit every request, drain, and return (results, latencies)."""
    from repro.serve.scheduler import RequestStatus
    sreqs = [engine.submit(r) for r in reqs]
    engine.drain()
    for s in sreqs:
        _check(s.status is RequestStatus.DONE,
               f"request {s.request.rid} ended {s.status} ({s.error})")
    return ([s.request.result for s in sreqs],
            [s.request.result.telemetry.latency_s for s in sreqs])


def _compile_tick(engine):
    """AOT-compile the engine's tick on its staging shapes; returns
    (seconds, HLO text of the compiled executable)."""
    import jax

    from repro.serve.transport import StagingBank
    core = engine.core
    bank = StagingBank(core.cfg, engine.batch, core.frame_hw,
                       core.enc_cfg.event_capacity)
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (core.params, bank.slab, bank.voxels))
    t0 = time.perf_counter()
    compiled = core._step.lower(*spec).compile()
    return time.perf_counter() - t0, compiled.as_text()


def _worst(got, want, key):
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(getattr(g, key))
                                   - np.asarray(getattr(w, key)))))
               for g, w in zip(got, want))


def _compare_npu(name, got, want):
    """NPU outputs of two engines, request by request."""
    for g in got:
        _finite(name, g.rgb, g.control, g.raw_pred)
    ctl, raw = _worst(got, want, "control"), _worst(got, want, "raw_pred")
    print(f"{name}: worst |diff| control {ctl:.3e} (limit {CONTROL_ATOL})"
          f", raw_pred {raw:.3e} (limit {RAW_PRED_ATOL})")
    _check(ctl <= CONTROL_ATOL, f"{name}: control off")
    _check(raw <= RAW_PRED_ATOL, f"{name}: raw_pred off")


def _compare_rgb(name, got, want):
    d = _worst(got, want, "rgb")
    print(f"{name}: worst |diff| rgb {d:.3e} (limit {RGB_ATOL})")
    _check(d <= RGB_ATOL, f"{name}: rgb off")


def _isp_reference(reqs, got):
    """The jnp ISP (same stages) on each request's Bayer frame and the
    control vector the kernel engine's NPU produced, so the fused ISP
    is checked on its own inputs and NPU drift does not reach it."""
    import jax
    import numpy as np

    from repro.configs.registry import ISP_CONFIGS
    from repro.isp.pipeline import control_vector_pipeline
    from repro.serve.cognitive_engine import PerceptionResult
    isp = ISP_CONFIGS["default"]               # same stages, jnp backend
    bayer = np.stack([r.bayer for r in reqs])
    ctrl = np.stack([np.asarray(g.control) for g in got])
    with jax.default_matmul_precision("highest"):
        rgb = np.asarray(jax.jit(jax.vmap(
            lambda r, c: control_vector_pipeline(r, c, isp)))(bayer, ctrl))
    return [PerceptionResult(rgb=x, control=None, raw_pred=None,
                             stage_params=None) for x in rgb]


def _voxel_parity(s):
    """Phase: the Pallas voxelizer against the jnp reference, exact."""
    import jax
    import numpy as np

    from repro.core.encoding import (events_to_voxel_batch, fit_stream,
                                     stack_streams)
    from repro.kernels.ops import event_voxel_op
    cfg, enc = s["cfg"], s["enc"]
    ev = stack_streams([fit_stream(r.events, enc.event_capacity)
                        for r in s["reqs"]])
    kw = dict(time_steps=cfg.time_steps, height=cfg.height,
              width=cfg.width, window=enc.window, mode=enc.mode,
              oob=enc.oob)
    got = np.asarray(event_voxel_op(ev, **kw))
    want = np.asarray(jax.jit(
        lambda e: events_to_voxel_batch(e, **kw))(ev))
    n_diff = int(np.sum(got != want))
    print(f"voxels: {n_diff} of {got.size} differ over {N_REQUESTS} "
          f"windows ({int(np.sum(np.asarray(ev.valid)))} live events, "
          f"{int(want.sum())} occupied voxels)")
    _check(n_diff == 0, "voxels differ from the jnp reference")
    return want


def _spike_flips(s, voxels):
    """Phase: share of backbone output spikes that differ between the
    kernel path and the highest-precision jnp path on the same voxels."""
    import jax
    import numpy as np

    from repro.core.backbones import BACKBONES
    cfg = s["cfg"]
    _, apply_bb = BACKBONES[cfg.backbone]
    ref_cfg = dataclasses.replace(cfg, backend="jnp")
    vox = np.moveaxis(voxels, 0, 1)                   # [T, B, H, W, 2]
    params = s["params"]["backbone"]
    kernels = jax.jit(lambda p, x: apply_bb(p, x, cfg))
    reference = jax.jit(lambda p, x: apply_bb(p, x, ref_cfg))
    flips = total = 0
    for b0 in range(0, N_REQUESTS, BATCH):
        v = vox[:, b0:b0 + BATCH]
        got = kernels(params, v)
        with jax.default_matmul_precision("highest"):
            want = reference(params, v)
        got, want = np.asarray(got), np.asarray(want)
        _finite("backbone spikes", got)
        flips += int(np.sum(got != want))
        total += got.size
    share = flips / total
    print(f"backbone spikes: {flips} of {total} differ (share {share:.2e},"
          f" limit {MAX_SPIKE_FLIP_SHARE:.0e})")
    _check(share <= MAX_SPIKE_FLIP_SHARE, "too many spikes flipped")


def one_chip(seed: int):
    import jax
    import numpy as np

    from repro.configs.registry import ISP_CONFIGS
    from repro.serve.fleet import FleetEngine

    s = _setup(seed)
    cfg = s["cfg"]
    print(f"config: {cfg.name} {cfg.height}x{cfg.width} T={cfg.time_steps}"
          f" base_channels={cfg.base_channels} stages={cfg.num_stages} "
          f"batch={BATCH} isp={s['isp'].name} requests={N_REQUESTS}")
    voxels = _voxel_parity(s)

    engine = FleetEngine(s["params"], cfg, s["isp"], fleet_cfg=s["fleet"],
                         enc_cfg=s["enc"], mesh=None)
    secs, hlo = _compile_tick(engine)
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"tick compile: {secs:.1f} s, {n_kernels} tpu_custom_call "
          f"(expected at least {EXPECTED_KERNELS})")
    _check(n_kernels >= EXPECTED_KERNELS,
           "kernels missing from the compiled tick")
    t0 = time.perf_counter()
    got, lat = _serve(engine, _fresh(s["reqs"]))
    print(f"kernel engine: {N_REQUESTS} requests in "
          f"{time.perf_counter() - t0:.2f} s (first tick includes the "
          f"jit call's compile-cache lookup); latencies ms: "
          + " ".join(f"{1e3 * x:.1f}" for x in lat))
    t0 = time.perf_counter()
    got2, lat2 = _serve(engine, _fresh(s["reqs"]))
    print(f"kernel engine, warm: {N_REQUESTS} requests in "
          f"{time.perf_counter() - t0:.3f} s; latencies ms: "
          + " ".join(f"{1e3 * x:.1f}" for x in lat2))
    for a, b in zip(got, got2):
        _check(np.array_equal(np.asarray(a.rgb), np.asarray(b.rgb)),
               "kernel engine not deterministic across runs")

    ref_isp = ISP_CONFIGS["default"]           # same stages, jnp backend
    ref_cfg = dataclasses.replace(cfg, backend="jnp")
    with jax.default_matmul_precision("highest"):
        ref = FleetEngine(s["params"], ref_cfg, ref_isp,
                          fleet_cfg=s["fleet"],
                          enc_cfg=dataclasses.replace(s["enc"],
                                                      backend="jnp"),
                          mesh=None)
        t0 = time.perf_counter()
        want, _ = _serve(ref, _fresh(s["reqs"]))
    print(f"jnp engine (highest precision): {N_REQUESTS} requests in "
          f"{time.perf_counter() - t0:.2f} s, compile included")
    # the jnp engine as the fleet's fallback ladder serves it, with no
    # precision asked for: the engine takes the kernels' precision
    # itself, so it must compute exactly the reference's numbers
    served = FleetEngine(s["params"], ref_cfg, ref_isp, fleet_cfg=s["fleet"],
                         enc_cfg=dataclasses.replace(s["enc"], backend="jnp"),
                         mesh=None)
    rung, _ = _serve(served, _fresh(s["reqs"]))
    n_same = sum(all(np.array_equal(np.asarray(getattr(a, k)),
                                    np.asarray(getattr(b, k)))
                     for k in ("rgb", "control", "raw_pred"))
                 for a, b in zip(rung, want))
    print(f"jnp engine as served vs at highest precision: {n_same} of "
          f"{N_REQUESTS} results bit-equal")
    _check(n_same == N_REQUESTS,
           "the served jnp engine does not run at the kernels' precision")
    _compare_npu("kernel vs jnp engine", got, want)
    _compare_rgb("fused ISP vs jnp ISP on the same control",
                 got, _isp_reference(s["reqs"], got))
    _spike_flips(s, voxels)


def four_chips(seed: int):
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh
    from repro.serve.fleet import FleetEngine

    _check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    s = _setup(seed)
    mesh = make_serving_mesh(BATCH, max_devices=4)
    _check(mesh is not None and mesh.devices.size == 4,
           "no 4-device serving mesh")
    sharded = FleetEngine(s["params"], s["cfg"], s["isp"],
                          fleet_cfg=s["fleet"], enc_cfg=s["enc"], mesh=mesh)
    from repro.serve.transport import StagingBank
    bank = StagingBank(s["cfg"], BATCH, sharded.core.frame_hw,
                       s["enc"].event_capacity)
    # one staged voxel slot, so the voxel slab goes up beside the slab
    bank.stage_voxels(0, bank.voxels[:, 0], bank.bayer[0])
    slab_dev, vox_dev = sharded.core.upload(bank)   # [B, words], [T, B, ...]
    for name, arr, axis in (("slab", slab_dev, 0), ("voxels", vox_dev, 1)):
        for shard in sorted(arr.addressable_shards,
                            key=lambda sh: sh.device.id):
            print(f"bank {name} on device {shard.device.id}: batch slots "
                  f"{shard.index[axis].start}:{shard.index[axis].stop}, "
                  f"shard shape {tuple(shard.data.shape)}")
    t0 = time.perf_counter()
    got, lat = _serve(sharded, _fresh(s["reqs"]))
    print(f"4-chip engine: {N_REQUESTS} requests in "
          f"{time.perf_counter() - t0:.2f} s, compile included; "
          f"latencies ms: " + " ".join(f"{1e3 * x:.1f}" for x in lat))
    single = FleetEngine(s["params"], s["cfg"], s["isp"],
                         fleet_cfg=s["fleet"], enc_cfg=s["enc"], mesh=None)
    t0 = time.perf_counter()
    want, _ = _serve(single, _fresh(s["reqs"]))
    print(f"1-chip engine: {N_REQUESTS} requests in "
          f"{time.perf_counter() - t0:.2f} s, compile included")
    _compare_npu("4-chip vs 1-chip engine", got, want)
    _compare_rgb("4-chip vs 1-chip engine", got, want)
    n_same = sum(np.array_equal(np.asarray(g.rgb), np.asarray(w.rgb))
                 for g, w in zip(got, want))
    print(f"4-chip vs 1-chip: {n_same} of {N_REQUESTS} frames bit-equal")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    result = {"ok": False}
    try:
        import jax
        devices = jax.devices()
        result["device"] = {"platform": devices[0].platform,
                            "kind": devices[0].device_kind,
                            "count": len(devices)}
        print(f"devices: {len(devices)} x {devices[0].device_kind} "
              f"({devices[0].platform})")
        _check(devices[0].platform == "tpu",
               f"no TPU: JAX found {devices[0].platform}")
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        print(f"compile cache: {cache}, {_entries(cache)} entries at start")
        (four_chips if args.chips == 4 else one_chip)(args.seed)
        print(f"compile cache: {_entries(cache)} entries at the end")
        result["ok"] = True
    except Exception as e:                   # any failed phase
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    sys.stdout.flush()
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
