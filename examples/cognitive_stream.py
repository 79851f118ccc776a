"""Streaming cognitive perception with the slot-based CognitiveEngine
(paper §VI as a servable workload): requests carrying one DVS voxel
window + one Bayer frame arrive raggedly; the engine batches whatever is
active into ONE jit-compiled NPU->control->ISP executable per tick.

Also demos the stage registry: the same engine, pointed at the "hdr"
pipeline (tonemap + colour-matrix stages spliced in before gamma), needs
only a resized control head — no pipeline code changes.

Also demos the raw-event ingestion path (paper §IV-A): requests can
carry a bounded DVS event buffer instead of finished voxels —
``submit_events`` budgets it into the slot FIFO and the SAME tick
executable voxelizes it (scenario generators sweep the event-rate
regimes: ego-motion, night flicker, noise storms, crossings).

``--fused`` serves the ISP half through the fusion planner
(``backend="pallas_fused"``): the stage ordering collapses into a few
tile-resident megakernel passes — the software analogue of the paper's
line-buffered single-pass datapath.  Either way a per-tick ISP timing
comparison (per-stage jnp vs fused) is printed so the speedup is
visible.

``--concurrency N`` switches the demo to the FleetEngine serving
front-end: N closed-loop client streams share the sharded, double-
buffered, continuously-batched tick with bounded admission; add
``--deadline-ms X`` to shed requests that can't make their deadline
(the ADAS stale-frame-is-worse-than-dropped policy).  Prints the
p50/p99 latency + req/s envelope and the shed/rejected counters.

  PYTHONPATH=src python examples/cognitive_stream.py [--frames 12]
  PYTHONPATH=src python examples/cognitive_stream.py --fused
  PYTHONPATH=src python examples/cognitive_stream.py \
      --concurrency 16 --deadline-ms 200
"""
import argparse
import time

import jax

from repro.configs import EncodingConfig, FleetConfig
from repro.configs.registry import get_isp_config, reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import configure_for_isp, init_npu
from repro.data.synthetic import SCENARIOS, make_scenario, make_scene_batch
from repro.isp.pipeline import plan_summary
from repro.isp.stages import default_stage_params, run_stages
from repro.serve.cognitive_engine import CognitiveEngine, PerceptionRequest


def make_requests(cfg, n, seed=0):
    scene = make_scene_batch(jax.random.PRNGKey(seed), batch=n,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    return [PerceptionRequest(rid=i, voxels=vox[:, i],
                              bayer=scene.bayer[i]) for i in range(n)]


def drive(engine, reqs, label):
    t0 = time.perf_counter()
    done = engine.run_to_completion(list(reqs))
    dt = time.perf_counter() - t0
    print(f"  {label}: {len(done)} frames in {engine.ticks} ticks "
          f"({len(done) / dt:.1f} fps, "
          f"last tick {engine.last_tick_s * 1e3:.1f} ms, "
          f"{engine._step._cache_size()} executable(s))")
    return done


def time_isp_per_tick(cfg, isp_cfg, batch, reps=5):
    """Per-tick cost of the ISP half alone: the batched pipeline in the
    engine's vmapped shape, jit-warmed, mean wall time."""
    bayer = make_scene_batch(jax.random.PRNGKey(7), batch=batch,
                             height=cfg.height, width=cfg.width).bayer
    sp = default_stage_params(isp_cfg.stages)
    fn = jax.jit(jax.vmap(lambda r: run_stages(
        r, sp, isp_cfg.stages, isp_cfg.backend)))
    jax.block_until_ready(fn(bayer))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(bayer)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def serve_fleet(cfg, isp, params, args):
    """Closed-loop fleet serving demo: ``--concurrency`` client streams
    each keep one request outstanding against the FleetEngine."""
    from repro.serve.fleet import FleetEngine
    from repro.serve.scheduler import RequestStatus

    n = args.concurrency
    fc = FleetConfig(batch=args.batch, max_queue=2 * n,
                     default_deadline_ms=args.deadline_ms)
    fleet = FleetEngine(params, cfg, isp, fleet_cfg=fc)
    payloads = make_requests(cfg, n)
    print(f"fleet serving: {n} closed-loop streams, batch {args.batch}, "
          f"{fleet.core.n_devices} device(s), "
          f"deadline {args.deadline_ms or 'none'} ms")

    # warm the executable outside the measured window
    fleet.submit(PerceptionRequest(rid=-1, voxels=payloads[0].voxels,
                                   bayer=payloads[0].bayer))
    fleet.drain()
    fleet.n_delivered = 0
    fleet.n_deadline_missed = 0    # warm-up absorbs the jit compile

    rounds = max(1, args.frames // n)
    outstanding, rid = {}, 0
    for s, p in enumerate(payloads):
        sreq = fleet.submit(PerceptionRequest(rid=rid, voxels=p.voxels,
                                              bayer=p.bayer))
        outstanding[rid] = (s, rounds - 1)
        rid += 1
    lat = []
    t0 = time.perf_counter()
    while outstanding or fleet._inflight is not None:
        for sreq in fleet.step():
            s, left = outstanding.pop(sreq.rid)
            if sreq.status is RequestStatus.DONE:
                lat.append(sreq.telemetry.latency_s)
            if sreq.status is RequestStatus.DONE and left > 0:
                p = payloads[s]
                nxt = fleet.submit(PerceptionRequest(
                    rid=rid, voxels=p.voxels, bayer=p.bayer))
                if nxt.status is RequestStatus.QUEUED:
                    outstanding[rid] = (s, left - 1)
                rid += 1
    wall = time.perf_counter() - t0
    st = fleet.stats()
    lat.sort()
    pct = lambda p: lat[min(len(lat) - 1, int(p * len(lat)))]
    print(f"  delivered {st['delivered']} "
          f"({st['delivered'] / wall:.1f} req/s sustained)")
    print(f"  latency p50 {pct(0.50) * 1e3:.1f} ms / "
          f"p99 {pct(0.99) * 1e3:.1f} ms "
          f"(enqueue->deliver, queueing included)")
    print(f"  shed {st['expired']} expired, {st['rejected']} rejected, "
          f"{st['deadline_missed']} delivered-late, "
          f"{st['ticks']} ticks, "
          f"{fleet._step._cache_size()} executable(s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--fused", action="store_true",
                    help="serve the ISP through the fusion planner "
                         "(backend='pallas_fused')")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="serve N closed-loop streams through the "
                         "FleetEngine instead of the plain engine demo")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for --concurrency mode "
                         "(expired queued requests are shed)")
    args = ap.parse_args()

    cfg = reduced_snn("spiking_yolo")
    isp = get_isp_config("fused" if args.fused else "default")

    if args.concurrency > 0:
        params = init_npu(jax.random.PRNGKey(0), cfg)
        serve_fleet(cfg, isp, params, args)
        return

    print(f"{isp.name} pipeline (control_dim derived = "
          f"{isp.control_dim}):")
    if args.fused:
        print(f"  fusion plan: {plan_summary(isp)}")
    params = init_npu(jax.random.PRNGKey(0), cfg)
    eng = CognitiveEngine(params, cfg, isp, batch=args.batch)
    done = drive(eng, make_requests(cfg, args.frames), "stream")
    if done:
        r = done[0].result
        print(f"  frame 0: NPU chose gamma="
              f"{float(r.stage_params['gamma']['gamma']):.2f} "
              f"nlm={float(r.stage_params['nlm']['strength']):.2f}")

    print("\nraw-event ingestion (submit_events, encode inside the tick "
          "executable):")
    enc = EncodingConfig(event_capacity=1024)
    eng_ev = CognitiveEngine(params, cfg, batch=args.batch, enc_cfg=enc)
    bayer = make_scene_batch(jax.random.PRNGKey(2), batch=len(SCENARIOS),
                             height=cfg.height, width=cfg.width).bayer
    reqs = []
    for i, name in enumerate(SCENARIOS):
        ev = make_scenario(name, jax.random.PRNGKey(i), height=cfg.height,
                           width=cfg.width, n_events=2048)  # overfull: budgeted
        reqs.append(PerceptionRequest(rid=i, events=ev, bayer=bayer[i]))
        print(f"  scenario {name!r}: {int(ev.num_events())} events "
              f"-> FIFO of {enc.event_capacity}")
    drive(eng_ev, reqs, "event stream")

    hdr = get_isp_config("hdr_fused" if args.fused else "hdr")
    print(f"\n{hdr.name} pipeline {hdr.stages} "
          f"(control_dim derived = {hdr.control_dim}):")
    if args.fused:
        print(f"  fusion plan: {plan_summary(hdr)}")
    cfg_hdr = configure_for_isp(cfg, hdr)
    params_hdr = init_npu(jax.random.PRNGKey(1), cfg_hdr)
    eng_hdr = CognitiveEngine(params_hdr, cfg_hdr, hdr, batch=args.batch)
    done = drive(eng_hdr, make_requests(cfg, args.frames, seed=1), "stream")
    if done:
        r = done[0].result
        print(f"  frame 0: tonemap="
              f"{float(r.stage_params['tonemap']['strength']):.2f} "
              f"saturation={float(r.stage_params['ccm']['saturation']):.2f}")

    print("\nper-tick ISP cost (batched pipeline alone, "
          f"{args.batch}x{cfg.height}x{cfg.width}):")
    t_ps = time_isp_per_tick(cfg, get_isp_config("default"), args.batch)
    t_fu = time_isp_per_tick(cfg, get_isp_config("fused"), args.batch)
    print(f"  per-stage jnp : {t_ps * 1e3:6.1f} ms/tick")
    print(f"  pallas_fused  : {t_fu * 1e3:6.1f} ms/tick "
          f"({t_ps / t_fu:.2f}x, plan {plan_summary()})")


if __name__ == "__main__":
    main()
