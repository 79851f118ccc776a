"""Chaos soak: sustained open-loop load on the SUPERVISED fleet, with
and without a seeded fault schedule, reporting the self-healing
envelope (ISSUE 10 tentpole d).

Two arms over the same reduced spiking-YOLO pallas fleet:

* ``nofault`` — the supervision overhead control.  Its p99 is the
  number the acceptance bar compares against the closed-loop
  ``serve_bench`` row (supervised soak within ~10% of unsupervised
  closed-loop serving).
* ``chaos``   — the registry's ``chaos`` FaultConfig (all five fault
  kinds, seed 7).  The schedule is a pure function of the seed, so
  every run — CI's chaos-smoke lane included — sees the same faults on
  the same ticks.

Open loop: ``OFFERED_PER_TICK`` fresh requests are submitted every
scheduler round regardless of completions (arrival is not gated on
service, unlike ``serve_bench``'s closed loop), plus a malformed
request on every tick the plan marks MALFORMED.  Latency percentiles
(p50/p99/p99.9) reduce over delivered-request telemetry and carry real
microseconds — regression-guarded by ``bench_diff``.  Availability and
degraded-mode residency are PERCENT-valued rows (<= 100, under the CI
diff's ``--min-us`` floor — recorded, not ratio-judged); the CI lane
asserts on them directly instead.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import smoke_reps
from repro.configs.base import FleetConfig
from repro.configs.registry import (get_fault_config, get_supervisor_config,
                                    reduced_snn)
from repro.core.encoding import voxel_batch
from repro.data.synthetic import make_scene_batch
from repro.core.npu import init_npu
from repro.serve.cognitive_engine import PerceptionRequest
from repro.serve.faults import FaultPlan, make_malformed_request
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import RequestStatus

BATCH = 8
OFFERED_PER_TICK = 8          # offered load = tick capacity (open loop)
N_TICKS = 400                 # full soak horizon (smoke: 80)


def _payloads(cfg, n=32):
    scene = make_scene_batch(jax.random.PRNGKey(9), batch=n,
                             height=cfg.height, width=cfg.width,
                             time_steps=cfg.time_steps, n_events=1024)
    vox = voxel_batch(scene.events, time_steps=cfg.time_steps,
                      height=cfg.height, width=cfg.width)
    return [(np.asarray(vox[:, i]), np.asarray(scene.bayer[i]))
            for i in range(n)]


def _soak(params, cfg, fault_name: str, n_ticks: int):
    """One soak arm; returns (fleet, wall_s, sorted latencies in s of
    the delivered requests)."""
    fault_cfg = get_fault_config(fault_name)
    plan = FaultPlan.from_config(fault_cfg, n_ticks + 8, BATCH) \
        if fault_name != "none" else None
    fleet = FleetEngine(
        params, cfg,
        fleet_cfg=FleetConfig(batch=BATCH, max_queue=256, shard=False),
        supervisor_cfg=get_supervisor_config("soak"),
        fault_plan=plan)
    payloads = _payloads(cfg)

    # warm every ladder rung outside the measured window so a
    # breaker-driven swap mid-soak never pays a first-trace
    fleet._prewarm()

    rid = 0
    finished = []
    t0 = time.perf_counter()
    for tick in range(n_ticks):
        for _ in range(OFFERED_PER_TICK):
            vox, bay = payloads[rid % len(payloads)]
            fleet.submit(PerceptionRequest(rid=rid, voxels=vox, bayer=bay))
            rid += 1
        if plan is not None and plan.malformed_at(tick):
            fleet.submit(make_malformed_request(rid))
            rid += 1
        finished.extend(fleet.step())
    finished.extend(fleet.drain())
    wall = time.perf_counter() - t0
    lat = sorted(r.telemetry.latency_s for r in finished
                 if r.status is RequestStatus.DONE)
    return fleet, wall, lat


def _pct(lat, p):
    return lat[min(len(lat) - 1, int(p * len(lat)))] if lat \
        else float("nan")


def run(emit):
    n_ticks = smoke_reps(N_TICKS, 80)
    cfg = reduced_snn("spiking_yolo", backend="pallas")
    params = init_npu(jax.random.PRNGKey(1), cfg)
    for arm in ("none", "chaos"):
        fleet, wall, lat = _soak(params, cfg, arm, n_ticks)
        s = fleet.stats()
        sup = s["supervisor"]
        label = "nofault" if arm == "none" else "chaos"
        ndev = s["n_devices"]
        tag = (f"avail{s['availability']:.4f}_nan{s['nan_delivered']}"
               f"_batch{BATCH}_ndev{ndev}")
        emit(f"soak_latency_p50_{label}", _pct(lat, 0.50) * 1e6, tag)
        emit(f"soak_latency_p99_{label}", _pct(lat, 0.99) * 1e6, tag)
        emit(f"soak_latency_p999_{label}", _pct(lat, 0.999) * 1e6, tag)
        transitions = sup["transitions"]
        demotes = sum(e["event"] == "demote" for e in transitions)
        promotes = sum(e["event"] == "promote" for e in transitions)
        # percent-valued rows (<= 100): recorded in the baseline but
        # below the diff's --min-us floor, so they are asserted by the
        # chaos-smoke lane, not ratio-judged
        emit(f"soak_availability_{label}", s["availability"] * 100.0,
             f"delivered{s['delivered']}_failed{s['failed']}"
             f"_expired{s['expired']}_retries{s['retries']}"
             f"_nan{s['nan_delivered']}")
        residency = (100.0 * sup["degraded_ticks"]
                     / max(sup["supervised_ticks"], 1))
        emit(f"soak_degraded_residency_{label}", residency,
             f"demotes{demotes}_promotes{promotes}"
             f"_quarantined{sup['quarantined']}"
             f"_final{sup['breaker_state']}")
