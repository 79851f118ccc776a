"""Fleet-serving envelope (ROADMAP "millions of users"): p50/p99
request latency and sustained req/s for the continuous-batching
``FleetEngine`` under 10s of concurrent synthetic streams, per NPU
backend.

The sweep is CLOSED-LOOP: ``N_STREAMS`` independent clients each keep
exactly one request outstanding (submit -> wait for delivery ->
resubmit), cycling the DVS scenario generators, so the engine sees
sustained concurrency rather than one pre-loaded burst.  Requests
enter the bounded admission queue, get packed into free tick slots,
and ride the double-buffered staging pipeline; per-request latencies
come from the scheduler's telemetry timestamps (enqueue -> deliver),
NOT from outer wall clocks, so queueing is included in the percentile.

On a multi-device host (the CI serving-smoke lane forces 8 host
devices) the tick batch is sharded over the ``("data",)`` serving
mesh; the ``ndev`` tag in every row records the mesh extent.  On this
CPU container the pallas rows run in interpret mode — correctness
anchors, not speed claims (on a TPU the kernels run compiled).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import smoke_reps
from repro.configs.base import FleetConfig
from repro.configs.registry import reduced_snn
from repro.core.encoding import voxel_batch
from repro.core.npu import init_npu
from repro.data.synthetic import SCENARIOS, make_scenario_batch, \
    make_scene_batch
from repro.serve.cognitive_engine import PerceptionRequest
from repro.serve.fleet import FleetEngine
from repro.serve.scheduler import RequestStatus

N_STREAMS = 32       # acceptance floor: >= 32 concurrent streams
BATCH = 8


def _make_stream_payloads(cfg, n_streams):
    """One (voxels, bayer) payload per stream, drawn from the scenario
    generators round-robin so the fleet sees every event-rate regime."""
    names = list(SCENARIOS)
    bayer = make_scene_batch(jax.random.PRNGKey(5), batch=n_streams,
                             height=cfg.height, width=cfg.width).bayer
    payloads = []
    per = -(-n_streams // len(names))
    for gi, name in enumerate(names):
        evs = make_scenario_batch(name, jax.random.PRNGKey(gi), per,
                                  height=cfg.height, width=cfg.width,
                                  n_events=1024)
        vox = voxel_batch(evs, time_steps=cfg.time_steps,
                          height=cfg.height, width=cfg.width)
        for b in range(per):
            payloads.append((np.asarray(vox[:, b]),
                             np.asarray(bayer[len(payloads) % n_streams])))
    return payloads[:n_streams]


def _drive_closed_loop(fleet, payloads, rounds):
    """Each stream keeps one request in flight for ``rounds`` rounds;
    returns (delivered, wall_s)."""
    outstanding = {}                      # rid -> rounds remaining
    rid = 0
    t0 = time.perf_counter()
    for s, (vox, bay) in enumerate(payloads):
        sreq = fleet.submit(PerceptionRequest(rid=rid, voxels=vox,
                                              bayer=bay))
        assert sreq.status is RequestStatus.QUEUED, sreq.status
        outstanding[rid] = (s, rounds - 1)
        rid += 1
    delivered = []
    for _ in range(100000):
        if not outstanding and fleet._inflight is None:
            break
        for sreq in fleet.step():
            if sreq.status is not RequestStatus.DONE:
                continue
            delivered.append(sreq)
            s, left = outstanding.pop(sreq.rid)
            if left > 0:                  # closed loop: resubmit
                vox, bay = payloads[s]
                nxt = fleet.submit(PerceptionRequest(rid=rid, voxels=vox,
                                                     bayer=bay))
                outstanding[rid] = (s, left - 1)
                rid += 1
    return delivered, time.perf_counter() - t0


def run(emit):
    rounds = smoke_reps(3, 1)
    for backend in ("jnp", "pallas"):
        cfg = reduced_snn("spiking_yolo", backend=backend)
        params = init_npu(jax.random.PRNGKey(1), cfg)
        fleet = FleetEngine(
            params, cfg,
            fleet_cfg=FleetConfig(batch=BATCH,
                                  max_queue=N_STREAMS + BATCH))
        ndev = fleet.core.n_devices
        payloads = _make_stream_payloads(cfg, N_STREAMS)

        # warm the tick executable outside the measured window
        warm = fleet.submit(PerceptionRequest(rid=-1, voxels=payloads[0][0],
                                              bayer=payloads[0][1]))
        fleet.drain()
        assert warm.status is RequestStatus.DONE
        fleet.n_delivered = 0

        delivered, wall = _drive_closed_loop(fleet, payloads, rounds)
        n = len(delivered)
        assert n == N_STREAMS * rounds, (n, N_STREAMS, rounds)
        lat_us = np.sort([s.telemetry.latency_s for s in delivered]) * 1e6
        p50 = float(lat_us[min(n - 1, int(0.50 * n))])
        p99 = float(lat_us[min(n - 1, int(0.99 * n))])
        tag = f"streams{N_STREAMS}_batch{BATCH}_ndev{ndev}"
        emit(f"serve_latency_p50_{backend}", p50, tag)
        emit(f"serve_latency_p99_{backend}", p99, tag)
        # sustained throughput: us_per_call is the per-request cost the
        # schema wants; the derived field carries the req/s headline
        emit(f"serve_throughput_{backend}", wall / n * 1e6,
             f"{n / wall:.1f}req_s_{tag}")
