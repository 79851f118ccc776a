"""Streaming engine for the cognitive perception loop: slot-based
batching of ``encode -> npu_forward -> control -> ISP`` (paper §VI as a
servable workload, mirroring ``ServeEngine``'s design).

Since the engine-core/transport split this module is the THIN single-
device composition of the serving stack:

* the jit-cached tick executable lives in
  :class:`repro.serve.engine_core.EngineCore` (which also knows how to
  shard the batch over a device mesh — not used here),
* the host-side numpy staging slots live in
  :class:`repro.serve.transport.StagingBank`,
* the multi-device continuous-batching front-end (admission control,
  deadlines, double-buffered staging) is
  :class:`repro.serve.fleet.FleetEngine`.

The public contract is unchanged.  A fixed pool of ``batch`` slots
shares ONE jit-compiled step executable (static shapes — TPU-friendly).
Clients submit perception requests — either a finished DVS voxel window
(``submit``) or a RAW event buffer (``submit_events``, paper §IV-A) —
plus one Bayer frame; every ``tick`` voxelizes the event slots, runs
the whole active batch through the NPU and the registry-built ISP
pipeline, hands back finished requests, and recycles their slots.
Perception completes in a single tick, so the slot machinery exists to
keep the batch full under ragged arrival.

Zero-copy tick discipline (PR 3): submissions stage into HOST-side
numpy slot buffers (a submit is a memcpy, no device dispatch), the tick
uploads what its slots use with ONE ``jax.device_put`` — the slab of
event windows, frames and flags, plus the voxel slab only when a slot
staged voxels — and DONATES the slab to the step executable; results
come back with one batched ``jax.device_get``.  The event path is part
of the SAME tick executable: bounded per-slot FIFOs ride along as
static-shape inputs and a traced per-slot flag selects
encoded-vs-submitted voxels, so mixing ``submit`` and ``submit_events``
costs no retrace.

Configuration is unchanged: ``ISPConfig`` (stage ordering/backend,
incl. ``"pallas_fused"`` megakernels), ``EncodingConfig`` (ingestion
policy), ``SNNConfig.backend`` (jnp vs Pallas NPU kernels), and
``collect_sparsity=True`` threads the SparsityTape through the tick so
per-layer spike rates ride back on every ``PerceptionResult``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EncodingConfig, ISPConfig, SNNConfig
from repro.core.encoding import EventStream


class PerceptionResult(NamedTuple):
    rgb: np.ndarray             # [H, W, 3] corrected RGB
    control: np.ndarray         # [control_dim] raw NPU control vector
    raw_pred: np.ndarray        # detection head output for this frame
    stage_params: Dict[str, Dict[str, np.ndarray]]
    # per-layer spike rates + "network_sparsity" for the TICK BATCH
    # this request rode in (the rates reduce over the whole batch, so
    # every request finished by one tick shares the dict); populated
    # when the engine was built with collect_sparsity=True, else None
    sparsity: Optional[Dict[str, float]] = None
    # per-request lifecycle timestamps (scheduler.RequestTelemetry:
    # enqueue -> admit -> dispatch -> deliver + deadline_missed);
    # populated by FleetEngine, None through the plain CognitiveEngine
    telemetry: Optional[Any] = None


@dataclasses.dataclass
class PerceptionRequest:
    rid: int
    voxels: Optional[jnp.ndarray] = None   # [T, Hd, Wd, 2] DVS voxel window
    bayer: Optional[jnp.ndarray] = None    # [H, W] RGGB mosaic in [0, 1]
    events: Optional[EventStream] = None   # raw [N]-leaf event buffer
    result: Optional[PerceptionResult] = None


class CognitiveEngine:
    """Slot-based streaming front-end over the cognitive loop."""

    def __init__(self, npu_params, cfg: SNNConfig,
                 isp_cfg: Optional[ISPConfig] = None, batch: int = 4,
                 frame_hw: Optional[tuple] = None,
                 control_order: str = "pipeline",
                 enc_cfg: Optional[EncodingConfig] = None,
                 collect_sparsity: bool = False):
        """``control_order``: how the NPU head's slots are laid out.
        "pipeline" (default) is the registry's derived stage order;
        "legacy" serves heads trained through the ``cognitive_step`` /
        ``control_to_params`` shim (historical hand-picked slot order)
        by permuting the control vector before range mapping.

        ``collect_sparsity``: thread the SparsityTape through the tick
        executable so per-layer spike rates come back with every tick
        (``PerceptionResult.sparsity``) — same jit'd forward, no second
        pass; the only cost is a handful of extra scalar outputs."""
        from repro.serve.engine_core import EngineCore
        from repro.serve.transport import StagingBank

        self.core = EngineCore(
            npu_params, cfg, isp_cfg, batch=batch, frame_hw=frame_hw,
            control_order=control_order, enc_cfg=enc_cfg,
            collect_sparsity=collect_sparsity, mesh=None)
        self.params = npu_params
        self.cfg = cfg
        self.isp_cfg = self.core.isp_cfg
        self.enc_cfg = self.core.enc_cfg
        self.batch = batch
        self.staging = StagingBank(cfg, batch, self.core.frame_hw,
                                   self.enc_cfg.event_capacity)
        self.active: List[Optional[PerceptionRequest]] = [None] * batch
        self.ticks = 0
        self.last_tick_s = 0.0      # wall time of the latest tick()
        self._step = self.core._step   # the ONE tick executable

    # staging-bank views (host numpy; kept as attributes of record so
    # tests and tools can inspect the slot state directly)
    @property
    def voxels(self):
        return self.staging.voxels

    @property
    def bayer(self):
        return self.staging.bayer

    @property
    def events(self):
        return self.staging.events

    @property
    def from_events(self):
        return self.staging.from_events

    # ------------------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def submit(self, req: PerceptionRequest) -> bool:
        """Stage a voxel-carrying request into a free slot (a host-side
        memcpy — no device dispatch until the tick).  False if the
        engine is full.  Requests carrying raw events (and no voxels)
        route through ``submit_events``."""
        from repro.serve.transport import stage_request, validate_request
        kind = validate_request(req, self.cfg.in_channels)
        if kind == "events":
            return self.submit_events(req)
        slot = self._free_slot()
        if slot is None:
            return False
        stage_request(self.staging, slot, req, kind, self.enc_cfg)
        self.active[slot] = req
        return True

    def submit_events(self, req: PerceptionRequest) -> bool:
        """Stage a RAW event buffer into a free slot; the voxelization
        happens inside the next tick's executable (paper §IV-A).  The
        buffer is coerced to the engine's bounded per-slot FIFO:
        under-full windows are validity-padded, overfull ones budgeted
        to the ``enc_cfg.event_capacity`` earliest events.  False if
        the engine is full."""
        from repro.serve.transport import stage_request, validate_request
        kind = validate_request(req, self.cfg.in_channels,
                                events_only=True)
        slot = self._free_slot()
        if slot is None:
            return False
        stage_request(self.staging, slot, req, kind, self.enc_cfg)
        self.active[slot] = req
        return True

    # ------------------------------------------------------------------
    def tick(self) -> List[PerceptionRequest]:
        """Run one batched perception step; returns finished requests
        (every active request completes — perception has no decode tail)
        and recycles their slots."""
        if not any(r is not None for r in self.active):
            return []
        t0 = time.perf_counter()
        # ONE host->device upload of what the slots use, ONE step
        # launch, ONE batched device->host fetch (EngineCore.tick);
        # per-request results below are numpy views into the fetch
        out, rgb, sp = self.core.tick(self.staging, self.ticks)
        self.staging.voxels_staged = False  # every slot is recycled
        self.last_tick_s = time.perf_counter() - t0
        self.ticks += 1
        # batch-level sparsity telemetry (one dict per tick, shared by
        # every request that rode in it)
        spars = None
        if out.layer_rates is not None:
            spars = {k: float(v) for k, v in out.layer_rates.items()}
        finished: List[PerceptionRequest] = []
        for i, r in enumerate(self.active):
            if r is None:
                continue
            r.result = PerceptionResult(
                rgb=rgb[i], control=out.control[i],
                raw_pred=out.raw_pred[i],
                stage_params=jax.tree_util.tree_map(lambda x: x[i], sp),
                sparsity=spars)
            finished.append(r)
            self.active[i] = None
        return finished

    def run_to_completion(self, requests: List[PerceptionRequest],
                          max_ticks: int = 10000) \
            -> List[PerceptionRequest]:
        done: List[PerceptionRequest] = []
        pending = collections.deque(requests)
        ticks = 0
        while (pending or any(r is not None for r in self.active)) \
                and ticks < max_ticks:
            while pending and self._free_slot() is not None:
                self.submit(pending.popleft())
            done.extend(self.tick())
            ticks += 1
        return done
