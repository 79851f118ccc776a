"""FleetEngine: sharded, continuously-batched, SELF-HEALING serving
for the cognitive path (ROADMAP "millions of users" direction).

Composes the split serving stack:

* :class:`repro.serve.engine_core.EngineCore` — the jit-cached
  ``encode -> NPU -> control -> ISP`` tick, batch-sharded over a 1-D
  ``("data",)`` mesh (``repro.launch.mesh.make_serving_mesh``).
* :class:`repro.serve.transport.DoubleBuffer` — two host staging
  banks; tick N+1 packs and uploads while tick N computes.
* :class:`repro.serve.scheduler.AdmissionQueue` — bounded admission,
  per-request deadlines, shed-don't-stall expiry, retry backoff gates.
* :class:`repro.serve.supervisor.FleetSupervisor` — NaN/stall health
  checks, the circuit breaker, and the fallback-ladder degradation
  policy (optional; ``supervisor_cfg=None`` serves unsupervised).
* :class:`repro.serve.faults.FaultInjector` — deterministic fault
  injection at the core boundary (optional; ``fault_plan=None`` runs
  clean).  The injector wraps every ladder rung with ONE shared tick
  counter, so a seeded chaos schedule replays identically.

Continuous batching: every ``step()`` packs as many queued requests as
there are free slots into the next tick (ragged arrival keeps the
static batch full), dispatches it asynchronously, and harvests the
PREVIOUS tick's results.  With double buffering the pipeline is two
deep; with ``double_buffer=False`` each step dispatches and harvests
the same tick (the low-latency edge profile).

Fault semantics (the paper's ADAS/UAV envelope: a wrong answer is
worse than a late one, a late one worse than a shed one):

* A malformed submit gets status ``FAILED`` + ``error`` — the serving
  loop never dies on client garbage (validation happens at the edge,
  and staging failures inside ``step()`` are caught per-request).
* A non-finite result is QUARANTINED by the supervisor's NaN guard:
  the request FAILS (and may retry) — garbage is NEVER delivered.
* A tick raising :class:`TransientTickError` fails every request it
  carried; transiently failed requests retry up to
  ``SupervisorConfig.max_retries`` times behind an exponential-backoff
  gate with deterministic seeded jitter.
* A request in flight past ``hedge_after_ms`` gets ONE hedged
  duplicate enqueued; first delivery wins, the loser is discarded.
* Consecutive tick failures open the circuit breaker and demote the
  engine down the pre-built fallback ladder (fused-pallas ->
  per-layer pallas -> jnp, bit-identical outputs); half-open probes
  climb back up after recovery.

Every delivered ``PerceptionResult`` carries a
``scheduler.RequestTelemetry`` (timestamps, the serving tick, and
retry/hedge/quarantine/rung accounting); callers reduce their own
requests' telemetry to latency percentiles, and ``stats()`` keeps the
fleet's counters and availability.

Tracing: each ``step()`` opens ``jax.profiler.TraceAnnotation`` spans,
one per stage and never one per slot or request: ``serve.step`` (arg
``tick``, the number of the tick this step would dispatch) around
``serve.shed`` (shedding and hedging), ``serve.pack`` (args ``slots``,
the requests packed, and ``queued``, the queue left), the engine core's
``serve.upload``, ``serve.dispatch`` and ``serve.fetch``, and
``serve.deliver`` (the health checks and hand-off of the harvested
tick's ``slots`` requests).  Every span carries the ``tick`` it works
for, the number each delivered request records as
``RequestTelemetry.tick``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import (EncodingConfig, FleetConfig, ISPConfig,
                                SNNConfig, SupervisorConfig)
from repro.kernels import tune
from repro.launch.mesh import make_serving_mesh
from repro.serve.cognitive_engine import PerceptionRequest, PerceptionResult
from repro.serve.engine_core import EngineCore
from repro.serve.faults import (FaultInjector, FaultPlan, TransientTickError,
                                _SharedTicker)
from repro.serve.scheduler import (AdmissionQueue, RequestStatus,
                                   RequestTelemetry, ServeRequest)
from repro.serve.supervisor import FleetSupervisor
from repro.serve.transport import (DoubleBuffer, StagingBank,
                                   stage_request, validate_request)


class _Inflight:
    """One dispatched tick: its packed (slot, request) pairs, the
    not-yet-fetched output futures, and WHICH core/rung ran it (the
    supervisor may swap the active rung while this tick is in
    flight)."""

    def __init__(self, packed, outputs, core, rung: int, rung_name: str,
                 tick_no: int, t_dispatch: float):
        self.packed: List[Tuple[int, ServeRequest]] = packed
        self.outputs = outputs
        self.core = core
        self.rung = rung
        self.rung_name = rung_name
        self.tick_no = tick_no
        self.t_dispatch = t_dispatch


class FleetEngine:
    """Multi-device continuous-batching front-end over the cognitive
    tick.  ``mesh="auto"`` shards over the largest visible-device count
    dividing the batch (single device => local, bit-compatible with
    ``CognitiveEngine``); pass an explicit mesh or ``None`` to pin.

    ``supervisor_cfg`` enables self-healing: NaN quarantine, the
    circuit breaker over a pre-built fallback ladder, retries, and
    hedging.  ``fault_plan`` wraps every ladder rung in a
    :class:`FaultInjector` (testing/chaos only); ``fault_advance``
    overrides how an injected STALL manifests (default: sleep — tests
    pass a fake-clock advance)."""

    def __init__(self, npu_params, cfg: SNNConfig,
                 isp_cfg: Optional[ISPConfig] = None, *,
                 fleet_cfg: Optional[FleetConfig] = None,
                 mesh="auto",
                 enc_cfg: Optional[EncodingConfig] = None,
                 control_order: str = "pipeline",
                 collect_sparsity: bool = False,
                 frame_hw: Optional[tuple] = None,
                 supervisor_cfg: Optional[SupervisorConfig] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 fault_advance: Optional[Callable[[float], None]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.fleet_cfg = fleet_cfg if fleet_cfg is not None else FleetConfig()
        fc = self.fleet_cfg
        if mesh == "auto":
            mesh = make_serving_mesh(fc.batch) if fc.shard else None
        self.mesh = mesh

        def _core(core_cfg, tune_table):
            return EngineCore(
                npu_params, core_cfg, isp_cfg, batch=fc.batch,
                frame_hw=frame_hw, control_order=control_order,
                enc_cfg=enc_cfg, collect_sparsity=collect_sparsity,
                mesh=mesh, tune_table=tune_table)

        # ---- fallback ladder --------------------------------------------
        # rung 0 is the configured primary (active tune table — fused
        # backbone winners when the table carries them); the pallas
        # path degrades through the per-layer default-block composition
        # (an EMPTY pinned table resolves every op to its untuned
        # default, fused=False) down to the pure-XLA jnp reference.
        # Every rung computes the SAME numbers (bit-parity pinned in
        # tests/test_supervisor.py) — degradation trades speed, never
        # correctness.
        if supervisor_cfg is not None and cfg.backend == "pallas":
            ladder = [("pallas_fused", cfg, "active"),
                      ("pallas", cfg, tune.TuningTable()),
                      ("jnp", dataclasses.replace(cfg, backend="jnp"),
                       "active")]
        else:
            ladder = [(cfg.backend, cfg, "active")]
        self.ladder_names = [name for name, _, _ in ladder]
        self.cores = [_core(c, t) for _, c, t in ladder]
        if fault_plan is not None:
            ticker = _SharedTicker()
            self.cores = [FaultInjector(c, fault_plan, ticker,
                                        advance=fault_advance)
                          for c in self.cores]
        self.core = self.cores[0]

        self.supervisor: Optional[FleetSupervisor] = None
        if supervisor_cfg is not None:
            self.supervisor = FleetSupervisor(supervisor_cfg,
                                              self.ladder_names, clock)

        self.cfg = cfg
        self.batch = fc.batch
        self.clock = clock
        self._step = self.core._step        # executable-cache introspection
        self.buffers = DoubleBuffer(
            lambda: StagingBank(cfg, fc.batch, self.core.frame_hw,
                                self.core.enc_cfg.event_capacity),
            enabled=fc.double_buffer)
        self.queue = AdmissionQueue(fc.max_queue)
        self._inflight: Optional[_Inflight] = None
        self.ticks = 0
        self.last_tick_s = 0.0
        self.n_delivered = 0
        self.n_deadline_missed = 0
        self.n_failed = 0                   # terminal FAILED requests
        self.n_malformed = 0                # FAILED at submit validation
        self.n_retries = 0                  # re-enqueues after failures
        self.n_hedges = 0                   # hedge duplicates launched
        self.n_hedge_wins = 0               # deliveries won by the hedge
        self.n_nan_delivered = 0            # non-finite results DELIVERED
                                            # (must stay 0 supervised)
        if supervisor_cfg is not None and supervisor_cfg.prewarm:
            self._prewarm()

    # ------------------------------------------------------------------
    # client edge
    # ------------------------------------------------------------------
    def submit(self, req: PerceptionRequest, *,
               deadline_ms: Optional[float] = None) -> ServeRequest:
        """Admit a request (voxel- or event-carrying) into the bounded
        queue.  Returns the wrapping ``ServeRequest`` — check
        ``.status``: ``QUEUED`` on admission, ``REJECTED`` when the
        queue is full (admission control; nothing was copied),
        ``FAILED`` (+ ``.error``) when the payload is malformed — a
        garbage submit must never crash the serving loop.
        ``deadline_ms`` is measured from now; omitted requests inherit
        ``FleetConfig.default_deadline_ms``."""
        try:
            kind = validate_request(
                req, self.cfg.in_channels,
                time_steps=self.cfg.time_steps,
                voxel_hw=(self.cfg.height, self.cfg.width),
                frame_hw=self.core.frame_hw)
        except (ValueError, TypeError) as e:
            sreq = ServeRequest(request=req, status=RequestStatus.FAILED,
                                error=str(e))
            self.n_failed += 1
            self.n_malformed += 1
            return sreq
        now = self.clock()
        if deadline_ms is None:
            deadline_ms = self.fleet_cfg.default_deadline_ms
        sreq = ServeRequest(
            request=req, kind=kind,
            deadline=None if deadline_ms is None
            else now + deadline_ms / 1e3)
        self.queue.offer(sreq, now)
        return sreq

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------
    def step(self) -> List[ServeRequest]:
        """One scheduler round: shed expired queued work, hedge
        overdue in-flight work, pack free slots from the queue into the
        front staging bank, dispatch it on the supervisor-selected
        ladder rung, then harvest the previous in-flight tick (health-
        checking every delivered slot).  Returns every request that
        REACHED A TERMINAL STATUS this round — delivered (``DONE``),
        shed (``EXPIRED``), and failed (``FAILED``, retries exhausted)
        alike, so no outcome is ever a silent stall."""
        t0 = time.perf_counter()
        now = self.clock()
        terminal: List[ServeRequest] = []
        tick = self.ticks
        with TraceAnnotation("serve.step", tick=tick):
            with TraceAnnotation("serve.shed", tick=tick):
                for sreq in self.queue.shed_expired(now):
                    if sreq.is_hedge:       # client never sees the copy
                        self._settle_dead_hedge(sreq, terminal)
                        continue
                    terminal.append(sreq)
                self._maybe_hedge(now)
            with TraceAnnotation("serve.pack", tick=tick) as span:
                packed = self._pack(now, terminal)
                span.set_metadata(slots=len(packed), queued=len(self.queue))
            # dispatch the new tick BEFORE blocking on the old one: the
            # upload + launch are queued asynchronously, so the H2D copy
            # of tick N+1 overlaps tick N's compute
            new_inflight = (self._dispatch(packed, terminal) if packed
                            else None)
            # harvest: block on the PREVIOUS tick's results (pipeline
            # depth 2 with double buffering; without it, harvest this
            # very tick)
            if self.fleet_cfg.double_buffer:
                harvest, self._inflight = self._inflight, new_inflight
            else:
                harvest, self._inflight = new_inflight, None
            if harvest is not None:
                self._harvest(harvest, terminal)
        self.last_tick_s = time.perf_counter() - t0
        return terminal

    def _pack(self, now: float,
              terminal: List[ServeRequest]) -> List[Tuple[int, ServeRequest]]:
        """Continuous batching: fill every slot of the front staging
        bank the queue can; returns the packed ``(slot, request)``
        pairs."""
        bank = self.buffers.front
        bank.voxels_staged = False          # packed anew from here
        packed: List[Tuple[int, ServeRequest]] = []
        while len(packed) < self.batch and len(self.queue):
            sreq = self.queue.pop_ready(now)
            if sreq is None:
                break                       # rest is backing off
            if sreq.expired(now):           # raced past its deadline
                sreq.status = RequestStatus.EXPIRED
                self.queue.n_expired += 1
                if sreq.is_hedge:
                    self._settle_dead_hedge(sreq, terminal)
                else:
                    terminal.append(sreq)
                continue
            if sreq.is_hedge and sreq.primary.status in (
                    RequestStatus.DONE, RequestStatus.FAILED,
                    RequestStatus.EXPIRED):
                continue                    # race already settled
            slot = len(packed)
            try:
                stage_request(bank, slot, sreq.request, sreq.kind,
                              self.core.enc_cfg)
            except (ValueError, TypeError) as e:
                # malformed payload that slipped past edge validation:
                # fail the request, never the serving loop
                self.n_malformed += 1
                self._fail(sreq, f"staging: {e}", retryable=False,
                           now=now, terminal=terminal)
                continue
            sreq.telemetry.t_admit = now
            sreq.attempts += 1
            packed.append((slot, sreq))
        for slot in range(len(packed), self.batch):
            bank.from_events[slot] = False  # recycled slots stay inert
        return packed

    def _dispatch(self, packed: List[Tuple[int, ServeRequest]],
                  terminal: List[ServeRequest]) -> Optional[_Inflight]:
        """Upload the front bank and launch its tick on the
        supervisor-selected rung; None when the launch failed."""
        tick = self.ticks
        rung = (self.supervisor.select_rung(tick)
                if self.supervisor is not None else 0)
        core = self.cores[rung]
        try:
            # ONE device_put, then the asynchronous launch
            dev = core.upload(self.buffers.front, tick)
            outputs = core.dispatch(dev, tick)
        except TransientTickError as e:
            t_fail = self.clock()
            if self.supervisor is not None:
                self.supervisor.record_tick(tick, rung, False, 0.0,
                                            f"dispatch: {e}")
            for _, sreq in packed:
                self._fail(sreq, str(e), retryable=True, now=t_fail,
                           terminal=terminal)
            return None
        t_disp = self.clock()
        for _, sreq in packed:
            sreq.status = RequestStatus.IN_FLIGHT
            sreq.telemetry.t_dispatch = t_disp
            sreq.telemetry.tick = tick
        self.buffers.flip()
        self.ticks += 1
        return _Inflight(packed, outputs, core, rung,
                         self.ladder_names[rung], tick, t_disp)

    # ------------------------------------------------------------------
    # failure handling + resilience
    # ------------------------------------------------------------------
    def _fail(self, sreq: ServeRequest, error: str, *, retryable: bool,
              now: float, terminal: List[ServeRequest]) -> None:
        """A request's dispatch went wrong.  Transient failures retry
        behind an exponential-backoff gate (deterministic seeded
        jitter) while budget remains; otherwise the request reaches
        terminal FAILED.  Hedge copies are never retried and never
        surfaced — the primary owns the outcome."""
        if sreq.is_hedge:
            sreq.status = RequestStatus.FAILED
            primary = sreq.primary
            if primary.parked and primary.status is not RequestStatus.DONE:
                # the primary was only waiting on this hedge: settle it
                self._finalize_fail(primary, primary.error or error,
                                    terminal)
            return
        sup = self.supervisor
        if (retryable and sup is not None and sup.cfg.max_retries > 0
                and sreq.attempts <= sup.cfg.max_retries
                and not sreq.expired(now)):
            c = sup.cfg
            jitter_ms = float(np.random.default_rng(
                (c.retry_seed, sreq.rid & 0x7FFFFFFF, sreq.attempts)
            ).uniform(0.0, c.retry_jitter_ms)) if c.retry_jitter_ms else 0.0
            backoff_ms = c.retry_backoff_ms * (2 ** (sreq.attempts - 1)) \
                + jitter_ms
            sreq.not_before = now + backoff_ms / 1e3
            sreq.telemetry.n_retries += 1
            self.n_retries += 1
            if self.queue.offer(sreq, now, requeue=True):
                return
            # queue full: the retry loses to fresh admissions
        if (sreq.hedge is not None and sreq.hedge.status in
                (RequestStatus.QUEUED, RequestStatus.IN_FLIGHT)):
            # a live hedge still races: park instead of failing — the
            # hedge's delivery or failure settles this request, so it
            # reaches exactly ONE terminal status
            sreq.parked = True
            sreq.error = error
            return
        self._finalize_fail(sreq, error, terminal)

    def _settle_dead_hedge(self, hedge: ServeRequest,
                           terminal: List[ServeRequest]) -> None:
        """A hedge copy left the race (shed/expired) without
        delivering.  If its primary was parked on it, the primary's
        deferred failure becomes terminal NOW — no request may dangle
        with neither outcome."""
        primary = hedge.primary
        if primary.parked and primary.status is not RequestStatus.DONE:
            self._finalize_fail(primary,
                                primary.error or "hedge expired",
                                terminal)

    def _finalize_fail(self, sreq: ServeRequest, error: str,
                       terminal: List[ServeRequest]) -> None:
        sreq.status = RequestStatus.FAILED
        sreq.error = error
        self.n_failed += 1
        terminal.append(sreq)

    def _maybe_hedge(self, now: float) -> None:
        """Hedged re-dispatch: a PRIMARY request in flight past the
        latency SLO gets one duplicate enqueued to race it — if the
        original tick fails (transient / quarantined), the hedge
        delivers without waiting out a retry backoff."""
        sup = self.supervisor
        if (sup is None or sup.cfg.hedge_after_ms is None
                or self._inflight is None):
            return
        slo_s = sup.cfg.hedge_after_ms / 1e3
        for _, sreq in self._inflight.packed:
            if (sreq.is_hedge or sreq.status is not RequestStatus.IN_FLIGHT
                    or sreq.telemetry.n_hedges > 0):
                continue
            if now - sreq.telemetry.t_enqueue <= slo_s:
                continue
            hedge = ServeRequest(request=sreq.request, kind=sreq.kind,
                                 deadline=sreq.deadline, primary=sreq)
            if self.queue.offer(hedge, now):
                sreq.hedge = hedge
                sreq.telemetry.n_hedges += 1
                self.n_hedges += 1

    # ------------------------------------------------------------------
    # harvest + health checks
    # ------------------------------------------------------------------
    def _harvest(self, inflight: _Inflight,
                 terminal: List[ServeRequest]) -> None:
        sup = self.supervisor
        try:
            with inflight.core.fetch_span(inflight.tick_no):
                out, rgb, sp = inflight.core.fetch(inflight.outputs)
        except TransientTickError as e:
            now = self.clock()
            if sup is not None:
                sup.record_tick(inflight.tick_no, inflight.rung, False,
                                now - inflight.t_dispatch,
                                f"transient: {e}")
            for _, sreq in inflight.packed:
                self._fail(sreq, str(e), retryable=True, now=now,
                           terminal=terminal)
            return
        now = self.clock()
        wall = now - inflight.t_dispatch
        spars = None
        if out.layer_rates is not None:
            spars = {k: float(v) for k, v in out.layer_rates.items()}
        ok, reason = True, ""
        guard = sup is not None and sup.cfg.nan_guard
        with TraceAnnotation("serve.deliver", tick=inflight.tick_no,
                             slots=len(inflight.packed)):
            for slot, sreq in inflight.packed:
                finite = bool(
                    np.isfinite(np.asarray(rgb[slot])).all()
                    and np.isfinite(np.asarray(out.control[slot])).all()
                    and np.isfinite(np.asarray(out.raw_pred[slot])).all())
                if guard and not finite:
                    # quarantine: a non-finite result is NEVER delivered
                    ok, reason = False, "nan_output"
                    sup.n_quarantined += 1
                    sreq.telemetry.quarantined = True
                    self._fail(sreq, "non-finite result quarantined",
                               retryable=True, now=now, terminal=terminal)
                    continue
                if not finite:
                    self.n_nan_delivered += 1   # unsupervised: the leak
                self._deliver_one(sreq, slot, out, rgb, sp, spars, now,
                                  inflight, terminal)
        if sup is not None:
            dl = sup.cfg.tick_deadline_ms
            if ok and dl is not None and wall * 1e3 > dl:
                ok, reason = False, "stall"
            sup.record_tick(inflight.tick_no, inflight.rung, ok, wall,
                            reason)

    def _deliver_one(self, sreq: ServeRequest, slot: int, out, rgb, sp,
                     spars, now: float, inflight: _Inflight,
                     terminal: List[ServeRequest]) -> None:
        primary = sreq.primary if sreq.is_hedge else sreq
        if primary.status is RequestStatus.DONE:
            sreq.status = RequestStatus.DONE    # lost the race: discard
            return
        tel = primary.telemetry
        tel.t_deliver = now
        tel.deadline_missed = primary.expired(now)
        tel.rung = inflight.rung_name
        if sreq.is_hedge:
            tel.hedge_won = True
            tel.tick = inflight.tick_no     # the tick that delivered
            self.n_hedge_wins += 1
            sreq.status = RequestStatus.DONE
        primary.request.result = PerceptionResult(
            rgb=rgb[slot], control=out.control[slot],
            raw_pred=out.raw_pred[slot],
            stage_params=jax.tree_util.tree_map(
                lambda x, s=slot: x[s], sp),
            sparsity=spars, telemetry=tel)
        primary.status = RequestStatus.DONE
        self.n_delivered += 1
        self.n_deadline_missed += bool(tel.deadline_missed)
        terminal.append(primary)

    # ------------------------------------------------------------------
    def _prewarm(self) -> None:
        """Trace every ladder rung's tick executable up front so a
        breaker-driven swap never pays a trace in the serving path
        ("pre-built fallback executables")."""
        bank = StagingBank(self.cfg, self.batch, self.core.frame_hw,
                           self.core.enc_cfg.event_capacity)
        for core in self.cores:
            real = getattr(core, "_core", core)  # bypass fault injection
            real.fetch(real.dispatch(real.upload(bank)))

    def drain(self, max_steps: int = 10000) -> List[ServeRequest]:
        """Step until the queue and the pipeline are empty; returns
        every request that reached a terminal status while draining.
        NOTE: with a fake clock, retried requests gate on
        ``not_before`` — advance the clock between steps or they drain
        as FAILED when ``max_steps`` runs out."""
        finished: List[ServeRequest] = []
        for _ in range(max_steps):
            if not len(self.queue) and self._inflight is None:
                break
            finished.extend(self.step())
        return finished

    def run_to_completion(self, requests: List[PerceptionRequest],
                          max_steps: int = 10000) -> List[ServeRequest]:
        """Submit-then-drain convenience mirroring
        ``CognitiveEngine.run_to_completion`` (admission control still
        applies: the returned list includes REJECTED and malformed
        FAILED submits)."""
        submitted = [self.submit(r) for r in requests]
        dead = [s for s in submitted
                if s.status in (RequestStatus.REJECTED,
                                RequestStatus.FAILED)]
        return dead + self.drain(max_steps)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Serving counters: availability and shed/rejected/failed/
        retried/hedged work; supervisor state rides along when
        supervision is enabled.  Latency percentiles are the caller's,
        over its delivered requests' ``RequestTelemetry``."""
        terminal = (self.n_delivered + self.n_failed
                    + self.queue.n_expired)
        out = {
            "delivered": self.n_delivered,
            "rejected": self.queue.n_rejected,
            "expired": self.queue.n_expired,
            "failed": self.n_failed,
            "malformed": self.n_malformed,
            "retries": self.n_retries,
            "hedges": self.n_hedges,
            "hedge_wins": self.n_hedge_wins,
            "nan_delivered": self.n_nan_delivered,
            "deadline_missed": self.n_deadline_missed,
            "availability": (self.n_delivered / terminal) if terminal
            else float("nan"),
            "ticks": self.ticks,
            "n_devices": self.core.n_devices,
        }
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.stats()
        return out
