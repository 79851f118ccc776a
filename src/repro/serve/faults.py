"""Deterministic fault injection for the fleet-serving stack.

The paper positions AceleradorSNN for ADAS/UAV perception, where a
tick that silently NaNs or a hung accelerator is WORSE than a slow
one.  This module makes those failure modes first-class and
*replayable*: a :class:`FaultPlan` is an explicit per-(tick, slot)
event list expanded from a :class:`repro.configs.base.FaultConfig`
seed, and a :class:`FaultInjector` applies it at the
``EngineCore``/``StagingBank`` boundary — wrapping ``upload`` /
``dispatch`` / ``fetch`` — so the ``FleetEngine`` and
``FleetSupervisor`` code under test is the REAL serving code, not a
mock.

Fault kinds (:class:`FaultKind`):

* ``CORRUPT_INPUT``   — NaN poison written into one staged voxel slot
  just before the host->device upload (DMA / SEU analogue).
* ``NAN_OUTPUT``      — NaN/Inf forced into one slot of the fetched
  NPU outputs (kernel-corruption analogue).  The supervisor's NaN
  guard must quarantine it; an unsupervised fleet would deliver it.
* ``TRANSIENT_ERROR`` — the tick raises :class:`TransientTickError`
  at harvest (device-side launch/compute failure; retryable).
* ``STALL``           — the harvest stalls ``stall_s`` past dispatch
  (hung-accelerator analogue).  On a real clock this sleeps; tests and
  the soak bench pass an ``advance`` hook that moves a fake clock.
* ``MALFORMED``       — the CLIENT edge submits a structurally invalid
  request (shape garbage / missing payloads).  Not applied by the
  injector (it never reaches the core); drivers consult
  ``plan.malformed_at(tick)`` and submit :func:`make_malformed_request`.

Determinism contract: ``FaultPlan.from_config(cfg, n_ticks, batch)``
depends only on its arguments — the same seed always yields the same
schedule, so the CI chaos-smoke lane and any local repro see the same
fault sequence tick for tick.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from typing import Callable, Dict, Iterable, List, Optional, Set

import numpy as np

from repro.configs.base import FaultConfig


class FaultKind(str, enum.Enum):
    CORRUPT_INPUT = "corrupt_input"
    NAN_OUTPUT = "nan_output"
    TRANSIENT_ERROR = "transient_error"
    STALL = "stall"
    MALFORMED = "malformed"


class TransientTickError(RuntimeError):
    """A device-side tick failure the supervisor may retry (launch
    failure, transfer error, preempted accelerator)."""


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault.  ``slot`` targets one staging slot for the
    slot-scoped kinds; whole-tick kinds (transient/stall) leave it
    None.  ``value`` is the poison payload (NaN or +/-inf)."""
    tick: int
    kind: FaultKind
    slot: Optional[int] = None
    value: float = float("nan")
    stall_s: float = 0.0


class FaultPlan:
    """An explicit, immutable injection schedule keyed on tick."""

    def __init__(self, events: Iterable[FaultEvent] = ()):
        self._by_tick: Dict[int, List[FaultEvent]] = {}
        for ev in events:
            self._by_tick.setdefault(ev.tick, []).append(ev)

    @classmethod
    def from_config(cls, cfg: FaultConfig, n_ticks: int,
                    batch: int) -> "FaultPlan":
        """Expand a seeded :class:`FaultConfig` into the deterministic
        event list: one independent draw per (tick, kind)."""
        rng = np.random.default_rng(cfg.seed)
        events: List[FaultEvent] = []
        for tick in range(n_ticks):
            # one draw per kind per tick, in a FIXED kind order so the
            # schedule is a pure function of (seed, n_ticks, batch)
            for kind, p in ((FaultKind.CORRUPT_INPUT, cfg.p_corrupt_input),
                            (FaultKind.NAN_OUTPUT, cfg.p_nan_output),
                            (FaultKind.TRANSIENT_ERROR, cfg.p_transient),
                            (FaultKind.STALL, cfg.p_stall),
                            (FaultKind.MALFORMED, cfg.p_malformed)):
                hit = rng.random() < p
                slot = int(rng.integers(0, max(batch, 1)))
                poison = (float("inf")
                          if rng.random() < cfg.inf_fraction
                          else float("nan"))
                if not hit:
                    continue            # draws above keep the stream aligned
                if kind in (FaultKind.CORRUPT_INPUT, FaultKind.NAN_OUTPUT):
                    events.append(FaultEvent(tick, kind, slot=slot,
                                             value=poison))
                elif kind is FaultKind.STALL:
                    events.append(FaultEvent(tick, kind,
                                             stall_s=cfg.stall_ms / 1e3))
                else:
                    events.append(FaultEvent(tick, kind))
        return cls(events)

    def events_at(self, tick: int) -> List[FaultEvent]:
        return self._by_tick.get(tick, [])

    def malformed_at(self, tick: int) -> bool:
        return any(ev.kind is FaultKind.MALFORMED
                   for ev in self.events_at(tick))

    def kinds(self) -> Set[FaultKind]:
        return {ev.kind for evs in self._by_tick.values() for ev in evs}

    def __len__(self) -> int:
        return sum(len(v) for v in self._by_tick.values())

    def __iter__(self):
        for tick in sorted(self._by_tick):
            yield from self._by_tick[tick]


class _SharedTicker:
    """One dispatch counter shared by every injector of a fleet, so
    the fault schedule stays tick-aligned across ladder rungs."""

    def __init__(self):
        self.tick = 0


class FaultInjector:
    """Wraps ONE EngineCore with the plan.  Every attribute the fleet
    reads (``frame_hw``, ``enc_cfg``, ``n_devices``, ``_step``, ...)
    delegates to the wrapped core; only ``upload``/``dispatch``/
    ``fetch`` are intercepted.  Multiple rungs of a fallback ladder
    share one :class:`_SharedTicker` so the tick index — and therefore
    the schedule — is global to the fleet, not per-rung."""

    def __init__(self, core, plan: FaultPlan,
                 ticker: Optional[_SharedTicker] = None,
                 advance: Optional[Callable[[float], None]] = None):
        self._core = core
        self._plan = plan
        self._ticker = ticker if ticker is not None else _SharedTicker()
        # how a STALL manifests: real deployments block (sleep); tests
        # and the soak bench advance their fake serving clock instead
        self._advance = advance if advance is not None else time.sleep
        self.n_injected = 0

    def __getattr__(self, name):
        return getattr(self._core, name)

    # -- intercepted boundary ------------------------------------------
    def upload(self, bank, tick: int = -1):
        fault_tick = self._ticker.tick
        for ev in self._plan.events_at(fault_tick):
            if ev.kind is FaultKind.CORRUPT_INPUT:
                # the poison reaches the tick where the slot staged
                # voxels (they then go up); an event slot never reads it
                voxels = bank.voxels
                voxels[:, ev.slot % voxels.shape[1]] = ev.value
                self.n_injected += 1
        return self._core.upload(bank, tick)

    def dispatch(self, slots_dev, tick: int = -1):
        fault_tick = self._ticker.tick
        self._ticker.tick += 1
        return (fault_tick, self._core.dispatch(slots_dev, tick))

    def fetch(self, outputs):
        tick, real = outputs
        faults = self._plan.events_at(tick)
        for ev in faults:
            if ev.kind is FaultKind.TRANSIENT_ERROR:
                self.n_injected += 1
                raise TransientTickError(
                    f"injected transient failure at tick {tick}")
        out, rgb, sp = self._core.fetch(real)
        for ev in faults:
            if ev.kind is FaultKind.STALL:
                self.n_injected += 1
                self._advance(ev.stall_s)
            elif ev.kind is FaultKind.NAN_OUTPUT:
                self.n_injected += 1
                slot = ev.slot % out.raw_pred.shape[0]
                raw = np.array(out.raw_pred)
                ctl = np.array(out.control)
                raw[slot] = ev.value
                ctl[slot] = ev.value
                out = out._replace(raw_pred=raw, control=ctl)
        return out, rgb, sp


def make_malformed_request(rid: int, seed: int = 0):
    """A structurally invalid :class:`PerceptionRequest` — the chaos
    drivers submit these on the plan's MALFORMED ticks.  Variants cycle
    deterministically on (rid, seed): missing payloads, missing bayer,
    and shape garbage that MUST be caught at validation, never allowed
    to blow up mid-tick inside the serving loop."""
    from repro.serve.cognitive_engine import PerceptionRequest
    variant = (rid + seed) % 4
    if variant == 0:                       # neither voxels nor events
        return PerceptionRequest(rid=rid)
    if variant == 1:                       # voxels but no bayer frame
        return PerceptionRequest(
            rid=rid, voxels=np.zeros((1, 2, 2, 2), np.float32))
    if variant == 2:                       # rank garbage
        return PerceptionRequest(
            rid=rid, voxels=np.zeros((3,), np.float32),
            bayer=np.zeros((4, 4), np.float32))
    return PerceptionRequest(               # wrong voxel grid shape
        rid=rid, voxels=np.zeros((1, 1, 1, 7), np.float32),
        bayer=np.zeros((4, 4), np.float32))
