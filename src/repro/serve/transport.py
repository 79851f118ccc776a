"""Host-side transport for the cognitive serving stack: numpy staging
banks and the double-buffer that overlaps upload with compute.

A submit is a memcpy into a :class:`StagingBank` slot — no device
dispatch (the zero-copy discipline PR 3 established; asserted by the
dispatch-counting engine test).  ``EngineCore.upload`` later moves a
whole bank with ONE ``jax.device_put`` and donates the device buffers
to the tick executable.

:class:`DoubleBuffer` holds TWO banks.  While tick N computes on the
device buffers uploaded from bank A (already donated — the host copy in
bank A is dead the moment ``device_put`` returns), the scheduler packs
tick N+1 into bank B and uploads it; JAX's async dispatch queues the
N+1 launch behind N, so the host-side pack + H2D transfer of N+1 runs
concurrently with N's compute.  This is the software analogue of the
paper's ping-pong line buffers between the sensor front-end and the
NPU.

Request staging/validation is shared here so ``CognitiveEngine`` and
``FleetEngine`` enforce identical payload rules (voxels XOR events,
mandatory bayer frame, DVS channel layout, FIFO budgeting on overfull
event windows).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.configs.base import EncodingConfig, SNNConfig
from repro.core.encoding import EventStream, fit_stream


def _bank_layout(cfg: SNNConfig, batch: int, frame_hw: Tuple[int, int],
                 event_capacity: int):
    """``(shape, dtype)`` of every staging array, in upload order."""
    H, W = frame_hw
    ev = (batch, event_capacity)
    return (((cfg.time_steps, batch, cfg.height, cfg.width,
              cfg.in_channels), np.float32),
            ((batch, H, W), np.float32),
            EventStream(t=(ev, np.float32), x=(ev, np.int32),
                        y=(ev, np.int32), p=(ev, np.int32),
                        valid=(ev, bool)),
            ((batch,), bool))


def bank_nbytes(cfg: SNNConfig, batch: int, frame_hw: Tuple[int, int],
                event_capacity: int) -> int:
    """Bytes of one staging bank: what its upload moves to the device."""
    voxels, bayer, events, from_events = _bank_layout(
        cfg, batch, frame_hw, event_capacity)
    return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for shape, dtype in (voxels, bayer, *events, from_events))


class StagingBank:
    """Host numpy slot buffers for one tick batch: DVS voxel windows,
    Bayer frames, per-slot bounded event FIFOs, and the per-slot
    encoded-vs-submitted flag.  Inactive slots carry zeros and ride
    along in the fixed-shape executable."""

    def __init__(self, cfg: SNNConfig, batch: int,
                 frame_hw: Tuple[int, int], event_capacity: int):
        voxels, bayer, events, from_events = _bank_layout(
            cfg, batch, frame_hw, event_capacity)
        self.voxels = np.zeros(*voxels)
        self.bayer = np.zeros(*bayer)
        self.events = EventStream(*(np.zeros(*a) for a in events))
        self.from_events = np.zeros(*from_events)
        # the slot pytree is built ONCE: staging mutates the arrays in
        # place, so the donated upload tuple never needs rebuilding on
        # the per-tick path (ISSUE 9 satellite — engine_tick staging
        # overhead)
        self._tuple = (self.voxels, self.bayer, self.events,
                       self.from_events)

    def stage_voxels(self, slot: int, voxels, bayer) -> None:
        self.voxels[:, slot] = np.asarray(voxels, np.float32)
        self.bayer[slot] = np.asarray(bayer, np.float32)
        self.from_events[slot] = False

    def stage_events(self, slot: int, ev: EventStream, bayer) -> None:
        """``ev`` must already fit the bank's FIFO capacity (see
        :func:`stage_request`, which budgets overfull windows)."""
        self.events.t[slot] = np.asarray(ev.t, np.float32)
        self.events.x[slot] = np.asarray(ev.x, np.int32)
        self.events.y[slot] = np.asarray(ev.y, np.int32)
        self.events.p[slot] = np.asarray(ev.p, np.int32)
        self.events.valid[slot] = np.asarray(ev.valid, bool)
        self.bayer[slot] = np.asarray(bayer, np.float32)
        self.from_events[slot] = True

    def as_tuple(self):
        """The slot pytree in ``EngineCore.upload`` argument order —
        the SAME tuple object every call (slots mutate in place), so
        per-tick staging is zero-allocation on the host side."""
        return self._tuple


class DoubleBuffer:
    """Two staging banks, flipped every dispatched tick.  ``front`` is
    the bank being packed for the NEXT tick; ``flip()`` after its upload
    so the other (whose device copy was donated) becomes packable."""

    def __init__(self, make_bank, enabled: bool = True):
        self.banks = [make_bank(), make_bank()] if enabled else [make_bank()]
        self.idx = 0

    @property
    def front(self) -> StagingBank:
        return self.banks[self.idx]

    def flip(self) -> None:
        self.idx = (self.idx + 1) % len(self.banks)


def validate_request(req, in_channels: int,
                     events_only: bool = False, *,
                     time_steps: int = None,
                     voxel_hw: Tuple[int, int] = None,
                     frame_hw: Tuple[int, int] = None) -> str:
    """Payload validation shared by every submit path.  Returns the
    staging kind ``"voxels"`` | ``"events"`` or raises ValueError with
    the engine's historical messages.

    The optional keyword shapes harden the edge: when given, a voxel
    payload must be exactly ``[time_steps, H, W, in_channels]`` and the
    bayer frame ``frame_hw`` — shape garbage then fails HERE with a
    client-attributable error instead of blowing up mid-tick inside the
    serving loop (the fleet's malformed-request fault mode)."""
    if events_only or req.voxels is None:
        if req.events is None:
            if events_only:
                raise ValueError(f"request {req.rid} carries no events")
            raise ValueError(f"request {req.rid}: neither voxels nor "
                             f"events")
        if req.bayer is None:
            raise ValueError(f"request {req.rid} carries no bayer frame")
        if in_channels != 2:
            raise ValueError("event ingestion needs in_channels=2 "
                             "(DVS polarity channels)")
        for leaf in (req.events.t, req.events.x, req.events.y,
                     req.events.p):
            if np.ndim(leaf) != 1:
                raise ValueError(
                    f"request {req.rid}: event stream leaves must be "
                    f"1-D [N], got ndim={np.ndim(leaf)}")
        _check_bayer(req, frame_hw)
        return "events"
    if req.bayer is None:
        raise ValueError(f"request {req.rid} carries no bayer frame")
    vox = np.shape(req.voxels)
    if len(vox) != 4:
        raise ValueError(
            f"request {req.rid}: voxels must be [T, H, W, C], got "
            f"shape {vox}")
    want = (time_steps if time_steps is not None else vox[0],
            voxel_hw[0] if voxel_hw is not None else vox[1],
            voxel_hw[1] if voxel_hw is not None else vox[2],
            in_channels)
    if vox != want:
        raise ValueError(
            f"request {req.rid}: voxel shape {vox} does not match the "
            f"engine's [T, H, W, C]={want}")
    _check_bayer(req, frame_hw)
    return "voxels"


def _check_bayer(req, frame_hw) -> None:
    shape = np.shape(req.bayer)
    if len(shape) != 2:
        raise ValueError(
            f"request {req.rid}: bayer frame must be 2-D [H, W], got "
            f"shape {shape}")
    if frame_hw is not None and tuple(shape) != tuple(frame_hw):
        raise ValueError(
            f"request {req.rid}: bayer frame {shape} does not match "
            f"the engine's frame_hw={tuple(frame_hw)}")


def stage_request(bank: StagingBank, slot: int, req, kind: str,
                  enc_cfg: EncodingConfig) -> None:
    """Stage a validated request into a bank slot (host memcpy only).
    Event windows are coerced to the bounded per-slot FIFO:
    under-full windows validity-padded, overfull ones budgeted to the
    ``enc_cfg.event_capacity`` earliest events."""
    if kind == "events":
        bank.stage_events(slot, fit_stream(req.events,
                                           enc_cfg.event_capacity),
                          req.bayer)
    else:
        bank.stage_voxels(slot, req.voxels, req.bayer)
