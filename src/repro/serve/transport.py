"""Host-side transport for the cognitive serving stack: numpy staging
banks and the double-buffer that overlaps upload with compute.

A submit is a memcpy into a :class:`StagingBank` slot — no device
dispatch (the zero-copy discipline PR 3 established; asserted by the
dispatch-counting engine test).  ``EngineCore.upload`` later moves the
bank with ONE ``jax.device_put`` and donates the slab's device buffer
to the tick executable.

What a tick uploads is only what its slots use.  Every slot's event
window, Bayer frame and ``from_events`` flag sit in one row of an
``int32`` slab (:class:`SlabLayout`), so they go up as one array.  The
voxel slab stays an array of its own and goes up only in a tick in
which some packed slot staged voxels; other ticks read a zero buffer
that the engine core keeps on the device.

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

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import EncodingConfig, SNNConfig
from repro.core.encoding import EventStream, fit_stream

SLAB_ALIGN = 128            # a slab row is padded to whole 128-lane tiles


class SlabLayout:
    """Where each staged input sits in a slot's row of the ``int32``
    slab: the event window (``t`` as float32 bits, then ``x``, ``y``,
    ``p``, and ``valid`` as 0/1, ``event_capacity`` words each; only
    when the NPU takes DVS events, ``in_channels == 2``), the Bayer
    frame as float32 bits, ``H * W`` words, and the ``from_events``
    flag, one word.  ``sections`` maps each name to its static
    ``(offset, words)``; ``words`` is the row, padded to
    ``SLAB_ALIGN``."""

    def __init__(self, cfg: SNNConfig, frame_hw: Tuple[int, int],
                 event_capacity: int):
        self.frame_hw = tuple(frame_hw)
        sizes = ([(name, event_capacity) for name in EventStream._fields]
                 if cfg.in_channels == 2 else [])
        sizes += [("bayer", self.frame_hw[0] * self.frame_hw[1]),
                  ("from_events", 1)]
        self.sections, off = {}, 0
        for name, n in sizes:
            self.sections[name] = (off, n)
            off += n
        self.words = -(-off // SLAB_ALIGN) * SLAB_ALIGN
        self.has_events = cfg.in_channels == 2

    def _split(self, slab, as_f32, as_flag):
        """``(bayer, events, from_events)`` cut from ``slab`` at the
        static offsets; ``events`` is None without an event section."""
        def cut(name):
            off, n = self.sections[name]
            return slab[:, off:off + n]
        bayer = as_f32(cut("bayer")).reshape(slab.shape[0], *self.frame_hw)
        events = None
        if self.has_events:
            events = EventStream(t=as_f32(cut("t")), x=cut("x"), y=cut("y"),
                                 p=cut("p"), valid=as_flag(cut("valid")))
        return bayer, events, as_flag(cut("from_events")[:, 0])

    def views(self, slab: np.ndarray):
        """Writable numpy views into a host slab (flags as int32 0/1)."""
        return self._split(slab, lambda a: a.view(np.float32),
                           lambda a: a)

    def unpack(self, slab):
        """The device side of :meth:`views`, on a traced slab: float
        sections bitcast back and flags as bool — bit for bit what was
        staged."""
        return self._split(
            slab, lambda a: lax.bitcast_convert_type(a, jnp.float32),
            lambda a: a != 0)


def voxel_shape(cfg: SNNConfig, batch: int) -> Tuple[int, ...]:
    """The voxel slab ``[T, B, H, W, C]``."""
    return (cfg.time_steps, batch, cfg.height, cfg.width, cfg.in_channels)


def bank_nbytes(cfg: SNNConfig, batch: int, frame_hw: Tuple[int, int],
                event_capacity: int) -> Tuple[int, int]:
    """Bytes one upload of a staging bank moves: the slab alone, and
    the slab with the voxel slab."""
    slab = 4 * batch * SlabLayout(cfg, frame_hw, event_capacity).words
    return slab, slab + 4 * int(np.prod(voxel_shape(cfg, batch)))


class StagingBank:
    """Host numpy slot buffers for one tick batch: the slab that holds
    every slot's event FIFO, Bayer frame and encoded-vs-submitted flag
    (``bayer``, ``events`` and ``from_events`` are views into it), and
    the DVS voxel windows.  Inactive slots ride along in the
    fixed-shape executable; their outputs are never read.

    ``voxels_staged`` records whether a slot packed since the last
    reset staged voxels, and so whether the upload carries the voxel
    slab; whoever packs the bank anew resets it."""

    def __init__(self, cfg: SNNConfig, batch: int,
                 frame_hw: Tuple[int, int], event_capacity: int):
        self.layout = SlabLayout(cfg, frame_hw, event_capacity)
        self.slab = np.zeros((batch, self.layout.words), np.int32)
        self.voxels = np.zeros(voxel_shape(cfg, batch), np.float32)
        self.bayer, self.events, self.from_events = self.layout.views(
            self.slab)
        self.voxels_staged = False
        # both upload tuples are built ONCE: staging mutates the arrays
        # in place, so the per-tick path never rebuilds them
        self._uploads = ((self.slab,), (self.slab, self.voxels))

    def stage_voxels(self, slot: int, voxels, bayer) -> None:
        self.voxels[:, slot] = np.asarray(voxels, np.float32)
        self.bayer[slot] = np.asarray(bayer, np.float32)
        self.from_events[slot] = False
        self.voxels_staged = True

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
        """What ``EngineCore.upload`` moves: ``(slab,)``, or ``(slab,
        voxels)`` when a slot staged voxels — the SAME tuple objects
        every call (slots mutate in place), so per-tick staging is
        zero-allocation on the host side."""
        return self._uploads[self.voxels_staged]


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
