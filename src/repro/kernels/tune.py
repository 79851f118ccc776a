"""Shape-keyed kernel autotuner: measured launch configs per (op, shape).

The paper's FPGA datapath is synthesised per network — block RAM
widths, MAC array shapes and the conv→LIF pipeline boundary are picked
per layer at build time.  The TPU analogue is this module: for each
(op, layer shape) the tuner sweeps launch block shapes (``bm/bn/bk``),
activity-gate modes (``mask``/``inline``/``none``) and fusion variants
(fused conv→LIF vs the per-op composition) against MEASURED wall-clock
on the layer's real inputs, and caches the winner in a persistent
tuning table.

How a sweep is bounded: candidates are first ranked by the roofline
launch estimate (``repro.launch.roofline.kernel_launch_estimate`` —
compute/memory bound plus per-grid-step overhead, with gated FLOPs
discounted by the measured live-tile fraction), and only the
``TuneConfig.prune_to`` most promising configs are wall-clocked
(min over ``reps``, after a warmup call that absorbs compile time).
The untuned default is always measured too, so every table entry
records its own speedup.

Dispatch contract (``repro.kernels.ops``):

* ``resolve``/``dispatch`` are PURE Python at trace time — a shape key
  is looked up through an lru cache, so repeated jit traces of the same
  layer see one stable ``LaunchConfig`` and reuse one executable (the
  no-retrace property tests/test_tune.py asserts).
* Tuning happens on the FIRST EAGER call of an op under the
  ``tuning()`` context: inputs are concrete there, so the sweep times
  the kernel on the layer's actual activation sparsity — a gate mode
  that wins on synthetic dense data and loses on 95%-sparse DVS voxels
  is ranked by what the network really feeds it.
* Table resolution chain: ``set_table`` (explicit) > the
  ``REPRO_TUNE_TABLE`` env file > the packaged ``tuned_defaults.json``
  shipped next to this module > untuned defaults.  ``off()`` forces
  untuned defaults (the baseline the tuned-vs-default bench rows
  compare against).

Versioning: tables carry ``schema`` (file format) and
``kernels_version`` (numerics/launch semantics of the kernels they
were measured against).  ``TuningTable.load`` invalidates wholesale on
either mismatch — a stale table silently re-tuned beats a stale table
silently trusted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import time
from typing import Callable, Dict, List, Optional

import jax

from repro.configs.base import TuneConfig
from repro.kernels import blocks
from repro.kernels.blocks import (CANONICAL_K_BLOCK, DEFAULT_BK, DEFAULT_BM,
                                  DEFAULT_BN, DEFAULT_LIF_BLOCK_N,
                                  validate_bk)
from repro.launch.roofline import kernel_launch_estimate

# File-format version of the JSON table.
TUNE_SCHEMA_VERSION = 1
# Version of the kernels the measurements are valid for — bump whenever
# kernel numerics or launch semantics change (e.g. CANONICAL_K_BLOCK).
# v2: whole-backbone fused segments (ISSUE 9) — new "backbone_seg" op
# keys; stale v1 tables are wholesale-invalidated.
KERNELS_VERSION = 2

# The packaged default table (committed, produced by the bench sweep).
DEFAULT_TABLE_PATH = os.path.join(os.path.dirname(__file__),
                                  "tuned_defaults.json")


@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """One launch decision: tile shapes + gate mode + fusion variant.
    Frozen/hashable so it can ride into jit static args unchanged."""
    bm: int = DEFAULT_BM
    bn: int = DEFAULT_BN
    bk: int = DEFAULT_BK
    gate: str = "mask"              # "mask" | "inline" | "none"
    fused: bool = False             # conv_lif: fused kernel vs per-op


# Untuned per-op defaults — what ``off()`` and an empty table resolve
# to.  conv_lif defaults to the UNFUSED per-op composition (the PR 5
# path), so fusion is an earned, measured win, never a silent default.
_OP_DEFAULTS: Dict[str, LaunchConfig] = {
    "spike_conv": LaunchConfig(),
    "spike_dwconv": LaunchConfig(),
    "spike_matmul": LaunchConfig(gate="inline"),
    "lif_scan": LaunchConfig(bn=DEFAULT_LIF_BLOCK_N, gate="none"),
    "conv_lif": LaunchConfig(fused=False),
    # whole-backbone segments likewise default to the per-layer
    # composition — an untuned deployment behaves exactly like PR 8
    "backbone_seg": LaunchConfig(fused=False),
}


def default_config(op: str) -> LaunchConfig:
    return _OP_DEFAULTS.get(op, LaunchConfig())


def shape_key(op: str, **dims) -> str:
    """Stable table key, e.g. ``"conv_lif|B2,HW1024,K18,N8,T3"``."""
    return op + "|" + ",".join(f"{k}{v}" for k, v in sorted(dims.items()))


class TuningTable:
    """key -> winning LaunchConfig (+ its measured µs and the untuned
    default's µs, so every entry documents its own speedup)."""

    def __init__(self, entries: Optional[Dict[str, Dict]] = None):
        self.entries: Dict[str, Dict] = dict(entries or {})

    def config_for(self, key: str) -> Optional[LaunchConfig]:
        e = self.entries.get(key)
        if e is None:
            return None
        return LaunchConfig(bm=int(e["bm"]), bn=int(e["bn"]),
                            bk=int(e["bk"]), gate=str(e["gate"]),
                            fused=bool(e["fused"]))

    def record(self, key: str, cfg: LaunchConfig, us: float,
               default_us: float) -> None:
        self.entries[key] = dict(dataclasses.asdict(cfg),
                                 us=round(us, 3),
                                 default_us=round(default_us, 3))

    def to_json(self) -> Dict:
        return {"schema": TUNE_SCHEMA_VERSION,
                "kernels_version": KERNELS_VERSION,
                "entries": self.entries}

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        """Load a table; a schema or kernels_version mismatch
        invalidates it WHOLESALE (returns an empty table)."""
        with open(path) as f:
            data = json.load(f)
        if (data.get("schema") != TUNE_SCHEMA_VERSION
                or data.get("kernels_version") != KERNELS_VERSION):
            return cls()
        return cls(data.get("entries", {}))


# ---------------------------------------------------------------------------
# Active-table state (module-level; epoch-keyed so the resolve cache
# can never serve a stale entry after a table swap)
# ---------------------------------------------------------------------------

_UNSET = object()                   # fall through to env/packaged chain
_OFF = object()                     # force untuned defaults
_explicit = _UNSET
_epoch = 0


@dataclasses.dataclass
class _TuneContext:
    table: TuningTable
    cfg: TuneConfig


_tune_ctx: Optional[_TuneContext] = None

_FILE_CACHE: Dict[str, tuple] = {}  # path -> (mtime, TuningTable)


def _bump_epoch() -> None:
    global _epoch
    _epoch += 1


def _load_table_file(path: str) -> Optional[TuningTable]:
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    hit = _FILE_CACHE.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    try:
        table = TuningTable.load(path)
    except (OSError, ValueError, KeyError):
        return None
    _FILE_CACHE[path] = (mtime, table)
    return table


def active_table() -> Optional[TuningTable]:
    """The table dispatch currently resolves through (chain: tuning
    context > set_table > REPRO_TUNE_TABLE env > packaged defaults)."""
    if _tune_ctx is not None:
        return _tune_ctx.table
    if _explicit is _OFF:
        return None
    if _explicit is not _UNSET:
        return _explicit
    env = os.environ.get("REPRO_TUNE_TABLE")
    if env:
        return _load_table_file(env)
    return _load_table_file(DEFAULT_TABLE_PATH)


def set_table(table: Optional[TuningTable]) -> None:
    """Install ``table`` as the active table (``None`` resets to the
    env/packaged chain).  Bumps the epoch: every subsequent resolve
    re-reads.  NOTE: already-traced jit executables keep the configs
    they were traced with — benches that swap tables mid-run must
    dispatch through fresh calls (the public ops do; a user-jitted
    closure over an op does not)."""
    global _explicit
    _explicit = table if table is not None else _UNSET
    _bump_epoch()


@contextlib.contextmanager
def off():
    """Force untuned per-op defaults — the default-block pallas
    baseline the tuned-vs-default bench rows compare against."""
    global _explicit, _tune_ctx
    prev, prev_ctx = _explicit, _tune_ctx
    _explicit, _tune_ctx = _OFF, None
    _bump_epoch()
    try:
        yield
    finally:
        _explicit, _tune_ctx = prev, prev_ctx
        _bump_epoch()


@contextlib.contextmanager
def pinned(table: Optional[TuningTable]):
    """Resolve through a SNAPSHOT table for the duration of the block
    (``None`` pins the env/packaged chain as it stands — a no-op).

    This is the engine's trace-time hoist (ISSUE 9 satellite): the
    engine captures ``active_table()`` once at construction and wraps
    its jit'd tick body in ``pinned(snapshot)``, so every op dispatch
    inside the tick resolves against the table the engine was BUILT
    with — once, at trace time — instead of re-reading module state on
    each tick.  A later ``set_table`` swap cannot silently half-apply
    to an engine whose executable is already traced."""
    if table is None:
        yield
        return
    global _explicit, _tune_ctx
    prev, prev_ctx = _explicit, _tune_ctx
    _explicit, _tune_ctx = table, None
    _bump_epoch()
    try:
        yield
    finally:
        _explicit, _tune_ctx = prev, prev_ctx
        _bump_epoch()


def default_tune_config() -> TuneConfig:
    from repro.configs.registry import TUNE_CONFIGS
    name = ("smoke" if os.environ.get("REPRO_TUNE_SMOKE", "0") == "1"
            else "default")
    return TUNE_CONFIGS[name]


@contextlib.contextmanager
def tuning(table: Optional[TuningTable] = None,
           tune_cfg: Optional[TuneConfig] = None):
    """Enable tune-on-first-dispatch: while active, the first EAGER
    call of an op on a shape not yet in ``table`` runs the sweep on
    that call's real inputs and records the winner.  Yields the table
    (save it afterwards to persist).  Traced calls only resolve."""
    global _tune_ctx
    t = table if table is not None else TuningTable()
    ctx = _TuneContext(t, tune_cfg or default_tune_config())
    prev = _tune_ctx
    _tune_ctx = ctx
    _bump_epoch()
    try:
        yield t
    finally:
        _tune_ctx = prev
        _bump_epoch()


def tuning_active() -> bool:
    return _tune_ctx is not None


def concrete(*arrays) -> bool:
    """True when none of the arrays is a jit tracer — i.e. we are on an
    eager call whose inputs the sweep can actually measure."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


# ---------------------------------------------------------------------------
# Resolution (the trace-time hot path: pure, lru-cached, epoch-keyed)
# ---------------------------------------------------------------------------

def resolve(op: str, key: str) -> LaunchConfig:
    return _resolve_cached(op, key, _epoch)


@functools.lru_cache(maxsize=4096)
def _resolve_cached(op: str, key: str, epoch: int) -> LaunchConfig:
    table = active_table()
    cfg = table.config_for(key) if table is not None else None
    return cfg if cfg is not None else default_config(op)


# ---------------------------------------------------------------------------
# Candidate space
# ---------------------------------------------------------------------------

_CONV_GATES = ("mask", "inline", "none")


def candidates(op: str, dims: Dict[str, int],
               tune_cfg: TuneConfig) -> List[LaunchConfig]:
    """Enumerate the legal launch configs for (op, shape) — every
    ``bk`` a canonical multiple (``validate_bk``), capped at
    ``tune_cfg.max_candidates``."""
    out: List[LaunchConfig] = []
    if op in ("spike_conv", "spike_matmul"):
        gates = _CONV_GATES if op == "spike_conv" else ("inline",)
        for gate in gates:
            for bm in (128, 256):
                for bn in (128, 256):
                    for bk in (128, 256):
                        out.append(LaunchConfig(bm=bm, bn=bn,
                                                bk=validate_bk(bk),
                                                gate=gate))
    elif op == "conv_lif":
        # fused variants: bm is the row-chunk of the per-batch slab
        for gate in _CONV_GATES:
            for bm in (128, 256, 512):
                out.append(LaunchConfig(bm=bm, gate=gate, fused=True))
        # per-op variants (the conv's own launch shapes are tuned by
        # its nested spike_conv dispatch; gate rides through)
        for gate in _CONV_GATES:
            out.append(LaunchConfig(gate=gate, fused=False))
    elif op == "backbone_seg":
        # fused variants: one megakernel per segment, bm the row-chunk
        # of every layer's per-batch MAC loop; "mask" does not apply
        # (interior patch matrices never exist outside the kernel)
        for gate in ("inline", "none"):
            for bm in (128, 256, 512):
                out.append(LaunchConfig(bm=bm, gate=gate, fused=True))
        # the per-layer composition (each layer's own tuned dispatch)
        out.append(LaunchConfig(fused=False))
    elif op == "spike_dwconv":
        for gate in ("mask", "none"):
            for bm in (128, 256, 512):
                out.append(LaunchConfig(bm=bm, gate=gate))
    elif op == "lif_scan":
        for bn in (256, 512, 1024, 2048):
            out.append(LaunchConfig(bn=bn, gate="none"))
    else:
        out.append(default_config(op))
    return out[:tune_cfg.max_candidates]


def _grid_steps(op: str, dims: Dict[str, int], cfg: LaunchConfig) -> int:
    def cdiv(a, b):
        return -(-a // b)

    if op in ("spike_conv", "spike_matmul"):
        return (cdiv(dims["M"], cfg.bm) * cdiv(dims["N"], cfg.bn)
                * cdiv(dims["K"], cfg.bk))
    if op == "conv_lif":
        M = dims["B"] * dims["T"] * dims["HW"]
        if cfg.fused:
            return dims["B"]
        # per-op: conv matmul grid + the norm+LIF kernel's batch grid
        return (cdiv(M, cfg.bm) * cdiv(dims["N"], cfg.bn)
                * cdiv(dims["K"], cfg.bk)) + dims["B"]
    if op == "backbone_seg":
        # fused: ONE launch, one program per batch element; unfused:
        # the per-layer composition's precomputed grid-step total
        # (dims["G"] — see ops.backbone_segment_op)
        return dims["B"] if cfg.fused else dims["G"]
    if op == "spike_dwconv":
        return cdiv(dims["M"], cfg.bm)
    if op == "lif_scan":
        return cdiv(dims["N"], cfg.bn)
    return 1


def estimate(op: str, dims: Dict[str, int], cfg: LaunchConfig,
             live: float = 1.0, interpret: Optional[bool] = None) -> float:
    """Roofline launch estimate (seconds) used to RANK candidates —
    ``live`` is the measured live-tile fraction of the real inputs,
    discounting gated FLOPs.  Only relative order matters.
    ``interpret`` defaults to :func:`blocks.interpret_mode`."""
    if interpret is None:
        interpret = blocks.interpret_mode()
    gated = cfg.gate != "none"
    frac = live if gated else 1.0
    if op in ("spike_conv", "spike_matmul"):
        M, K, N = dims["M"], dims["K"], dims["N"]
        flops = 2.0 * M * K * N * frac
        # gating also discounts the activation-side traffic: a dead
        # tile's occupancy bit can gate its DMA (scalar prefetch) just
        # like its MXU pass, so in the memory-bound regime sparsity
        # still separates gated from dense candidates
        bytes_moved = 4.0 * (M * K * frac + K * N + M * N)
        if cfg.gate == "inline":
            # the in-kernel jnp.any re-reduces the activation tile on
            # every (N-step, K-step) visit instead of once up front
            bytes_moved += 4.0 * M * K * (dims["N"] / cfg.bn - 1)
    elif op == "conv_lif":
        M = dims["B"] * dims["T"] * dims["HW"]
        K, N = dims["K"], dims["N"]
        flops = 2.0 * M * K * N * frac
        rt = 1 if cfg.fused else 3   # HBM round-trips of the conv out
        bytes_moved = 4.0 * (M * K * frac + K * N + rt * M * N)
    elif op == "backbone_seg":
        # aggregate segment terms precomputed by the dispatcher: F total
        # MACs, A total per-layer activation elements.  The fused path
        # keeps interior activations VMEM-resident (they cross HBM
        # once, at the segment edge); the per-layer path round-trips
        # each layer's conv output ~3x (conv out, norm in, spikes out)
        flops = 2.0 * dims["F"] * frac
        rt = 1 if cfg.fused else 3
        bytes_moved = 4.0 * dims["A"] * rt
    elif op == "spike_dwconv":
        M, taps, C = dims["M"], dims["taps"], dims["C"]
        flops = 2.0 * M * taps * C * frac
        bytes_moved = 4.0 * (M * taps * C + M * C)
    elif op == "lif_scan":
        flops = 5.0 * dims["T"] * dims["N"]
        bytes_moved = 8.0 * dims["T"] * dims["N"]
    else:
        flops, bytes_moved = 0.0, 0.0
    return kernel_launch_estimate(flops, bytes_moved,
                                  _grid_steps(op, dims, cfg),
                                  interpret=interpret)


# ---------------------------------------------------------------------------
# Measurement + sweep
# ---------------------------------------------------------------------------

def measure(runner: Callable[[LaunchConfig], object], cfg: LaunchConfig,
            reps: int, key: str) -> float:
    """Min-of-reps wall-clock (µs) of ``runner(cfg)`` after one warmup
    call that absorbs trace/compile time; inf if the config fails (the
    op key and the exception are printed, so a compiler refusal during
    a sweep is visible)."""
    try:
        jax.block_until_ready(runner(cfg))
    except Exception as e:
        print(f"tune: {key} candidate {cfg} failed: "
              f"{type(e).__name__}: {e}")
        return float("inf")
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(runner(cfg))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _sweep(op: str, dims: Dict[str, int],
           runner: Callable[[LaunchConfig], object],
           tune_cfg: TuneConfig, live: float):
    key = shape_key(op, **dims)
    cands = candidates(op, dims, tune_cfg)
    ranked = sorted(cands, key=lambda c: estimate(op, dims, c, live))
    short = ranked[:max(1, tune_cfg.prune_to)]
    dflt = default_config(op)
    if dflt not in short:
        short.append(dflt)          # the baseline is always measured
    best_cfg, best_us, default_us = dflt, float("inf"), float("inf")
    for c in short:
        us = measure(runner, c, tune_cfg.reps, key)
        if c == dflt:
            default_us = us
        if us < best_us:
            best_cfg, best_us = c, us
    return best_cfg, best_us, default_us


def dispatch(op: str, dims: Dict[str, int],
             runner: Optional[Callable[[LaunchConfig], object]] = None,
             *, live: float = 1.0) -> LaunchConfig:
    """The op-dispatch entry point (called by ``repro.kernels.ops``):
    resolve the LaunchConfig for (op, shape).  When a ``tuning()``
    context is active, ``runner`` is non-None (the caller verified the
    inputs are concrete) and the shape is untuned, run the sweep on the
    real inputs first and record the winner."""
    key = shape_key(op, **dims)
    ctx = _tune_ctx
    if (ctx is not None and runner is not None
            and key not in ctx.table.entries):
        cfg, us, default_us = _sweep(op, dims, runner, ctx.cfg, live)
        ctx.table.record(key, cfg, us, default_us)
        _bump_epoch()               # resolve cache must see the entry
        return cfg
    return resolve(op, key)
