"""Pluggable ISP stage registry — the software analogue of the FPGA's
run-time reconfigurability (paper §V–§VI).

The fixed exposure→DPC→demosaic→AWB→NLM→gamma→sharpen pipeline becomes
a set of registered *stages*.  Each stage declares:

  * a name,
  * an ordered tuple of control parameters with ``[lo, hi]`` ranges and
    defaults (``ParamSpec``),
  * one implementation per *backend* (``"jnp"`` pure-XLA reference,
    ``"pallas"`` TPU kernels from ``repro.kernels.ops``; unknown
    backends fall back to ``"jnp"`` per stage).

A pipeline is then just an ordered stage-name tuple (``ISPConfig`` in
``repro.configs.base``), and the NPU control vector is mapped onto the
declared ranges automatically — ``control_dim`` is *derived* from the
registered stages instead of hardcoded index positions.

Adding a custom stage::

    from repro.isp.stages import ParamSpec, register_stage

    def my_vignette(x, p):          # x: image, p: {name: scalar}
        ...

    register_stage("vignette", params=(ParamSpec("amount", 0.0, 1.0, 0.0),),
                   impl=my_vignette, domain="rgb", kind="pointwise")

then put ``"vignette"`` anywhere in ``ISPConfig.stages``.

Fusion metadata (the ``backend="pallas_fused"`` streaming path)
---------------------------------------------------------------

Each stage may declare how it composes into the fused single-pass
datapath (``repro.isp.fuse`` plans, ``repro.kernels.isp_fused``
executes):

  * ``kind="pointwise"`` — output pixel depends only on the input
    pixel and the stage params.  Contiguous pointwise stages compile
    into ONE tiled Pallas kernel; the stage's ``jnp`` impl is reused
    verbatim per VMEM-resident tile.
  * ``kind="stencil"`` — output pixel reads a bounded neighbourhood.
    Declares ``radius`` (halo width), ``pad`` ("wrap" for
    ``jnp.roll``-style cyclic references, "zero" for SAME-conv
    references) and ``window_fn(win, params, *, y0, x0, bh, bw)``,
    which maps a halo'd ``[bh+2r, bw+2r(, C)]`` window to the
    ``[bh, bw(, C')]`` output tile.  A stencil stage terminates its
    fusion segment; any preceding pointwise run rides along as the
    kernel's prologue (recomputed on the halo — the classic
    overlapped-tile trade).
  * ``kind="reduce"`` — needs a global statistic of its input (AWB's
    grey-world means).  Declares ``stats_fn(image, params) -> [w]``,
    ``stats_width`` and the pointwise ``apply_fn(image, params, stats)``;
    the planner materialises the stage's input, runs ONE up-front
    stats pass, and fuses ``apply_fn`` into the segment kernel.
  * ``kind=None`` (default) — no metadata: the fused path falls back
    to materialising the stage through its ``jnp`` impl as an opaque
    single-stage segment, so unannotated custom stages stay correct,
    just unfused.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.isp.awb import (AWB_STATS_WIDTH, apply_wb, awb_apply_stats,
                           awb_gains, awb_stats)
from repro.isp.demosaic import DEMOSAIC_RADIUS, demosaic_mhc, demosaic_window
from repro.isp.dpc import DPC_RADIUS, dpc_correct, dpc_window
from repro.isp.gamma import (SHARPEN_CONSTS, SHARPEN_RADIUS, apply_gamma,
                             gamma_lut, gamma_tile, sharpen_luma,
                             sharpen_window)
from repro.isp.nlm import NLM_RADIUS, nlm_denoise, nlm_window
from repro.isp.tone import (CCM_CONSTS, apply_saturation,
                            apply_saturation_tile,
                            reinhard_tonemap)


class ParamSpec(NamedTuple):
    """One NPU-controllable parameter: mapped from the control vector's
    [0, 1] sigmoid output onto ``[lo, hi]`` by lerp."""
    name: str
    lo: float
    hi: float
    default: float


# Stage impls take (image, params) where params is a {name: scalar}
# dict following the stage's declared ParamSpecs.
StageFn = Callable[[jax.Array, Dict[str, jax.Array]], jax.Array]


@dataclasses.dataclass(frozen=True)
class Stage:
    name: str
    params: Tuple[ParamSpec, ...]
    impls: Dict[str, StageFn]       # backend name -> implementation
    domain: str = "rgb"             # "bayer" | "rgb" | "any": input domain
    out_domain: Optional[str] = None  # None => unchanged (demosaic: "rgb")
    doc: str = ""
    # --- fusion metadata (see module docstring) ------------------------
    kind: Optional[str] = None      # "pointwise" | "stencil" | "reduce"
    radius: int = 0                 # stencil halo width
    pad: str = "wrap"               # stencil halo fill: "wrap" | "zero"
    window_fn: Optional[Callable] = None   # stencil: halo'd window -> tile
    tile_fn: Optional[Callable] = None     # pointwise fused form
    #   (x, params, consts) — only needed when the stage's jnp impl
    #   closes over array constants; otherwise the impl is reused
    fuse_consts: Tuple = ()         # array constants fed to the fused form
    #   (Pallas kernels cannot close over non-scalar constants, so the
    #    fused executor passes these as extra kernel inputs)
    stats_fn: Optional[Callable] = None    # reduce: (image, params) -> [w]
    stats_width: int = 0
    apply_fn: Optional[Callable] = None    # reduce: (image, params, stats)

    def impl_for(self, backend: str) -> StageFn:
        """Resolve a backend implementation, falling back to ``jnp``."""
        fn = self.impls.get(backend)
        return fn if fn is not None else self.impls["jnp"]


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

STAGES: Dict[str, Stage] = {}
BACKENDS: List[str] = []

# Bumped on every (re-)registration; the fusion planner keys its plan
# cache on it so replacing a stage invalidates stale segmentations.
REGISTRY_VERSION = 0


def _bump_registry_version() -> None:
    global REGISTRY_VERSION
    REGISTRY_VERSION += 1


def register_backend(name: str) -> None:
    if name not in BACKENDS:
        BACKENDS.append(name)


def register_stage(name: str, params: Tuple[ParamSpec, ...],
                   impl: StageFn, domain: str = "rgb",
                   out_domain: Optional[str] = None,
                   doc: str = "", kind: Optional[str] = None,
                   radius: int = 0, pad: str = "wrap",
                   window_fn: Optional[Callable] = None,
                   tile_fn: Optional[Callable] = None,
                   fuse_consts: Tuple = (),
                   stats_fn: Optional[Callable] = None,
                   stats_width: int = 0,
                   apply_fn: Optional[Callable] = None) -> Stage:
    """Register (or replace) a stage with its ``jnp`` reference impl and
    optional fusion metadata (see module docstring).  Replacing keeps
    any previously attached non-jnp backend impls."""
    if kind not in (None, "pointwise", "stencil", "reduce"):
        raise ValueError(f"stage {name!r}: unknown fusion kind {kind!r}")
    if kind == "stencil" and (window_fn is None or radius <= 0):
        raise ValueError(f"stencil stage {name!r} needs window_fn and a "
                         f"positive radius")
    if pad not in ("wrap", "zero"):
        raise ValueError(f"stage {name!r}: pad must be 'wrap' or 'zero'")
    if kind == "reduce" and (stats_fn is None or apply_fn is None
                             or stats_width <= 0):
        raise ValueError(f"reduce stage {name!r} needs stats_fn, apply_fn "
                         f"and a positive stats_width")
    if kind == "pointwise" and fuse_consts and tile_fn is None:
        raise ValueError(
            f"pointwise stage {name!r} declares fuse_consts but no "
            f"tile_fn to receive them (a jnp impl cannot take consts)")
    impls = dict(STAGES[name].impls) if name in STAGES else {}
    impls["jnp"] = impl
    stage = Stage(name=name, params=tuple(params), impls=impls,
                  domain=domain, out_domain=out_domain, doc=doc,
                  kind=kind, radius=radius, pad=pad, window_fn=window_fn,
                  tile_fn=tile_fn, fuse_consts=tuple(fuse_consts),
                  stats_fn=stats_fn, stats_width=stats_width,
                  apply_fn=apply_fn)
    STAGES[name] = stage
    _bump_registry_version()
    return stage


def register_stage_impl(name: str, backend: str, impl: StageFn) -> None:
    """Attach an alternative backend implementation to a stage.

    The registered ``Stage`` is rebuilt with a fresh ``impls`` dict
    rather than mutated: the frozen dataclass's dict is shared with any
    previously returned/replaced ``Stage`` objects, and mutating it in
    place would leak the new impl into those aliases (and into stages a
    test restored from a saved reference)."""
    if name not in STAGES:
        raise KeyError(f"unknown ISP stage {name!r}")
    register_backend(backend)
    stage = STAGES[name]
    impls = dict(stage.impls)
    impls[backend] = impl
    STAGES[name] = dataclasses.replace(stage, impls=impls)
    _bump_registry_version()


def get_stage(name: str) -> Stage:
    try:
        return STAGES[name]
    except KeyError:
        raise KeyError(f"unknown ISP stage {name!r}; registered: "
                       f"{sorted(STAGES)}") from None


# ---------------------------------------------------------------------------
# Control-vector <-> per-stage parameter mapping
# ---------------------------------------------------------------------------

def stage_param_specs(stage_names) -> List[Tuple[str, ParamSpec]]:
    """Flattened (stage, spec) list in pipeline order — the layout of
    the control vector.  Duplicate stage names are rejected: the
    {stage: {param: value}} layout cannot carry two distinct parameter
    sets for the same stage, so a duplicate would silently alias its
    control slots."""
    seen = set()
    for name in stage_names:
        if name in seen:
            raise ValueError(
                f"duplicate ISP stage {name!r} in {tuple(stage_names)}: "
                f"control-vector mapping is keyed by stage name")
        seen.add(name)
    out: List[Tuple[str, ParamSpec]] = []
    for name in stage_names:
        for spec in get_stage(name).params:
            out.append((name, spec))
    return out


def control_dim_for(stage_names) -> int:
    """Derived control-vector width for a stage ordering."""
    return len(stage_param_specs(stage_names))


def control_to_stage_params(ctrl: jax.Array, stage_names) \
        -> Dict[str, Dict[str, jax.Array]]:
    """Map a [control_dim] sigmoid vector in [0, 1] onto the declared
    ranges: slot ``i`` drives the ``i``-th (stage, param) in order."""
    out: Dict[str, Dict[str, jax.Array]] = {n: {} for n in stage_names}
    for i, (sname, spec) in enumerate(stage_param_specs(stage_names)):
        out[sname][spec.name] = spec.lo + (spec.hi - spec.lo) * ctrl[i]
    return out


def stage_params_to_control(stage_params, stage_names) -> jax.Array:
    """Inverse of :func:`control_to_stage_params` (for tests and for
    seeding the NPU control head from a known-good parameter set)."""
    slots = []
    for sname, spec in stage_param_specs(stage_names):
        v = stage_params[sname][spec.name]
        slots.append((v - spec.lo) / (spec.hi - spec.lo))
    return jnp.stack([jnp.asarray(s, jnp.float32) for s in slots])


def default_stage_params(stage_names) -> Dict[str, Dict[str, jax.Array]]:
    return {n: {s.name: jnp.float32(s.default)
                for s in get_stage(n).params}
            for n in stage_names}


# ---------------------------------------------------------------------------
# Pipeline runner
# ---------------------------------------------------------------------------

def check_stage_order(stage_names) -> None:
    """Trace-time domain check for a stage ordering: a stage declaring
    ``domain="rgb"`` cannot run before demosaic, and vice versa."""
    domain = "bayer"
    for name in stage_names:
        stage = get_stage(name)
        if stage.domain not in ("any", domain):
            raise ValueError(
                f"stage {name!r} expects {stage.domain!r} input but the "
                f"pipeline {tuple(stage_names)} is in the {domain!r} "
                f"domain at that point")
        domain = stage.out_domain or domain


def resolve_stage_params(name: str, stage_params) -> Dict[str, jax.Array]:
    """One stage's {param: scalar} dict with missing entries defaulted."""
    p = dict(stage_params.get(name, {})) if stage_params else {}
    for spec in get_stage(name).params:
        p.setdefault(spec.name, jnp.float32(spec.default))
    return p


def run_stages(raw: jax.Array, stage_params, stage_names,
               backend: str = "jnp") -> jax.Array:
    """Run ``raw`` ([H, W] Bayer mosaic) through the named stages in
    order.  ``stage_params``: {stage: {param: scalar}} (missing stages
    get their defaults).  One compiled executable serves every parameter
    setting — the TPU analogue of reconfiguring the FPGA without
    re-synthesis.

    ``backend="pallas_fused"`` routes through the fusion planner
    (``repro.isp.fuse``): the ordering is segmented into maximal fused
    runs and executed in O(#segments) memory passes instead of
    O(#stages).  Stage orderings are domain-checked at trace time
    either way."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown ISP backend {backend!r}; registered: "
                         f"{BACKENDS} (register_backend to add one)")
    # catch typos early: every stage key must name a registered stage
    # (extra registered stages are tolerated — a full settings dict may
    # drive a trimmed pipeline) and every param a declared ParamSpec.
    for sname, sp in (stage_params or {}).items():
        declared = {spec.name for spec in get_stage(sname).params}
        unknown = set(sp) - declared
        if unknown:
            raise ValueError(
                f"unknown param(s) {sorted(unknown)} for ISP stage "
                f"{sname!r}; declared: {sorted(declared)}")
    check_stage_order(stage_names)
    if backend == "pallas_fused":
        from repro.isp.fuse import run_fused_stages   # lazy: pallas path
        return run_fused_stages(raw, stage_params, tuple(stage_names))
    x = raw
    for name in stage_names:
        stage = get_stage(name)
        p = resolve_stage_params(name, stage_params)
        x = stage.impl_for(backend)(x, p)
    return x


# ---------------------------------------------------------------------------
# Built-in stages (paper §V) — same math as the seed's fixed pipeline,
# split at stage boundaries so orderings stay bit-compatible.
# ---------------------------------------------------------------------------

def _exposure(x, p):
    return jnp.clip(x * p["gain"], 0.0, 1.0)


def _dpc(x, p):
    fixed, _ = dpc_correct(x, threshold=p["threshold"])
    return fixed


def _demosaic_jnp(x, p):
    return demosaic_mhc(x)


def _demosaic_pallas(x, p):
    from repro.kernels.ops import demosaic_op
    return demosaic_op(x)


def _awb(x, p):
    return awb_apply_stats(x, p, awb_gains(x))


def _nlm_jnp(x, p):
    return nlm_denoise(x, strength=p["strength"])


def _nlm_pallas(x, p):
    from repro.kernels.ops import nlm_op
    return nlm_op(x, p["strength"])


def _gamma(x, p):
    return apply_gamma(x, gamma_lut(p["gamma"]))


def _sharpen(x, p):
    return sharpen_luma(x, p["amount"])


def _tonemap(x, p):
    return reinhard_tonemap(x, p["strength"])


def _ccm(x, p):
    return apply_saturation(x, p["saturation"])


register_backend("jnp")
register_backend("pallas")
register_backend("pallas_fused")     # fusion-planned streaming path

register_stage(
    "exposure", (ParamSpec("gain", 0.5, 2.0, 1.0),), _exposure,
    domain="any", kind="pointwise",
    doc="digital gain, clipped to [0,1] (either domain)")
register_stage(
    "dpc", (ParamSpec("threshold", 0.05, 0.5, 0.2),), _dpc,
    domain="bayer", kind="stencil", radius=DPC_RADIUS, pad="wrap",
    window_fn=dpc_window,
    doc="dynamic defective pixel correction (§V-B.1)")
register_stage(
    "demosaic", (), _demosaic_jnp, domain="bayer", out_domain="rgb",
    kind="stencil", radius=DEMOSAIC_RADIUS, pad="zero",
    window_fn=demosaic_window,
    doc="Malvar-He-Cutler 5x5 demosaic (§V-B.3)")
register_stage(
    "awb", (ParamSpec("enable", 0.0, 1.0, 1.0),
            ParamSpec("bias_r", 0.5, 2.0, 1.0),
            ParamSpec("bias_b", 0.5, 2.0, 1.0)), _awb,
    kind="reduce", stats_fn=awb_stats, stats_width=AWB_STATS_WIDTH,
    apply_fn=awb_apply_stats,
    doc="grey-world AWB, softly blended, with NPU r/b bias (§V-B.2)")
register_stage(
    "nlm", (ParamSpec("strength", 0.0, 1.0, 0.3),), _nlm_jnp,
    kind="stencil", radius=NLM_RADIUS, pad="wrap", window_fn=nlm_window,
    doc="bounded-window non-local-means denoise (§V-B.4)")
register_stage(
    "gamma", (ParamSpec("gamma", 0.4, 3.0, 2.2),), _gamma,
    kind="pointwise", tile_fn=gamma_tile,
    doc="256-entry gamma LUT with linear interp (§V-B.5)")
register_stage(
    "sharpen", (ParamSpec("amount", 0.0, 1.0, 0.3),), _sharpen,
    kind="stencil", radius=SHARPEN_RADIUS, pad="wrap",
    window_fn=sharpen_window, fuse_consts=SHARPEN_CONSTS,
    doc="luma sharpening in YCbCr (§V-B.5)")
register_stage(
    "tonemap", (ParamSpec("strength", 0.0, 1.0, 0.5),), _tonemap,
    kind="pointwise",
    doc="global Reinhard tone-mapping; strength 0 ~= identity")
register_stage(
    "ccm", (ParamSpec("saturation", 0.0, 2.0, 1.0),), _ccm,
    kind="pointwise", tile_fn=apply_saturation_tile,
    fuse_consts=CCM_CONSTS,
    doc="luma-preserving saturation matrix (CCM analogue)")

register_stage_impl("demosaic", "pallas", _demosaic_pallas)
register_stage_impl("nlm", "pallas", _nlm_pallas)
