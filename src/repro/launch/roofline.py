"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.  ``cost_analysis``/``memory_analysis`` of the SPMD-
partitioned executable are per-device, so terms are computed per device:

  compute_term    = flops_per_dev / peak
  memory_term     = bytes_per_dev / hbm_bw
  collective_term = collective_bytes_per_dev / ici_bw

Collective bytes are parsed from the partitioned HLO: the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (a ring-transfer estimate; each device moves ~the
full result size over its links as (N-1)/N ≈ 1).
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device result bytes of collective ops, keyed by op kind."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs_rhs = line.split(" = ", 1)
        rhs = lhs_rhs[1]
        for op in _COLLECTIVES:
            # match "<shape(s)> <op>(" — op must be the instruction, not a
            # substring of e.g. "all-reduce-start"s operand names
            m = re.match(r"^\s*(\([^)]*\)|\S+)\s+(%?)(" + op +
                         r")(-start|-done)?\(", rhs)
            if m:
                if m.group(4) == "-done":
                    break               # counted at -start
                out[op] += _shape_bytes(m.group(1))
                break
    return out


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> Dict[str, float]:
    terms = {
        "compute_s": flops_per_dev / PEAK_FLOPS,
        "memory_s": bytes_per_dev / HBM_BW,
        "collective_s": coll_bytes_per_dev / ICI_BW,
    }
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    bound = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction"] = (terms["compute_s"] / bound) if bound else 0.0
    return terms


# Per-grid-step fixed cost of a Pallas kernel launch.  In interpret mode
# (this container's CPU CI) each grid step is a Python-level kernel-body
# evaluation, so the fixed cost dwarfs the roofline terms and grid-step
# COUNT is the first-order wall-clock predictor — exactly why the
# autotuner's candidate ranking must include it.  On real hardware the
# per-step cost is the Mosaic dispatch overhead, orders of magnitude
# smaller.
INTERPRET_STEP_OVERHEAD_S = 50e-6
COMPILED_STEP_OVERHEAD_S = 2e-6

# VMEM (TPU v5e: 128 MiB per TensorCore; Mosaic's default scoped limit
# per kernel is 16 MiB).  Kernels whose working set outgrows the default
# request ``VMEM_LIMIT_BYTES`` through their compiler params.  The
# backbone fusion planner (``repro.kernels.backbone_fuse.plan_segments``)
# forces a segment boundary when a fused run's per-batch working set
# would exceed ``VMEM_BYTES`` — a quarter of that limit, because its
# estimate counts neither double-buffered blocks nor lane padding.
VMEM_LIMIT_BYTES = 64 * 2 ** 20
VMEM_BYTES = 16 * 2 ** 20
F32_BYTES = 4


def vmem_residency_estimate(*elem_counts: int) -> int:
    """Bytes of VMEM a kernel program holds resident, given the f32
    element counts of its live buffers (inputs, patch matrices,
    accumulators, scratch).  Deliberately coarse — everything counted
    at f32 width, no alignment padding — because the planner only needs
    a monotone budget signal, not an allocator."""
    return F32_BYTES * sum(int(n) for n in elem_counts)


def kernel_launch_estimate(flops: float, bytes_moved: float,
                           grid_steps: int, *,
                           interpret: bool = True) -> float:
    """Coarse wall-clock estimate (seconds) for one Pallas launch: the
    roofline compute/memory bound plus a fixed per-grid-step overhead.

    Used by ``repro.kernels.tune`` to RANK candidate launch configs and
    prune the measured sweep — only relative order matters, so the model
    is deliberately minimal (no VMEM-pressure or pipelining terms)."""
    step = (INTERPRET_STEP_OVERHEAD_S if interpret
            else COMPILED_STEP_OVERHEAD_S)
    return (max(flops / PEAK_FLOPS, bytes_moved / HBM_BW)
            + grid_steps * step)
