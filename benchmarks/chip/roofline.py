"""Chip peaks, and the operations and bytes the NPU's work needs.

The counts are of the algorithm, from the shapes the configuration's
plain reference declares (``conv_layers``, ``dense_layers``): a dense
convolution's multiply-adds whatever the kernels skip or pad, and each
call's input, weights and output once, at float32.  They never
depend on what the program does, so a roofline share read from them
cannot pass 100% unless the trace's time leaves out part of the work.
"""
from __future__ import annotations

import math

from harness import reference

# device_kind -> peaks of one chip.  Source: Google Cloud documentation,
# "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
F32 = 4


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"it to PEAKS with its source") from None


def conv_calls(cfg: dict, batch: int):
    """Per spiking conv of one tick: (name, flops, bytes).  A tick runs
    ``batch`` slots of ``time_steps`` bins each."""
    n = batch * cfg["time_steps"]
    h, w = cfg["dvs_resolution"]
    out = []
    for c in reference(cfg).conv_layers(cfg):
        ho, wo = math.ceil(h / c.stride), math.ceil(w / c.stride)
        taps = c.kernel * c.kernel * (c.cin // c.groups)
        flops = 2 * n * ho * wo * taps * c.cout
        nbytes = F32 * (n * h * w * c.cin + taps * c.cout
                        + n * ho * wo * c.cout)
        out.append((c.name, flops, nbytes))
        h, w = ho, wo
    return out


def tick_flops(cfg: dict, batch: int) -> int:
    """Dense conv and matmul operations of one tick's NPU: every conv
    and every dense layer, over T steps and the batch."""
    n = batch * cfg["time_steps"]
    dense = sum(2 * n * cin * cout
                for _, cin, cout in reference(cfg).dense_layers(cfg))
    return sum(f for _, f, _ in conv_calls(cfg, batch)) + dense


def least_time(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound): the least time the chip could take for the
    work, and which peak sets it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
