"""Device time of the backbone and head kernels per tick (kernel names in
``metrics/kernels.py``)."""
from metrics.kernels import NPU, ms_per_tick


def read(ctx):
    return ms_per_tick(ctx, NPU, "backbone_ms")
