"""Device time of the fused ISP segments per tick (kernel names in
``metrics/kernels.py``)."""
from metrics.kernels import ISP_FUSED, ms_per_tick


def read(ctx):
    return ms_per_tick(ctx, ISP_FUSED, "isp_ms")
