"""Device time of the voxelizer per tick (kernel names in
``metrics/kernels.py``)."""
from metrics.kernels import VOXEL, ms_per_tick


def read(ctx):
    return ms_per_tick(ctx, VOXEL, "encode_ms")
