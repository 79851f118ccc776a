"""Which device operations belong to which layer, by the HLO name the
trace gives them (a Pallas kernel's custom call is named after the
jitted op that launches it, e.g. ``_spike_conv_impl.13``,
``vmap_jit_stencil_segment_op__.10``), and a group's device time per
tick."""

VOXEL = r"event_voxel_op"
CONV = r"_spike_conv_impl|spike_conv_lif"
NPU = CONV + r"|norm_affine_lif_op|_lif_scan_jit|_spike_matmul_jit"
ISP_FUSED = r"stencil_segment_op|pointwise_segment_op"


def ms_per_tick(ctx, pattern, label):
    ticks = len(ctx.trace.ticks())
    ns, calls = ctx.trace.kernel_ns(pattern)
    if not ticks or not calls:
        return None
    ctx.notes.append(f"{label}: {calls} kernel calls over {ticks} ticks, "
                     f"{ns / 1e6:.6f} ms")
    return ns / ticks / 1e6
