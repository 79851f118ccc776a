"""Spiking backbone and heads: the least time the chip could take for
the spike-conv calls' dense work (``roofline.conv_calls``: operations
and bytes at float32 from the configuration's shapes) over their device
time, in percent.  The bound (compute or memory) goes to the notes."""
from metrics.kernels import CONV
from roofline import conv_calls, least_time


def read(ctx):
    ticks = len(ctx.trace.ticks())
    ns, calls = ctx.trace.kernel_ns(CONV)
    if not ticks or not calls:
        return None
    least, bounds = 0.0, {}
    for name, flops, nbytes in conv_calls(ctx.cell.config, ctx.batch):
        t, bound = least_time(flops, nbytes, ctx.device_kind)
        least += t
        bounds[bound] = bounds.get(bound, 0.0) + t
    ctx.notes.append(f"conv_roofline: {calls} conv kernel calls over "
                     f"{ticks} ticks; least time per tick {least:.4e} s, "
                     f"bound by " + ", ".join(
                         f"{k} {v:.4e} s" for k, v in sorted(bounds.items())))
    return 100.0 * least * ticks / (ns / 1e9)
