"""The tick executable: the NPU's dense conv and matmul operations per
tick (``roofline.tick_flops``, from the configuration's shapes) over
the traced ticks' device time at the chip's bf16 peak, in percent."""
from roofline import peaks, tick_flops


def read(ctx):
    ticks = ctx.trace.ticks()
    if not ticks:
        return None
    device_s = sum(d for _, _, d in ticks) / 1e9
    flops = tick_flops(ctx.cell.config, ctx.batch) * len(ticks)
    ctx.notes.append(f"tick_mfu: {len(ticks)} ticks, {device_s:.6f} s of "
                     f"tick executions, {flops:.4e} flop")
    return 100.0 * flops / (device_s * peaks(ctx.device_kind)["bf16_flops"])
