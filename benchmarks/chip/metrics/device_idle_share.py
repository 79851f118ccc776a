"""The device: 1 - (union of the device's operation intervals / traced
window), in percent."""


def read(ctx):
    tr = ctx.trace
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
