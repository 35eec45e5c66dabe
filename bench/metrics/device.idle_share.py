"""device.idle_share: 1 - (union of the device's operation intervals /
traced span), in %, from the profiler trace (layer: device)."""


def read(ctx):
    if not ctx.ops or ctx.hi <= ctx.lo:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
