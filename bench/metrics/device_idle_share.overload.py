"""Device idle share in the traced window: 1 - (union of the device
operations' intervals) / window, in percent."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
