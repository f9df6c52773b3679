"""idle_share.<cells>: the share of the traced slice in which no operation
ran on the device (1 - union of device-op intervals / slice length)."""


def read(name, ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
