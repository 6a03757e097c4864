"""device_idle_share: the share of the traced window in which no operation
ran on device 0."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    d0 = ctx.devices[0]
    return 100.0 * (1.0 - ctx.busy_ns(d0) * 1e-9 / ctx.window_s)
