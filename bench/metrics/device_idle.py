"""device_idle: the share of the traced window in which no XLA op ran on
the device, in percent (mean over the cell's chips)."""


def read(ctx):
    r = ctx.red
    if not r.has_device:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
