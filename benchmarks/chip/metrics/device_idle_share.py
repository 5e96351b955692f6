"""device_idle_share: the share of the traced window in which no operation ran
on the device, averaged over the cell's chips (100 * (1 - busy / window))."""


def read(ctx):
    if not ctx.busy_s or ctx.window_s <= 0:
        return None
    busy = sum(ctx.busy_s) / len(ctx.busy_s)
    return 100.0 * (1.0 - busy / ctx.window_s)
