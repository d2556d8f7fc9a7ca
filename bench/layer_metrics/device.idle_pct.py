"""Share of the traced window in which no operation ran on the chip:
one minus the union of the device's operation intervals over the window,
in percent (averaged over the cell's chips)."""


def read(ctx):
    w = ctx.window_s()
    if w <= 0 or not ctx.device_ops(0):
        return None
    return 100.0 * (1.0 - ctx.busy_s() / w)
