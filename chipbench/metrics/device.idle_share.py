"""Per cent of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window. Moves decisions_per_s."""


def read(ctx):
    if ctx.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
