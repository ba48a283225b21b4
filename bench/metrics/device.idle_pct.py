"""Share of the traced window in which no operation ran on the device,
averaged over the chips."""


def read(ctx):
    red = ctx.trace
    if red is None:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
