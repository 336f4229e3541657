"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    return 100.0 * (1.0 - ctx.red.busy_s / ctx.red.window_s)
