"""Process start to the window's opening: weights, warm-up, compiles or
compile-cache reads, the sessions' prefill and the arrivals' ramp."""


def read(ctx):
    return ctx.setup_s
