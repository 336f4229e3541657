"""Device time of one compiled prefill launch (one chunk): the mean
length of the program executions that ran the paged prefill kernel."""

from bench import trace_reduce


def read(ctx):
    mods = trace_reduce.modules_containing(ctx.red,
                                           trace_reduce.PREFILL_KERNEL)
    if not mods:
        return None
    return sum(d for _, _, d in mods) / len(mods) / 1e6
