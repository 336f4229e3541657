"""Share of the HBM roofline the paged decode kernel reaches: the bytes
its walk needs over the window (live pages of every active slot, query
and output rows, all layers; `bench/shapes.py`) at the chip's peak
bandwidth, over the kernel's device time. Decode attention is bound by
bandwidth, so the bytes set the bound."""

from bench import shapes, trace_reduce


def read(ctx):
    t, n = trace_reduce.op_time(ctx.red, trace_reduce.DECODE_KERNEL)
    if not n or not ctx.rec.decode_eff:
        return None
    bs = ctx.server["block_size"]
    need = sum(shapes.decode_kernel_bytes(ctx.hf, e, bs)
               for e in ctx.rec.decode_eff)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
