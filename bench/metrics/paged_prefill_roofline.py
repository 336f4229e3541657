"""Share of its roofline the paged prefill kernel reaches: for each
launch the larger of its FLOPs at the chip's bf16 peak and its bytes at
peak bandwidth (`bench/shapes.py`), summed over the window, over the
kernel's device time."""

from bench import shapes, trace_reduce


def read(ctx):
    t, n = trace_reduce.op_time(ctx.red, trace_reduce.PREFILL_KERNEL)
    if not n or not ctx.rec.prefill:
        return None
    bs = ctx.server["block_size"]
    least = 0.0
    for start, end in ctx.rec.prefill:
        f, b = shapes.prefill_kernel_work(ctx.hf, start, end, bs)
        least += max(f / ctx.peaks["bf16_flops"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
