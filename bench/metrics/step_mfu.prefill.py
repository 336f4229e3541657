"""Model FLOPs of the window's prefill launches (`bench/shapes.py`)
over the device time of the programs that ran them, at the chip's bf16
peak: the whole prefill step's share, which bounds the prefill kernel's
roofline share."""

from bench import shapes, trace_reduce


def read(ctx):
    mods = trace_reduce.modules_containing(ctx.red,
                                           trace_reduce.PREFILL_KERNEL)
    if not mods or not ctx.rec.prefill:
        return None
    t = sum(d for _, _, d in mods) / 1e9
    flops = sum(shapes.prefill_model_flops(ctx.hf, s, e)
                for s, e in ctx.rec.prefill)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
