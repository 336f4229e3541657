"""Model FLOPs of every token the window processed (prefill rows and
decode tokens, attention over their real context; `bench/shapes.py`)
over the summed tick time (`bench/step` spans) at the chip's bf16 peak."""

from bench import shapes


def read(ctx):
    ticks = sum(d for n, _, d in ctx.red.spans if n == "bench/step") / 1e9
    if not ticks:
        return None
    flops = sum(shapes.decode_model_flops(ctx.hf, e)
                for e in ctx.rec.decode_eff)
    flops += sum(shapes.prefill_model_flops(ctx.hf, s, e)
                 for s, e in ctx.rec.prefill)
    return 100.0 * flops / (ticks * ctx.peaks["bf16_flops"])
