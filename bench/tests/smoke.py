"""Small copies of the benchmark's cells for tests on the CPU."""

import time

from bench import harness, model

SMOKE_MIX = {
    "qwen2-1.5b.chat": {"arrivals": dict(
        phases=[{"seconds": 1.0, "rate_per_s": 4.0}], ramp_s=1.0,
        prompt_len_pool=[3, 9, 20, 37],
        output_len={"median": 16, "sigma": 0.5, "min": 8, "max": 40})},
    "qwen2.5-14b-12l.long_decode": {"sessions": dict(
        context_len_pool=[40, 75], max_new_tokens=30)},
}
#: the `max_logit_gap` limit at this size (4 s windows, about 300
#: tokens compared in chat, 60 in long_decode), set between the sound
#: runs' widest gaps (chat <= 0.0125, long_decode <= 0.0235 over six
#: seeds each) and the float8 control's (chat >= 0.036, long_decode
#: >= 0.087)
SMOKE_LIMIT = {"qwen2-1.5b.chat": 0.025, "qwen2.5-14b-12l.long_decode": 0.04}
SMOKE_CHECK = dict(sample_requests=16, sample_tokens=600)
SMOKE_SERVER = {
    "qwen2-1.5b.chat": dict(n_slots=4, max_len=96, prefill_chunk=32),
    "qwen2.5-14b-12l.long_decode": dict(n_slots=2, max_len=112,
                                        prefill_chunk=32),
}


def cell(name: str, **hf_over) -> harness.Cell:
    c = harness.load_cell(name)
    c.hf = model.smoke_config(c.hf, **hf_over)
    c.mix = dict(c.mix, **SMOKE_MIX[name])
    c.server = dict(c.server, **SMOKE_SERVER[name])
    c.check = dict(c.check, max_logit_gap=SMOKE_LIMIT[name], **SMOKE_CHECK)
    return c


def _no_cache(root=None):
    """Tests compile in memory only: the checkout's persistent cache is
    for the benchmark's runs on the chip."""
    return None


harness.enable_compile_cache = _no_cache


def run(c: harness.Cell, seed: int, seconds: float = 4.0, *,
        kernel_impl: str = "ref", sabotage=None, trace: bool = False,
        control: bool = False) -> dict:
    return harness.run_cell(c, seed, seconds, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False, kernel_impl=kernel_impl,
                            log=lambda m: None, sabotage=sabotage,
                            control=control)
