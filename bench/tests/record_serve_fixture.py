"""Records `fixtures/cpu_serve_trace.xplane.pb`, the small CPU trace
that `test_program_spans.py` reads: a tiny paged `ContinuousBatcher`
(chunked prefill, staggered submits, one request that ends at its first
token) drained tick by tick inside the benchmark's `bench/window` and
`bench/step` spans, with the program's `serve/*` spans inside them.

    cd bench/tests && JAX_PLATFORMS=cpu python3 - < record_serve_fixture.py

(read from standard input, so that no path of the recording machine
ends up in the trace's metadata)
"""

import contextlib
import glob
import os
import shutil
import sys
import tempfile

HERE = os.getcwd()
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import init_lm  # noqa: E402
from repro.serve import ContinuousBatcher, Request  # noqa: E402


def drain(cb, uid0, prompts, span):
    cb.submit(Request(uid0, prompts[0], 6))
    for _ in range(2):
        with span("bench/step"):
            cb.step()
    cb.submit(Request(uid0 + 1, prompts[1], 3))
    cb.submit(Request(uid0 + 2, prompts[2], 1))
    while cb.queue or any(s is not None for s in cb.slots):
        with span("bench/step"):
            cb.step()


def main():
    # one small layer: few ops a tick, so the fixture stays small
    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                      dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    cb = ContinuousBatcher(cfg, params, n_slots=2, cache_len=48,
                           paged=True, block_size=4, prefill_chunk=8)
    prompts = [jax.random.randint(jax.random.fold_in(
        jax.random.PRNGKey(5), k), (t,), 0, cfg.vocab_size).astype(jnp.int32)
        for k, t in enumerate((6, 20, 5))]
    # every program compiles here, so the trace holds none of them
    drain(cb, 0, prompts, lambda name: contextlib.nullcontext())
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    # no source paths in the fixture: no Python frames, no programs
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        drain(cb, 100, prompts, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(HERE, "fixtures",
                                  "cpu_serve_trace.xplane.pb"))
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
