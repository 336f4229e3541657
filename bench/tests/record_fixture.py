"""Records `fixtures/cpu_trace.xplane.pb`, the small CPU trace that
`test_trace_reduce.py` reads: a window span holding three steps, each a
jitted matrix product, with a 20 ms wait between steps.

    cd bench/tests && JAX_PLATFORMS=cpu python3 - < record_fixture.py

(read from standard input, so that no path of the recording machine
ends up in the trace's metadata)
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.getcwd()


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no source paths in the fixture
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(HERE, "fixtures", "cpu_trace.xplane.pb"))
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
