"""A run with the timed path broken underneath must come out not
correct. Drives the whole harness on the CPU at a small size, without
its look for a chip, with the oracle kernels, at the smoke limits of
`smoke.py`; both faults read above 0.3 there."""

import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from bench import harness

CELLS = smoke.CELLS


def token_altered(batcher):
    """Every decode step's token is pushed to id 1 where it is made."""
    step = batcher._decode_paged

    def altered(*args, **kw):
        out = step(*args, **kw)
        # on the host, so that the fault compiles nothing in the window
        logits = np.array(out[0])
        logits[..., 1] += 1e4
        return (jnp.asarray(logits),) + tuple(out[1:])

    batcher._decode_paged = altered


def state_unchanged(batcher):
    """Every decode step hands back the page pools it was given."""
    step = batcher._decode_paged

    def unchanged(*args, **kw):
        out = step(*args, **kw)
        return (out[0], args[2], args[3])

    batcher._decode_paged = unchanged


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = smoke.run(smoke.cell(name), 2**32 + 11)
    assert r["correct"], r["checks"]
    assert r["tokens_compared"] >= 30
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [token_altered, state_unchanged])
@pytest.mark.parametrize("name", CELLS)
def test_broken_step_is_not_correct(name, fault):
    r = smoke.run(smoke.cell(name), 2**32 + 11, sabotage=fault)
    assert not r["correct"]
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_compile_in_the_window_stops_the_run():
    """A program the window calls that set-up did not warm: the run
    stops rather than time the compiler. (A cell of set-up sessions
    alone: an open-loop cell's ramp, which runs before the window, would
    compile it.)"""

    def new_program(batcher):
        step = batcher._decode_paged

        def recompiled(*args, **kw):
            out = step(*args, **kw)
            return (jnp.roll(out[0], 1, axis=-1),) + tuple(out[1:])

        batcher._decode_paged = recompiled

    with pytest.raises(harness.WindowCompiled):
        smoke.run(smoke.cell(smoke.first_cell(open_loop=False)), 17,
                  seconds=1.0, sabotage=new_program)
