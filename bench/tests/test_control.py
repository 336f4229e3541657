"""The control of the `correct` check: the reference computed with
float8 weights, one precision step below the cells' bfloat16, put in
the program's place on the same prompts and served tokens. At a small
size on the CPU the harness's own `correct` predicate, at the limit the
program's sound runs pass, must come out false for it, and its widest
gap must read above every sound run."""

import pytest

import smoke


@pytest.mark.parametrize("name", smoke.CELLS)
def test_float8_control_fails_where_the_program_passes(name):
    program, control = [], []
    for seed in (3, 2**31 + 5):
        r = smoke.run(smoke.cell(name), seed, control=True)
        assert r["correct"], r["checks"]
        assert r["control_correct"] is False, r["control_checks"]
        program.append(r["checks"]["max_logit_gap"]["value"])
        control.append(r["control_checks"]["max_logit_gap"]["value"])
    assert min(control) > smoke.settings(name)["max_logit_gap"]
    assert min(control) > max(program)
