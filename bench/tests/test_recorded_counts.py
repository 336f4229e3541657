"""The harness against numbers recorded before its architecture code
moved into `bench/arch/` (`fixtures/counts.json`, `record_counts.py`):
the same seed draws byte-identical weights at the smoke size, the
program is given the same config and parameter shapes, and every
`bench/shapes.py` count is what it was."""

import json
import os

import pytest

import record_counts

with open(os.path.join(record_counts.HERE, "fixtures", "counts.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_weights_config_and_counts_are_as_recorded(name):
    got = json.loads(json.dumps(record_counts.record(name)))
    want = RECORDED[name]
    assert sorted(got) == sorted(want)
    for part in want:
        assert got[part] == want[part], part
