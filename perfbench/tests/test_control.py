"""The comparison has been shown to fail: each cell, with the timed path
broken underneath by each fault it can have (perfbench/faults.py), comes
out `correct: false` — and by the number that fault is for."""

import pytest

from perfbench import faults
from perfbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("root")))


CASES = [
    ("feed-cosmoflow", "altered_answer", {"every": 7},
     "samples_mismatched"),
    ("feed-cosmoflow", "stale_batch", {"every": 5}, "samples_mismatched"),
    ("restore-olmoe-chip", "altered_answer", {"every": 9},
     "tensors_mismatched"),
    ("restore-olmoe-chip", "missing_tensor", {}, "tensors_missing"),
    ("broadcast-olmoe-host4", "altered_answer", {"every": 9},
     "tensors_mismatched"),
    ("broadcast-olmoe-host4", "exchange_left_out", {},
     "tensors_misplaced"),
]


@pytest.mark.parametrize("workload,fault,kw,number", CASES)
def test_fault_is_seen(root, workload, fault, kw, number):
    with faults.FAULTS[fault](**kw):
        res = tiny.run(root, workload)
    value, limit = res["compared"][number]
    assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False
