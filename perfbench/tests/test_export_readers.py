"""The two readers of the worker's export table: what they make of a
window's counters, their silence on a program that keeps no
`shm.exports` (the commit before PR 32) or that granted nothing, and a
restore cell rehearsed on CPU devices, whose warming restore fills the
table so that every grant of the window is a dup."""

import pytest

from perfbench import harness
from perfbench.tests import tiny

READERS = {"worker.shm_export_hit_share.restore": "restore-olmoe-chip",
           "worker.shm_export_hit_share.feed": "feed-cosmoflow"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("root")))


def _run(cell, before: dict, after: dict):
    window = harness.Window(1.0, clock=iter((0.0, 1.0)).__next__)
    window.complete(0)
    window.complete(10)
    empty = {"client": {}, "worker": {}, "stages": {}, "fetched_bytes": 0,
             "delivered": 0}
    return harness.Run(cell=cell, window=window, setup_s=0.0,
                       spans=harness.Spans(),
                       before=dict(empty, worker=before),
                       after=dict(empty, worker=after, fetched_bytes=10),
                       compile_setup={}, compile_window={},
                       memory_peak_bytes=0, trace=None, notes={})


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_arithmetic_and_silence(root, name):
    cell = harness.load_cell(root, READERS[name])
    entry = next(m for m in cell.per_layer if m["name"] == name)
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "worker/ tier store"
    assert READERS[name] in entry["workloads"]
    read = cell.module("layer_metrics", name).read
    # every grant of the window a dup of a copy made before it
    assert read(_run(cell, {"shm.grants": 915, "shm.exports": 915},
                     {"shm.grants": 4575, "shm.exports": 915})) == 1.0
    # a shuffled epoch through 128 entries: 128 of 1,536 grants hit
    assert read(_run(cell, {"shm.grants": 0, "shm.exports": 0},
                     {"shm.grants": 1536, "shm.exports": 1408})) \
        == pytest.approx(128 / 1536)
    # every grant a copy is a reading, 0.0, not silence
    assert read(_run(cell, {"shm.grants": 10, "shm.exports": 10},
                     {"shm.grants": 30, "shm.exports": 30})) == 0.0
    # the parent commit: grants counted, copies not — nothing to read
    assert read(_run(cell, {"shm.grants": 10},
                     {"shm.grants": 500})) is None
    # nothing granted in the window: no share of nothing
    assert read(_run(cell, {"shm.grants": 7, "shm.exports": 7},
                     {"shm.grants": 7, "shm.exports": 7})) is None
    assert read(_run(cell, {}, {})) is None


def test_a_rehearsed_restore_window_copies_nothing(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, "restore-olmoe-chip", trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["worker.shm_export_hit_share.restore"] == 1.0
    assert res["metrics"]["client.zero_copy_share.restore"] == 1.0
    assert "worker.shm_export_hit_share.feed" not in res["metrics"]
