"""The reader of the client's connection pools: what it makes of a
window's counters, its silence on a program that keeps no `rpc.dials`
(the commit before PR 34) or that opened no file, and a restore cell
rehearsed on CPU devices, whose every restore meets two cold pools of
four with all of its opens at once."""

import pytest

from perfbench import harness
from perfbench.tests import tiny

NAME = "rpc.dials_per_file.restore"
CELLS = ["restore-olmoe-chip", "broadcast-olmoe-host4"]


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
                       before=dict(empty, client=before),
                       after=dict(empty, client=after, fetched_bytes=10),
                       compile_setup={}, compile_window={},
                       memory_peak_bytes=0, trace=None, notes={})


@pytest.mark.parametrize("name", CELLS)
def test_reader_arithmetic_and_silence(root, name):
    cell = harness.load_cell(root, name)
    entry = next(m for m in cell.per_layer if m["name"] == NAME)
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "rpc/ transport and the client's loop"
    assert entry["moves"] == "restore_gbps" and entry["better"] == "lower"
    assert entry["workloads"] == CELLS
    read = cell.module("layer_metrics", NAME).read
    # one restore: two cold pools of four, 915 tensors and the manifest
    assert read(_run(cell, {"rpc.dials": 8, "read.files": 916},
                     {"rpc.dials": 16, "read.files": 1832})) \
        == pytest.approx(0.00873, abs=5e-6)
    # every concurrent caller dials for itself, to the master and the worker
    assert read(_run(cell, {"rpc.dials": 0, "read.files": 0},
                     {"rpc.dials": 1832, "read.files": 916})) == 2.0
    # a window on warm pools is a reading, 0.0, not silence
    assert read(_run(cell, {"rpc.dials": 8, "read.files": 10},
                     {"rpc.dials": 8, "read.files": 30})) == 0.0
    # the parent commit: files counted, dials not — nothing to read
    assert read(_run(cell, {"read.files": 916},
                     {"read.files": 1832})) is None
    # no file opened in the window: no share of nothing
    assert read(_run(cell, {"rpc.dials": 8, "read.files": 916},
                     {"rpc.dials": 9, "read.files": 916})) is None
    assert read(_run(cell, {}, {})) is None


def test_a_rehearsed_restore_dials_at_most_eight_a_client(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, "restore-olmoe-chip", trace=True)
    assert res["correct"] is True, res["compared"]
    cell = harness.load_cell(root, "restore-olmoe-chip")
    gen = cell.module("generators", cell.config["generator"])
    opens = len(gen.DataSet(2**31 + 11, cell.config)) + 1    # the manifest
    assert opens > 8
    # a restore's new client: four dials to the master and four to the
    # worker at most, whatever the burst; at least one to each
    per_file = res["metrics"][NAME]
    assert 2 / opens - 1e-9 <= per_file <= 8 / opens + 1e-9
    assert res["metrics"]["client.zero_copy_share.restore"] == 1.0
    assert "rpc.meta_wait_ms.restore" in res["metrics"]
