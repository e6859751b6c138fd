"""The reader of the client's fetch hand-offs: what it makes of a
window's counters, its silence on a program that keeps no
`read.fetch.hops` (an older one) or that opened no file, and
a restore cell rehearsed on CPU devices, whose primed readers hand their
blocks to batch threads many at a time."""

import pytest

from perfbench import harness
from perfbench.tests import tiny
from perfbench.tests.test_pool_readers import _run      # client counters

NAME = "client.fetch_hops_per_file.restore"
CELLS = ["restore-olmoe-chip", "broadcast-olmoe-host4"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("root")))


@pytest.mark.parametrize("name", CELLS)
def test_reader_arithmetic_and_silence(root, name):
    cell = harness.load_cell(root, name)
    entry = next(m for m in cell.per_layer if m["name"] == NAME)
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "client/ read ladder"
    assert entry["moves"] == "restore_gbps" and entry["better"] == "lower"
    assert entry["unit"] == "1/file" and entry["workloads"] == CELLS
    read = cell.module("layer_metrics", NAME).read
    # one restore: 921 blocks a thread hop each, 916 files
    assert read(_run(cell, {"read.fetch.hops": 921, "read.files": 916},
                     {"read.fetch.hops": 1842, "read.files": 1832})) \
        == pytest.approx(1.005459, abs=5e-7)
    # the same restore in batches of 32
    assert read(_run(cell, {"read.fetch.hops": 0, "read.files": 0},
                     {"read.fetch.hops": 29, "read.files": 916})) \
        == pytest.approx(29 / 916)
    # a window whose files were all served from maps already made is a
    # reading, 0.0, not silence: the counter is there from before
    assert read(_run(cell, {"read.fetch.hops": 29, "read.files": 916},
                     {"read.fetch.hops": 29, "read.files": 1000})) == 0.0
    # the parent commit: files counted, hand-offs not — nothing to read
    assert read(_run(cell, {"read.files": 916},
                     {"read.files": 1832})) is None
    # no file opened in the window: no rate of nothing
    assert read(_run(cell, {"read.fetch.hops": 29, "read.files": 916},
                     {"read.fetch.hops": 30, "read.files": 916})) is None
    assert read(_run(cell, {}, {})) is None


def test_the_other_cells_do_not_report_it(root):
    for name in ("reshard-olmoe-host4", "feed-cosmoflow",
                 "feed-cosmoflow-ufs", "feed-unet3d"):
        cell = harness.load_cell(root, name)
        assert NAME not in {m["name"] for m in cell.per_layer}


def test_a_rehearsed_restore_hands_off_in_batches(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, "restore-olmoe-chip", trace=True)
    assert res["correct"] is True, res["compared"]
    cell = harness.load_cell(root, "restore-olmoe-chip")
    gen = cell.module("generators", cell.config["generator"])
    tensors = len(gen.DataSet(2**31 + 11, cell.config))
    assert tensors > 8
    # the manifest's block is a hop of its own (it is not primed); the
    # tensors' blocks go many a hop, so fewer hops than files
    hops = res["metrics"][NAME]
    assert 0 < hops < 1
    assert res["metrics"]["client.primed_open_share.restore"] \
        == pytest.approx(tensors / (tensors + 1))
    assert res["metrics"]["client.zero_copy_share.restore"] == 1.0
