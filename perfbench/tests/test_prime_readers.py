"""The reader of the client's primed opens: what it makes of a window's
counters, its silence on a program that keeps no `read.primed.files`
(the commit before PR 36) or that opened no file, and a restore cell
rehearsed on CPU devices, whose every restore names its manifest's
tensors to a new client before it opens one."""

import pytest

from perfbench import harness
from perfbench.tests import tiny
from perfbench.tests.test_pool_readers import _run      # client counters

NAME = "client.primed_open_share.restore"
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
    assert entry["moves"] == "restore_gbps" and entry["better"] == "higher"
    assert entry["unit"] == "share" and entry["workloads"] == CELLS
    read = cell.module("layer_metrics", NAME).read
    # one restore: 915 tensors found in the client, the manifest asked for
    assert read(_run(cell, {"read.primed.files": 915, "read.files": 916},
                     {"read.primed.files": 1830, "read.files": 1832})) \
        == pytest.approx(0.998908, abs=5e-7)
    assert read(_run(cell, {"read.primed.files": 0, "read.files": 0},
                     {"read.primed.files": 40, "read.files": 40})) == 1.0
    # a window whose files were all asked for one by one is a reading,
    # 0.0, not silence: the counter is there from an earlier restore
    assert read(_run(cell, {"read.primed.files": 915, "read.files": 916},
                     {"read.primed.files": 915, "read.files": 1000})) == 0.0
    # the parent commit: files counted, primed opens not — nothing to read
    assert read(_run(cell, {"read.files": 916},
                     {"read.files": 1832})) is None
    # no file opened in the window: no share of nothing
    assert read(_run(cell, {"read.primed.files": 915, "read.files": 916},
                     {"read.primed.files": 915, "read.files": 916})) is None
    assert read(_run(cell, {}, {})) is None


def test_the_other_cells_do_not_report_it(root):
    for name in ("reshard-olmoe-host4", "feed-cosmoflow",
                 "feed-cosmoflow-ufs", "feed-unet3d"):
        cell = harness.load_cell(root, name)
        assert NAME not in {m["name"] for m in cell.per_layer}


def test_a_rehearsed_restore_opens_its_tensors_primed(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, "restore-olmoe-chip", trace=True)
    assert res["correct"] is True, res["compared"]
    cell = harness.load_cell(root, "restore-olmoe-chip")
    gen = cell.module("generators", cell.config["generator"])
    tensors = len(gen.DataSet(2**31 + 11, cell.config))
    assert tensors > 8
    # every tensor's open served from the one batched answer, the
    # manifest's own not
    assert res["metrics"][NAME] == pytest.approx(tensors / (tensors + 1))
    assert res["metrics"]["client.zero_copy_share.restore"] == 1.0
    assert res["metrics"]["client.phase_ms.probe.restore"] >= 0
    assert "rpc.meta_wait_ms.restore" in res["metrics"]
