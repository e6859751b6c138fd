"""The cell `feed-unet3d` driven end to end at a tiny size on CPU devices:
35 npz files of 1 to 5 blocks (blocks of 128 KiB, everything of the
published shape divided by 512), batches of seven. The run is right timed
and traced, reports exactly its metrics, serves the files that span
blocks as views and copies nothing, and the two faults a feed can have
come out `correct: false` — the altered byte by the member's CRC-32
alone, since it lies far beyond what is handed on."""

import os

import numpy as np
import pytest

from perfbench import faults, harness
from perfbench.tests import tiny

CELL = "feed-unet3d"
NEW = ("client.fetched_gbps.feed", "client.span_view_share.feed",
       "client.phase_ms.map.feed", "client.phase_ms.copy.feed")
UFS_ONLY = ("client.ufs_read_share.feed", "client.phase_ms.ufs.feed",
            "worker.load_gbps.feed", "worker.load_ms.feed",
            "worker.evicted_per_s.feed")
SCALE = 512
BLOCK = (64 << 20) // SCALE


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.tiny_root(str(tmp_path_factory.mktemp("root")))

    def cut(c):
        c["cluster"]["tier_bytes"] = 256 << 20
        c["cluster"]["block_size"] = BLOCK
        c.update(record_length=146600628 // SCALE,
                 record_length_stdev=68341808 // SCALE,
                 record_length_resize=2097152 // SCALE)

    tiny.edit_json(os.path.join(dest, "perfbench", "configs",
                                "dlio-unet3d.json"), cut)
    return dest


def test_the_tiny_set_spans_one_to_five_blocks(root):
    cell = harness.load_cell(root, CELL)
    ds = cell.module("generators", "dlio_npz").DataSet(3, cell.config)
    blocks = [-(-ds.file_bytes(i) // BLOCK) for i in range(ds.files)]
    assert ds.files == 35 and sorted(set(blocks)) == [1, 2, 3, 4, 5]
    assert cell.traffic["warm_batches"] == 10 and cell.chips == 1
    assert cell.traffic["driver"] == "feed"


def test_timed_run(root):
    res = tiny.run(root, CELL, seconds=1.0)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 5 and res["failed"] == 0
    assert set(res["metrics"]) == {"into_hbm_gbps", "setup_s"} \
        == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res["metrics"].values())
    assert set(res["compared"]) == {"samples_mismatched",
                                    "samples_undecodable",
                                    "window_compiles"}
    assert all(v == 0 and lim == 0 for v, lim in res["compared"].values())


def test_traced_run_reads_the_span_view_and_no_copy(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, seconds=1.0, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    assert set(m) == {x["name"] for x in cell.per_layer
                      if not x["name"].startswith("device.peak_hbm_gb")}
    assert set(NEW) <= set(m) and not set(UFS_ONLY) & set(m)
    assert m["client.fetched_gbps.feed"] > 0
    assert 0.5 < m["client.span_view_share.feed"] < 1      # one file of
    assert m["client.zero_copy_share.feed"] == 1.0         # one block
    assert m["client.phase_ms.copy.feed"] == 0
    assert m["client.phase_ms.map.feed"] > 0
    assert m["worker.socket_gbps.feed"] == 0
    assert m["worker.shm_export_hit_share.feed"] > 0.9     # two warm epochs
    assert m["entry.window_compiles"] == 0
    # the cache's side is ~70 times what is handed on
    sample = cell.config["record_length_resize"]
    assert m["client.fetched_gbps.feed"] > 30 * (
        res["attempted"] * 7 * sample / res["device"]["window_s"] / 1e9)


def test_the_new_readers_are_silent_elsewhere(root):
    """In the other feed cells the four are not listed, and for a program
    that keeps none of the counters each reader but the driver's own
    finds nothing."""
    for other in ("feed-cosmoflow", "feed-cosmoflow-ufs"):
        cell = harness.load_cell(root, other)
        assert not {m["name"] for m in cell.per_layer} & set(NEW)
    cell = harness.load_cell(root, CELL)
    window = harness.Window(1.0, clock=iter((0.0, 1.0)).__next__)
    window.complete(0)
    window.complete(10)
    empty = {"client": {}, "worker": {}, "stages": {}, "fetched_bytes": 0,
             "delivered": 0}
    run = harness.Run(cell=cell, window=window, setup_s=0.0,
                      spans=harness.Spans(), before=empty,
                      after=dict(empty, fetched_bytes=10), compile_setup={},
                      compile_window={}, memory_peak_bytes=0, trace=None,
                      notes={})
    read = {n: cell.module("layer_metrics", n).read(run) for n in NEW}
    assert read.pop("client.fetched_gbps.feed") == pytest.approx(1e-8)
    assert set(read.values()) == {None}
    run.after = dict(empty)                    # nothing fetched at all
    assert cell.module("layer_metrics",
                       "client.fetched_gbps.feed").read(run) is None


@pytest.mark.parametrize("fault,kw,number", [
    ("altered_answer", {"every": 9}, "samples_undecodable"),
    ("stale_batch", {"every": 5}, "samples_mismatched")])
def test_fault_is_seen(root, fault, kw, number):
    with faults.FAULTS[fault](**kw):
        res = tiny.run(root, CELL, seconds=1.0)
    value, limit = res["compared"][number]
    assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False
    if fault == "altered_answer":
        # the flipped byte lies in the middle of the file: what reached
        # the device is still right, only decode's CRC-32 saw it
        assert res["compared"]["samples_mismatched"] == (0, 0)
        assert res["failed"] == value
