"""The cell `feed-cosmoflow-ufs` driven end to end at a tiny size on CPU
devices: a set of 24 plain files behind a mount with auto_cache, under a
MEM tier that holds half of them. The run is right whichever level served
a sample, its new per-layer metrics read what the program counts, and the
two faults a feed can have come out `correct: false` here too."""

import os

import pytest

from perfbench import faults, harness
from perfbench.tests import tiny

CELL = "feed-cosmoflow-ufs"
NEW = ("client.ufs_read_share.feed", "client.phase_ms.ufs.feed",
       "worker.load_gbps.feed", "worker.load_ms.feed",
       "worker.evicted_per_s.feed")
RECORD = 70001


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.tiny_root(str(tmp_path_factory.mktemp("root")))
    conf = os.path.join(dest, "perfbench", "configs",
                        "dlio-cosmoflow-ufs.json")

    def cut(c):
        c["cluster"]["tier_bytes"] = 12 * RECORD
        c["cluster"]["block_size"] = 128 << 10
        c.update(record_length=RECORD, record_length_stdev=1800,
                 record_length_resize=51200, num_files_train=24)

    tiny.edit_json(conf, cut)
    tiny.edit_json(os.path.join(dest, "perfbench", "traffic",
                                "train-feed-ufs.json"),
                   lambda t: t.update(warm_batches=48))
    return dest


def test_timed_run(root):
    res = tiny.run(root, CELL, seconds=1.5)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 24 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    compared = dict(res["compared"])
    held, files = compared.pop("cached_compared")
    assert 3 <= held <= files == 24     # a quarter of the tier's 12
    assert set(compared) == {
        "samples_mismatched", "samples_undecodable", "reads_failed",
        "cached_mismatched", "cached_short", "window_compiles"}
    assert all(v == 0 and lim == 0 for v, lim in compared.values())


def test_traced_run_reads_the_miss_the_load_and_the_hit(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, seconds=2.5, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    assert set(m) == {x["name"] for x in cell.per_layer
                      if not x["name"].startswith("device.peak_hbm_gb")}
    assert set(NEW) <= set(m)
    assert 0 < m["client.ufs_read_share.feed"] < 1
    assert m["client.phase_ms.ufs.feed"] > 0
    assert m["worker.load_gbps.feed"] > 0 and m["worker.load_ms.feed"] > 0
    assert m["worker.evicted_per_s.feed"] > 0
    assert m["worker.shm_grants_per_s.feed"] > 0          # hits were served
    assert m["client.zero_copy_share.feed"] > 0
    assert m["entry.window_compiles"] == 0


def test_the_new_readers_are_silent_elsewhere(root):
    """In a cell of a configuration without a mount, and for a program
    that keeps none of the counters, each new reader finds nothing."""
    other = harness.load_cell(root, "feed-cosmoflow")
    assert not {m["name"] for m in other.per_layer} & set(NEW)
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
    for name in NEW:
        assert cell.module("layer_metrics", name).read(run) is None, name


@pytest.mark.parametrize("fault,kw", [("altered_answer", {"every": 3}),
                                      ("stale_batch", {"every": 5})])
def test_fault_is_seen(root, fault, kw):
    with faults.FAULTS[fault](**kw):
        res = tiny.run(root, CELL, seconds=2.5)
    value, limit = res["compared"]["samples_mismatched"]
    assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False


def test_a_program_that_caches_nothing_is_not_correct(root, monkeypatch):
    """The guarantee on cached copies is not met by having none: a client
    that serves every miss and never asks for a load reads every sample
    right and still ends `correct: false`, by `cached_short` alone."""
    from curvine_tpu.client import CurvineClient
    monkeypatch.setattr(CurvineClient, "_submit_load",
                        lambda self, path: None)
    res = tiny.run(root, CELL, seconds=1.5)
    wrong = {k: v for k, (v, lim) in res["compared"].items() if v > lim}
    assert wrong == {"cached_short": 3}, res["compared"]
    assert res["compared"]["cached_compared"] == (0, 24)
    assert res["correct"] is False and res["failed"] == 0
