"""Each cell driven end to end at a tiny size on CPU devices: everything
of a run except the look for a chip. What the client reads back agrees
with the plain generators (`correct`), the result carries the cell's
metrics, and a cell that exists only as new files runs like the others."""

import json
import os

import pytest

from perfbench import harness
from perfbench.tests import tiny

CELLS = ("feed-cosmoflow", "restore-olmoe-chip", "broadcast-olmoe-host4")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("root")))


@pytest.mark.parametrize("workload", CELLS)
def test_timed_run(root, workload):
    res = tiny.run(root, workload)
    cell = harness.load_cell(root, workload)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == cell.chips
    assert all(lim == 0 for _, lim in res["compared"].values())
    line = json.loads(harness.result_line(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_every_layer_metric(root, workload, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, workload, trace=True)
    cell = harness.load_cell(root, workload)
    assert res["correct"] is True, res["compared"]
    # (a CPU device keeps no memory statistics: nothing to read there)
    assert set(res["metrics"]) == {
        m["name"] for m in cell.per_layer
        if not m["name"].startswith("device.peak_hbm_gb")}
    assert res["metrics"]["entry.window_compiles"] == 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    shares = [k for k in res["metrics"] if k.split(".")[1].endswith("share")]
    assert shares and all(0 <= res["metrics"][k] <= 1.05 for k in shares)


def test_a_cell_added_by_files_alone(root):
    """What a later PR does: a configuration, a traffic mix, a per-layer
    metric and a workloads entry — new files and new entries, no edit."""
    pb = os.path.join(root, "perfbench")
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(pb) for p in fs}
    tiny.edit_json(os.path.join(pb, "configs", "dlio-cosmoflow.json"),
                   lambda c: None)       # (rewritten unchanged by tiny_root)
    with open(os.path.join(pb, "configs", "dlio-cosmoflow.json")) as f:
        config = json.load(f)
    config.update(name="dlio-throwaway", record_length=30011,
                  record_length_stdev=0, record_length_resize=None,
                  num_files_train=12, batch_size=3, read_threads=2)
    with open(os.path.join(pb, "configs", "dlio-throwaway.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(pb, "traffic", "train-feed-cold.json"), "w") as f:
        json.dump({"name": "train-feed-cold", "driver": "feed",
                   "loop": "closed", "unit": "batch", "warm_batches": 1}, f)
    with open(os.path.join(pb, "layer_metrics", "feed.files_per_s.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return run.moved('fetched_bytes') / "
                "run.window.duration / 30027\n")

    def add(bench):
        bench["configs"].append({
            "name": "dlio-throwaway", "source": "a test",
            "file": "perfbench/configs/dlio-throwaway.json",
            "reduced": ["num_files_train"], "why": "a test"})
        bench["workloads"].append({
            "name": "feed-throwaway", "config": "dlio-throwaway",
            "traffic": "train-feed-cold", "chips": 1, "why": "a test"})
        for m in bench["end_to_end"]:
            if m["name"] == "into_hbm_gbps":
                m["workloads"].append("feed-throwaway")
        bench["per_layer"].append({
            "name": "feed.files_per_s", "unit": "1/s", "better": "higher",
            "source": "program_counter", "layer": "tpu/ingest.py",
            "moves": "into_hbm_gbps", "workloads": ["feed-throwaway"]})

    tiny.edit_json(os.path.join(root, "BENCHMARK.json"), add)
    res = tiny.run(root, "feed-throwaway")
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"into_hbm_gbps", "setup_s"}
    cell = harness.load_cell(root, "feed-throwaway")
    assert [m["name"] for m in cell.per_layer
            if "workloads" in m] == ["feed.files_per_s"]
    # batches of three samples, so the bytes of a unit are three records
    assert res["compared"]["samples_mismatched"] == (0, 0)
    unchanged = [p for p in before if p not in (
        "dlio-cosmoflow.json",)]
    assert all(os.path.getmtime(os.path.join(d, p)) == before[p]
               for d, _, fs in os.walk(pb) for p in fs if p in unchanged)
