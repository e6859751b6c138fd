"""The cell `hfload-dsv2lite-chip` driven end to end at a tiny size on a
CPU device: DeepSeek-V2-Lite's layer pattern (one dense layer, four MoE
layers) at small widths, 8 experts a layer of which rank 1 holds 2,
blocks of 64 KiB so that tensors share blocks, straddle two and span
several. The run is right timed and traced and reports exactly its
metrics, each block is fetched once; the comparison is exact and sees
a changed byte and a tensor of another rank; a program without the
loader fails at the driver's import, before any data is written."""

import os
import sys

import pytest

from perfbench import faults, harness
from perfbench.tests import tiny

CELL = "hfload-dsv2lite-chip"
CONFIG = "hf-deepseek-v2-lite"
NEW = ("client.fetches_per_block.restore", "broadcast.index_ms")
BLOCK = 64 << 10


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.tiny_root(str(tmp_path_factory.mktemp("root")))

    def cut(c):
        c["cluster"]["tier_bytes"] = 256 << 20
        c["cluster"]["block_size"] = BLOCK
        c.update(hidden_size=64, num_attention_heads=2, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                 intermediate_size=160, moe_intermediate_size=48,
                 n_routed_experts=8, vocab_size=1000, experts_held=2,
                 max_shard_size=600_000)

    tiny.edit_json(os.path.join(dest, "perfbench", "configs",
                                CONFIG + ".json"), cut)
    return dest


def dataset(root, seed=3):
    cell = harness.load_cell(root, CELL)
    gen = cell.module("generators", cell.config["generator"])
    return gen.DataSet(seed, cell.config)


def test_the_tiny_set_keeps_the_shape(root):
    ds = dataset(root)
    assert len(ds.shards) == 2 and len(ds.specs) == 3 + 10 + 4 * (7 + 24 + 4)
    assert len(ds.share) == 3 + 10 + 4 * (7 + 6 + 4)
    ends = {}
    for k, g in enumerate(ds.shard_tensors):
        at = len(ds.header(k))
        for i in g:
            ends[ds.specs[i][0]] = (at // BLOCK, (at + 2 * ds.sizes[i] - 1)
                                    // BLOCK)
            at += 2 * ds.sizes[i]
    spans = [hi - lo for lo, hi in (ends[n] for n in ds.share)]
    assert spans.count(0) > 20 and 1 in spans and max(spans) >= 2


def test_timed_run(root):
    res = tiny.run(root, CELL)
    cell = harness.load_cell(root, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "restore_hf"
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"restore_gbps", "setup_s"} \
        == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res["metrics"].values())
    assert set(res["compared"]) == {
        "tensors_mismatched", "tensors_missing", "tensors_misplaced",
        "tensors_foreign", "window_compiles"}
    assert all(v == 0 and lim == 0 for v, lim in res["compared"].values())


def test_traced_run_reports_the_cells_metrics(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    # (a CPU device keeps no memory statistics: nothing to read there)
    assert set(m) == {x["name"] for x in cell.per_layer
                      if not x["name"].startswith("device.peak_hbm_gb")}
    assert set(NEW) <= set(m) and len(cell.per_layer) == 23
    assert m["client.fetches_per_block.restore"] == 1.0
    assert m["broadcast.index_ms"] > 0
    assert m["client.zero_copy_share.restore"] == 1.0
    assert 0 < m["client.span_view_share.restore"] < 1
    assert m["client.phase_ms.copy.restore"] == 0
    assert m["worker.socket_gbps.restore"] == 0
    assert m["entry.window_compiles"] == 0
    assert m["broadcast.place_ms"] > 0


def test_the_restore_cell_reads_one_fetch_a_block(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, "restore-olmoe-chip", trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["client.fetches_per_block.restore"] == 1.0
    assert "broadcast.index_ms" not in res["metrics"]


def test_the_new_readers_are_silent_on_a_program_without_the_counters(
        root):
    cell = harness.load_cell(root, CELL)
    window = harness.Window(1.0, clock=iter((0.0, 1.0)).__next__)
    window.complete(0)
    window.complete(10)
    older = {"client": {"read.files": 3, "ckpt.place.s": 0.0},
             "worker": {}, "stages": {}, "fetched_bytes": 0, "delivered": 0}
    after = dict(older, client={"read.files": 6, "ckpt.place.s": 0.5},
                 fetched_bytes=10)
    run = harness.Run(cell=cell, window=window, setup_s=0.0,
                      spans=harness.Spans(), before=older, after=after,
                      compile_setup={}, compile_window={},
                      memory_peak_bytes=0, trace=None, notes={})
    assert {n: cell.module("layer_metrics", n).read(run)
            for n in NEW} == dict.fromkeys(NEW)
    run.after = dict(after, client={
        "read.block_fetches": 94, "read.blocks_mapped": 47,
        "ckpt.index.s": 0.02, "ckpt.headers.s": 0.03,
        "ckpt.index.n": 2})
    assert [cell.module("layer_metrics", n).read(run) for n in NEW] \
        == [2.0, 25.0]


def test_a_program_without_the_loader_fails_at_the_drivers_import(
        root, monkeypatch):
    """The commit before the loader: the driver cannot be loaded, the
    run raises at once — no data written, no result."""
    from curvine_tpu.tpu import broadcast
    monkeypatch.delattr(broadcast, "load_safetensors_to_device")
    path = os.path.join(root, "perfbench", "drivers", "restore_hf.py")
    for name in [k for k in sys.modules
                 if k.endswith("drivers_restore_hf_py")]:
        monkeypatch.delitem(sys.modules, name)
    with pytest.raises(ImportError):
        harness.load_module(path)
    with pytest.raises(ImportError):
        tiny.run(root, CELL)


@pytest.mark.parametrize("fault,number", [
    ("altered_answer", "tensors_mismatched"),
    ("every_rank", "tensors_foreign")])
def test_fault_is_seen(root, monkeypatch, fault, number):
    if fault == "every_rank":
        # the selection dropped: every expert of every rank comes back
        from curvine_tpu.tpu import broadcast
        inner = broadcast.load_safetensors

        async def everything(client, root, placer=None, select=None):
            return await inner(client, root, placer)

        monkeypatch.setattr(broadcast, "load_safetensors", everything)
        res = tiny.run(root, CELL)
    else:
        with faults.FAULTS[fault](every=9):
            res = tiny.run(root, CELL)
    value, limit = res["compared"][number]
    assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False
