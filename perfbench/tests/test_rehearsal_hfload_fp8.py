"""The cell `hfload-dsv3-fp8-chip` driven end to end at a tiny size on a
CPU device: DeepSeek-V3's layer pattern (three dense layers, four MoE
layers, the q-LoRA branch) at small, ragged widths, 8 experts in the
files of which rank 1 holds 2, blocks of 64 KiB so that ranges share
blocks and straddle them; the expansion kernel in interpret mode. The
run is right timed and traced and reports exactly its metrics; the
comparison is exact and sees a weight expanded without its scales and
fp8 handed back raw; a program whose loader takes no `dequant` fails at
the driver's import, before any data is written."""

import os
import sys

import pytest

from perfbench import faults, harness
from perfbench.tests import tiny

CELL = "hfload-dsv3-fp8-chip"
CONFIG = "hf-deepseek-v3-fp8"
NEW = ("fp8_block_dequant_roofline", "broadcast.dequant_ms",
       "broadcast.dequant_byte_share")
BLOCK = 64 << 10


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.tiny_root(str(tmp_path_factory.mktemp("root")))

    def cut(c):
        c["cluster"]["tier_bytes"] = 256 << 20
        c["cluster"]["block_size"] = BLOCK
        c.update(hidden_size=200, num_attention_heads=2, q_lora_rank=96,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 kv_lora_rank=32, intermediate_size=160,
                 moe_intermediate_size=48, n_routed_experts=16,
                 experts_in_files=8, experts_held=2, vocab_size=300,
                 max_shard_size=400_000)

    tiny.edit_json(os.path.join(dest, "perfbench", "configs",
                                CONFIG + ".json"), cut)
    return dest


def dataset(root, seed=3):
    cell = harness.load_cell(root, CELL)
    gen = cell.module("generators", cell.config["generator"])
    return gen, gen.DataSet(seed, cell.config)


def test_the_tiny_set_keeps_the_shape(root):
    gen, ds = dataset(root)
    fp8 = [n for n in ds.share if ds.dtypes[ds.index_of[n]] == "F8_E4M3"]
    # 3 dense layers of 5 + 3 linears, 4 MoE layers of 5 + 3 shared +
    # 2 experts x 3 linears
    assert len(fp8) == 3 * 8 + 4 * 14
    assert len(ds.share) == len(fp8) + 1 + 7 * 4 + 4 * 2
    assert len(ds.share_ranges) == len(ds.share) + len(fp8)
    assert len(ds.shards) >= 2
    assert {gen.hf.expert_of(n) for n in ds.share} == {None, 2, 3}


def test_timed_run(root):
    res = tiny.run(root, CELL)
    cell = harness.load_cell(root, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "restore_hf_fp8"
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"restore_gbps", "setup_s"} \
        == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res["metrics"].values())
    assert set(res["compared"]) == {
        "tensors_mismatched", "tensors_missing", "tensors_misplaced",
        "tensors_foreign", "scales_returned", "window_compiles"}
    assert all(v == 0 and lim == 0 for v, lim in res["compared"].values())


def test_traced_run_reports_the_cells_metrics(root, monkeypatch):
    from perfbench import peaks, trace_reduce
    tiny.fake_reduction(monkeypatch)
    faked = trace_reduce.reduce_dir

    def with_kernel(trace_dir, chips, span_names):
        r = faked(trace_dir, chips, span_names)
        r.device_ops = [["fp8_block_dequant.1", 0.002],
                        ["fp8_block_dequant.3", 0.002]] + r.device_ops
        return r

    monkeypatch.setattr(trace_reduce, "reduce_dir", with_kernel)
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"hbm_bytes_per_s": 1e9})
    res = tiny.run(root, CELL, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    # (a CPU device keeps no memory statistics: nothing to read there)
    assert set(m) == {x["name"] for x in cell.per_layer
                      if not x["name"].startswith("device.peak_hbm_gb")}
    assert set(NEW) <= set(m) and len(cell.per_layer) == 26
    gen, ds = dataset(root)
    units = res["attempted"]
    assert m["fp8_block_dequant_roofline"] == pytest.approx(
        100 * gen.kernel_bytes(cell.config) * units / (0.004 * 1e9))
    assert m["broadcast.dequant_ms"] > 0
    fp8 = [n for n in ds.share if ds.dtypes[ds.index_of[n]] == "F8_E4M3"]
    held = sum(ds.nbytes[ds.index_of[n]] + ds.nbytes[
        ds.index_of[n + gen.SCALE]] for n in fp8)
    assert m["broadcast.dequant_byte_share"] == pytest.approx(
        held / ds.share_bytes)
    assert m["client.fetches_per_block.restore"] == 1.0
    assert m["client.zero_copy_share.restore"] == 1.0
    assert m["entry.window_compiles"] == 0


def test_the_roofline_is_silent_without_the_kernel_in_the_trace(
        root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, trace=True)
    assert res["correct"] is True, res["compared"]
    assert "fp8_block_dequant_roofline" not in res["metrics"]
    assert res["metrics"]["broadcast.dequant_ms"] > 0


def test_the_new_readers_are_silent_on_a_program_without_the_counters(
        root):
    cell = harness.load_cell(root, CELL)
    window = harness.Window(1.0, clock=iter((0.0, 1.0)).__next__)
    window.complete(0)
    window.complete(10)
    older = {"client": {"ckpt.bytes": 0}, "worker": {}, "stages": {},
             "fetched_bytes": 0, "delivered": 0}
    after = dict(older, client={"ckpt.bytes": 100}, fetched_bytes=10)
    run = harness.Run(cell=cell, window=window, setup_s=0.0,
                      spans=harness.Spans(), before=older, after=after,
                      compile_setup={}, compile_window={},
                      memory_peak_bytes=0, trace=None, notes={})
    assert {n: cell.module("layer_metrics", n).read(run)
            for n in NEW} == dict.fromkeys(NEW)
    run.after = dict(after, client={
        "ckpt.bytes": 100, "ckpt.dequant.bytes_in": 70,
        "ckpt.dequant.s": 0.5, "ckpt.dequant.n": 250})
    assert [cell.module("layer_metrics", n).read(run) for n in NEW[1:]] \
        == [2.0, 0.7]


def test_a_program_without_dequant_fails_at_the_drivers_import(
        root, monkeypatch):
    """The commit before the expansion: the driver cannot be loaded, the
    run raises at once — no data written, no result."""
    from curvine_tpu.tpu import broadcast

    async def older(client, root, device, select=None):
        raise AssertionError("not to be called")

    monkeypatch.setattr(broadcast, "load_safetensors_to_device", older)
    path = os.path.join(root, "perfbench", "drivers", "restore_hf_fp8.py")
    for name in [k for k in sys.modules
                 if k.endswith("drivers_restore_hf_fp8_py")]:
        monkeypatch.delitem(sys.modules, name)
    with pytest.raises(ImportError):
        harness.load_module(path)
    with pytest.raises(ImportError):
        tiny.run(root, CELL)


@pytest.mark.parametrize("fault,numbers", [
    ("altered_answer", ("tensors_mismatched",)),
    ("scale_dropped", ("tensors_mismatched",)),
    ("raw_fp8", ("tensors_mismatched", "scales_returned")),
    ("every_rank", ("tensors_foreign",))])
def test_fault_is_seen(root, monkeypatch, fault, numbers):
    from curvine_tpu.tpu import broadcast
    if fault == "scale_dropped":
        # one weight a load expanded as if its scales were all 1
        inner = broadcast._expand
        seen = set()

        def unscaled(client, name, wq, scales, block, dtype):
            if id(client) not in seen:
                seen.add(id(client))
                scales = scales * 0 + 1
            return inner(client, name, wq, scales, block, dtype)

        monkeypatch.setattr(broadcast, "_expand", unscaled)
    elif fault in ("raw_fp8", "every_rank"):
        inner_load = broadcast.load_safetensors

        async def broken(client, root, placer=None, select=None,
                         dequant=None):
            if fault == "raw_fp8":      # the expansion skipped
                return await inner_load(client, root, placer, select)
            # the selection dropped: every expert of every rank
            return await inner_load(client, root, placer, None, dequant)

        monkeypatch.setattr(broadcast, "load_safetensors", broken)
    if fault == "altered_answer":
        with faults.FAULTS[fault](every=9):
            res = tiny.run(root, CELL)
    else:
        res = tiny.run(root, CELL)
    for number in numbers:
        value, limit = res["compared"][number]
        assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False


def test_the_reference_folds_in_pieces_as_the_fold_does_whole(root):
    import numpy as np
    from perfbench import fold
    cell = harness.load_cell(root, CELL)
    driver = cell.module("drivers", cell.traffic["driver"])
    rng = np.random.default_rng(5)
    a = rng.integers(0, 1 << 16, (700, 9001), dtype=np.uint16)
    f = rng.standard_normal(5000).astype(np.float32)
    assert (driver.fold_pieces([a[:128], a[128:256], a[256:]])
            == fold.host_fold(a)).all()
    assert (driver.fold_pieces([f]) == fold.host_fold(f)).all()
