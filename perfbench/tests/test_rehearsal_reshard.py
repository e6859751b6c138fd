"""The cell `reshard-olmoe-host4` driven end to end at a tiny size on four
CPU devices: every width of the configuration divided by 32, 8 experts a
layer, blocks of 8 KiB so that a stacked expert tensor is exactly four
and a chip's share of it one. The run is right timed and traced and
reports exactly its metrics; the comparison is of a shard on its own
chip: each fault it can have comes out `correct: false` by the number
that fault is for, and a layout that cannot be laid against the tree
ends in the restore's `ValueError`, not in a result."""

import contextlib
import json
import os

import numpy as np
import pytest

from perfbench import faults, harness
from perfbench.tests import tiny

CELL = "reshard-olmoe-host4"
CONFIG = "ckpt-olmoe-1b-7b-ep4"
NEW = ("broadcast.host_copy_share", "broadcast.placed_bytes_ratio",
       "client.span_view_share.restore")
BLOCK = 8192
H, F, E, V, LAYERS = 64, 32, 8, 196, 4
STACKED = 3 * LAYERS


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = tiny.tiny_root(str(tmp_path_factory.mktemp("root")))

    def cut(c):
        c["cluster"]["tier_bytes"] = 256 << 20
        c["cluster"]["block_size"] = BLOCK
        c.update(hidden_size=H, intermediate_size=F, num_experts=E,
                 vocab_size=V)

    tiny.edit_json(os.path.join(dest, "perfbench", "configs",
                                CONFIG + ".json"), cut)
    return dest


def dataset(root, seed=3):
    cell = harness.load_cell(root, CELL)
    gen = cell.module("generators", cell.config["generator"])
    return gen, gen.DataSet(seed, cell.config)


def share_bytes(gen, specs, chips=4) -> tuple[int, int]:
    """(checkpoint bytes, bytes four chips hold under the layout)."""
    once = sum(2 * int(np.prod(s)) for _, s in specs)
    divided = sum(2 * int(np.prod(s)) for n, s in specs if gen.stacked(n))
    return once, divided + chips * (once - divided)


def test_the_published_sizes_are_the_issues():
    """From `tensors` alone: no tensor is made at this size here."""
    with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    gen = harness.load_module(os.path.join(
        harness.HERE, "generators", config["generator"] + ".py"))
    specs = gen.tensors(config)
    once, held = share_bytes(gen, specs)
    assert len(specs) == 51 and once == 3_768_651_776
    assert held == 5_410_930_688 and held // 4 == 805_306_368 + 547_426_304
    block = config["cluster"]["block_size"]
    spanning = [2 * int(np.prod(s)) for _, s in specs
                if 2 * int(np.prod(s)) > block]
    assert len(spanning) == 14 and sum(spanning) == 3_633_315_840
    experts = [s for n, s in specs if gen.stacked(n)]
    assert len(experts) == 12 and {2 * int(np.prod(s)) for s in experts} \
        == {4 * block}
    assert {s[0] for s in experts} == {config["num_experts"]} == {64}
    assert config["layout"]["tensors"] == {"experts": ["expert", None, None],
                                           "dense": []}
    # the dense tensors are ckpt-olmoe-1b-7b's, name for name
    plain = [n for n, _ in gen.plain.tensors(dict(
        config, num_experts=1, published={"num_experts": 64}))
        if ".experts." not in n]
    assert [n for n, _ in specs if not gen.stacked(n)] == plain


def test_the_tiny_set_keeps_the_shape(root):
    gen, ds = dataset(root)
    again, other = gen.DataSet(3, harness.load_cell(root, CELL).config), \
        gen.DataSet(2**31 + 4, harness.load_cell(root, CELL).config)
    assert len(ds) == 51 and ds.total_bytes == sum(
        ds.tensor(i).nbytes for i in range(len(ds)))
    stacked = [i for i, (n, _) in enumerate(ds.specs) if gen.stacked(n)]
    assert len(stacked) == STACKED
    assert {ds.tensor(i).nbytes for i in stacked} == {4 * BLOCK}
    assert all(ds.layout_of(i) == (["expert", None, None] if i in stacked
                                   else []) for i in range(len(ds)))
    assert np.array_equal(ds.tensor(stacked[0]), again.tensor(stacked[0]))
    assert not np.array_equal(ds.tensor(stacked[0]),
                              other.tensor(stacked[0]))
    assert not np.array_equal(ds.tensor(stacked[0]), ds.tensor(stacked[1]))
    listed = json.loads(ds.manifest())
    assert [t["shape"] for t in listed["tensors"]] \
        == [list(s) for _, s in ds.specs]
    assert set(listed["tree"]["v"]) == {n for n, _ in ds.specs}


def test_timed_run(root):
    res = tiny.run(root, CELL)
    cell = harness.load_cell(root, CELL)
    assert cell.chips == 4 and cell.traffic["driver"] == "restore_sharded"
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"restore_gbps", "setup_s"} \
        == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res["metrics"].values())
    assert set(res["compared"]) == {
        "tensors_mismatched", "tensors_missing", "tensors_misplaced",
        "share_bytes_off", "window_compiles"}
    assert all(v == 0 and lim == 0 for v, lim in res["compared"].values())


def test_traced_run_reports_the_cells_metrics(root, monkeypatch):
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    # 21 of the 22 readers of `broadcast-olmoe-host4` (not
    # `rpc.dials_per_file.restore`: test_pool_readers.py holds its list
    # to two cells), the two of `setup_s`, the three new ones
    assert len(cell.per_layer) == 26
    assert "rpc.dials_per_file.restore" not in m
    # (a CPU device keeps no memory statistics: nothing to read there)
    assert set(m) == {x["name"] for x in cell.per_layer
                      if not x["name"].startswith("device.peak_hbm_gb")}
    assert set(NEW) <= set(m)
    gen, ds = dataset(root)
    once, held = share_bytes(gen, ds.specs)
    assert m["broadcast.placed_bytes_ratio"] == pytest.approx(held / once)
    assert 1 < held / once < 2
    spanning = sum(2 * n for n in ds.sizes if 2 * n > BLOCK)
    assert m["client.span_view_share.restore"] \
        == pytest.approx(spanning / once)
    assert 0 < m["broadcast.host_copy_share"] < 1
    assert m["broadcast.ready_wait_share"] < 1 and m["broadcast.place_ms"] > 0
    assert m["client.zero_copy_share.restore"] == 1.0
    assert m["client.phase_ms.copy.restore"] == 0
    assert m["worker.socket_gbps.restore"] == 0
    assert m["entry.window_compiles"] == 0
    assert m["broadcast.tensors_per_s"] > 0


def test_the_new_readers_are_silent_elsewhere(root):
    """No other cell lists the three, and for a program that keeps none
    of the counters (the parent keeps no ckpt.host_copy.s, ckpt.bytes)
    each finds nothing to read."""
    for other in ("restore-olmoe-chip", "broadcast-olmoe-host4"):
        cell = harness.load_cell(root, other)
        assert not {m["name"] for m in cell.per_layer} & set(NEW)
    cell = harness.load_cell(root, CELL)
    window = harness.Window(1.0, clock=iter((0.0, 1.0)).__next__)
    window.complete(0)
    window.complete(10)
    older = {"client": {"ckpt.wall_s": 0.0, "ckpt.place.s": 0.0},
             "worker": {}, "stages": {}, "fetched_bytes": 0, "delivered": 0}
    after = dict(older, client={"ckpt.wall_s": 1.0, "ckpt.place.s": 0.5},
                 fetched_bytes=10)
    run = harness.Run(cell=cell, window=window, setup_s=0.0,
                      spans=harness.Spans(), before=older, after=after,
                      compile_setup={}, compile_window={},
                      memory_peak_bytes=0, trace=None, notes={})
    assert {n: cell.module("layer_metrics", n).read(run)
            for n in NEW} == dict.fromkeys(NEW)
    run.after = dict(after, client={
        "ckpt.wall_s": 1.0, "ckpt.host_copy.s": 0.25, "ckpt.bytes": 10,
        "ckpt.placed_bytes": 14, "read.span_view_bytes": 9})
    assert [cell.module("layer_metrics", n).read(run) for n in NEW] \
        == [0.25, 1.4, 0.9]


def test_a_program_that_counts_as_the_parent_gives_a_whole_result(
        root, monkeypatch):
    """The parent's way through the branch — a host load whose copy is
    counted as ckpt.place, then broadcast_params, no ckpt.host_copy.*,
    no ckpt.bytes — runs the cell to a whole, correct result without the
    two readers of counters it lacks."""
    from curvine_tpu.tpu import broadcast

    async def as_the_parent(client, path, mesh, spec_tree, allow_pickle=False):
        host = await broadcast.load_checkpoint(client, path, placer=np.array)
        return broadcast.broadcast_params(host, mesh, spec_tree)

    monkeypatch.setattr(broadcast, "_distribute_sharded", as_the_parent)
    tiny.fake_reduction(monkeypatch)
    res = tiny.run(root, CELL, trace=True)
    cell = harness.load_cell(root, CELL)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {
        x["name"] for x in cell.per_layer
        if not x["name"].startswith("device.peak_hbm_gb")} - set(NEW[:2])
    assert res["metrics"]["client.span_view_share.restore"] > 0.7


@pytest.mark.parametrize("fault,kw,number", [
    ("altered_answer", {"every": 9}, "tensors_mismatched"),
    ("exchange_left_out", {}, "tensors_misplaced")])
def test_fault_is_seen(root, fault, kw, number):
    with faults.FAULTS[fault](**kw):
        res = tiny.run(root, CELL)
    value, limit = res["compared"][number]
    assert limit == 0 and value > 0, res["compared"]
    assert res["correct"] is False
    if fault == "exchange_left_out":
        # one chip holds everything once: a checkpoint's bytes, not the
        # layout's share
        gen, ds = dataset(root)
        once, held = share_bytes(gen, ds.specs)
        assert res["compared"]["share_bytes_off"][0] \
            == (held - once) * (res["attempted"] + 1)


def test_a_tree_short_of_a_leaf_ends_in_the_restores_error(root):
    """`missing_tensor` does not apply to this path: the tree cannot be
    laid against its spec_tree, the restore raises, and a run that
    crashes sets no reading."""
    with faults.FAULTS["missing_tensor"](), \
            pytest.raises(ValueError, match="lm_head.weight"):
        tiny.run(root, CELL)


@contextlib.contextmanager
def placement(change):
    """The placement's `device_put` of a divided leaf patched where
    `broadcast` looks up `jax`: `change(host array, sharding)` gives what
    is placed instead."""
    import jax
    from jax.sharding import NamedSharding
    from curvine_tpu.tpu import broadcast

    class Changed:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def device_put(x, target=None, **kw):
            if isinstance(target, NamedSharding) and "expert" in target.spec:
                x, target = change(x, target)
            return jax.device_put(x, target, **kw)

    with faults._patched(broadcast, "jax", lambda inner: Changed()):
        yield


def test_a_neighbours_experts_are_seen_on_the_shards_chip(root):
    """Every chip is handed the experts of the chip after it: each leaf
    is laid out as named, every index is the layout's, the bytes add up
    — and every shard of every stacked leaf is wrong on its own chip."""
    def neighbours(x, target):
        return np.roll(x, -(x.shape[0] // 4), axis=0), target

    with placement(neighbours):
        res = tiny.run(root, CELL)
    restores = res["attempted"] + 1              # and the warm-up
    assert res["compared"]["tensors_mismatched"] \
        == (restores * STACKED * 4, 0)
    assert res["compared"]["tensors_misplaced"] == (0, 0)
    assert res["compared"]["share_bytes_off"] == (0, 0)
    assert res["correct"] is False


def test_a_divided_leaf_replicated_is_seen_by_the_bytes(root):
    from jax.sharding import NamedSharding, PartitionSpec

    def replicated(x, target):
        return x, NamedSharding(target.mesh, PartitionSpec())

    with placement(replicated):
        res = tiny.run(root, CELL)
    restores = res["attempted"] + 1
    assert res["compared"]["share_bytes_off"] \
        == (restores * STACKED * 3 * 4 * BLOCK, 0)
    assert res["compared"]["tensors_misplaced"][0] >= restores * STACKED
    assert res["correct"] is False
