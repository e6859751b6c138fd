"""A restore under another layout: a checkpoint saved whole, one file a
leaf, through `distribute_checkpoint(client, path, mesh, spec_tree)` onto
four CPU devices as one expert-parallel group — stacked expert leaves
`P("expert", None, None)`, dense leaves `P()`. One restore, placement and
ready sweep inside it; every chip its own experts and no other's; a
layout that cannot be placed refused before a tensor file is opened."""

import time

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from curvine_tpu.testing import MiniCluster

CPUS = jax.devices("cpu")[:4]
E, F, H = 8, 16, 32                     # experts, expert width, hidden
BLOCK = E * F * H * 4 // 4              # a stacked f32 leaf is four blocks
EXPERTS = ("gate_proj", "up_proj", "down_proj")


def make_params(seed: int = 5) -> dict:
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {"embed": normal(42, H), "norm": normal(H),
            "layers": [{"q_proj": normal(H, H), "router": normal(E, H),
                        "gate_proj": normal(E, F, H),
                        "up_proj": normal(E, F, H),
                        "down_proj": normal(E, H, F)} for _ in range(2)]}


def layout(params: dict):
    def spec(path, _):
        stacked = getattr(path[-1], "key", None) in EXPERTS
        return P("expert", None, None) if stacked else P()
    return jax.tree_util.tree_map_with_path(spec, params)


def mesh_of(axis: str = "expert"):
    from curvine_tpu.tpu.mesh import make_mesh
    return make_mesh(devices=CPUS, axis_names=(axis,))


def grown(counters: dict, before: dict):
    return lambda k: counters.get(k, 0) - before.get(k, 0)


async def saved(mc, params, path="/ckpt/ep"):
    from curvine_tpu.tpu.broadcast import save_checkpoint
    writer = mc.client()
    await save_checkpoint(writer, path, params)
    await writer.close()
    return path


async def test_restore_under_a_layout_is_one_accounted_restore(tmp_path):
    from curvine_tpu.tpu.broadcast import distribute_checkpoint
    params, mesh = make_params(), mesh_of()
    specs = layout(params)
    leaves = jax.tree.leaves(params)
    stacked = [x for x in leaves if x.ndim == 3]
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        c = mc.client()
        c.tracer.sample_rate = 1.0
        before = dict(c.counters)
        t0 = time.perf_counter()
        back = await distribute_checkpoint(c, path, mesh, specs)
        took = time.perf_counter() - t0
        grew = grown(c.counters, before)

        # bit-exact, laid out as named, each chip its own two experts
        for (key, want), got, spec in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree.leaves(back), jax.tree.leaves(
                    specs, is_leaf=lambda x: isinstance(x, P))):
            assert np.asarray(got).tobytes() == want.tobytes(), key
            assert got.sharding.is_equivalent_to(
                NamedSharding(mesh, spec), want.ndim), key
            assert len(got.addressable_shards) == 4
            for shard in got.addressable_shards:
                i = CPUS.index(shard.device)
                own = want[2 * i:2 * i + 2] if want.ndim == 3 else want
                assert np.asarray(shard.data).tobytes() == own.tobytes()
        assert back["layers"][1]["up_proj"].sharding.spec \
            == P("expert", None, None)
        assert back["embed"].sharding.is_fully_replicated

        # one restore; placement and the ready sweep lie inside it
        assert grew("ckpt.restores") == 1
        assert took - 0.05 <= grew("ckpt.wall_s") <= took
        assert grew("ckpt.host_copy.n") == len(leaves) == grew("ckpt.place.n")
        assert grew("ckpt.ready_wait.n") == 1
        once = sum(x.nbytes for x in leaves)
        assert grew("ckpt.host_copy.bytes") == once == grew("ckpt.bytes")
        dense = once - sum(x.nbytes for x in stacked)
        assert grew("ckpt.placed_bytes") == once + 3 * dense
        for k in ("ckpt.host_copy.s", "ckpt.place.s", "ckpt.ready_wait.s"):
            assert 0 <= grew(k) <= grew("ckpt.wall_s")
        # six stacked leaves of four blocks, and the embedding's two
        spanning = [x for x in leaves if x.nbytes > BLOCK]
        assert grew("read.span_views") == len(spanning) == len(stacked) + 1
        assert grew("read.span_view_bytes") == sum(x.nbytes
                                                   for x in spanning)
        assert grew("read.span_view_blocks") == 4 * len(stacked) + 2

        # the spans: one root, the new steps under it with their attrs
        spans = c.tracer.store.drain(4096)
        (root,) = [s for s in spans if s["op"] == "ckpt.restore"]
        inside = [s for s in spans if s["trace_id"] == root["trace_id"]]
        ops = [s["op"] for s in inside]
        assert ops.count("ckpt.host_copy") == ops.count("ckpt.place") \
            == ops.count("ckpt.tensor") == len(leaves)
        assert ops.count("ckpt.ready_wait") == 1
        tensors = {s["span_id"] for s in inside if s["op"] == "ckpt.tensor"}
        assert {s["parent"] for s in inside
                if s["op"] == "ckpt.host_copy"} <= tensors
        placed = [s["attrs"] for s in inside if s["op"] == "ckpt.place"]
        assert {a["name"] for a in placed} \
            == {f"t{i:05d}.bin" for i in range(len(leaves))}
        assert sorted({a["spec"] for a in placed}) \
            == sorted({str(P()), str(P("expert", None, None))})
        await c.close()


def short_of_a_leaf(specs):
    return {**specs, "layers": [specs["layers"][0],
                                {k: v for k, v in specs["layers"][1].items()
                                 if k != "router"}]}


def axis_the_mesh_lacks(specs):
    return {**specs, "norm": P("model")}


def not_divisible(specs):
    return {**specs, "embed": P("expert", None)}         # 42 rows


@pytest.mark.parametrize("broken,names", [
    (short_of_a_leaf, r"\['layers'\]\[1\]\['router'\].*no PartitionSpec"),
    (axis_the_mesh_lacks, r"\['norm'\].*axis 'model'"),
    (not_divisible, r"\['embed'\].*42.*4 chips"),
])
async def test_a_layout_that_cannot_be_placed_fails_early(tmp_path, broken,
                                                          names):
    """One ValueError that names the leaf, before any tensor file is
    opened: the manifest is the only file read."""
    from curvine_tpu.tpu.broadcast import distribute_checkpoint
    params = make_params()
    specs = broken(layout(params))
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        c = mc.client()
        before = dict(c.counters)
        with pytest.raises(ValueError, match=names):
            await distribute_checkpoint(c, path, mesh_of(), specs)
        grew = grown(c.counters, before)
        assert grew("read.files") == 1           # manifest.json
        assert grew("ckpt.host_copy.n") == grew("ckpt.place.n") == 0
        assert grew("ckpt.restores") == 0        # none completed
        await c.close()


async def test_a_leaf_the_checkpoint_lacks_is_named_too(tmp_path):
    from curvine_tpu.tpu.broadcast import distribute_checkpoint
    params = make_params()
    specs = dict(layout(params), extra=P())
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        c = mc.client()
        with pytest.raises(ValueError, match=r"\['extra'\].*not in the "
                                             r"checkpoint"):
            await distribute_checkpoint(c, path, mesh_of(), specs)
        assert c.counters.get("read.files", 0) == 1
        await c.close()


@pytest.mark.parametrize("how", ["tree", "flat", "device", "host"])
async def test_other_paths_account_as_before(tmp_path, how):
    """No host copy is counted where none is made, `ckpt.place` is the
    dispatch of a transfer wherever it is counted, and the host load's
    owning copy is `ckpt.host_copy`, not `ckpt.place`."""
    from curvine_tpu.tpu.broadcast import (
        distribute_checkpoint, distribute_checkpoint_to_device,
        load_checkpoint,
    )
    params = make_params()
    n = len(jax.tree.leaves(params))
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        c = mc.client()
        before = dict(c.counters)
        if how == "device":
            back = await distribute_checkpoint_to_device(c, path, CPUS[0])
        elif how == "host":
            back = await load_checkpoint(c, path)
        else:
            back = await distribute_checkpoint(c, path, mesh_of("data"),
                                               schedule=how)
        grew = grown(c.counters, before)
        for want, got in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            assert np.asarray(got).tobytes() == want.tobytes()
            if how in ("tree", "flat"):
                assert got.sharding.is_fully_replicated
                assert len(got.addressable_shards) == 4
        assert grew("ckpt.restores") == 1
        assert grew("ckpt.bytes") == grew("ckpt.placed_bytes") == 0
        if how == "host":
            assert grew("ckpt.host_copy.n") == n and grew("ckpt.place.n") == 0
            assert grew("ckpt.ready_wait.n") == 0
            assert all(isinstance(x, np.ndarray) and x.flags.owndata
                       for x in jax.tree.leaves(back))
        else:
            assert grew("ckpt.host_copy.n") == 0 and grew("ckpt.place.n") == n
            assert grew("ckpt.ready_wait.n") == 1
        await c.close()
