"""A Hugging Face safetensors checkpoint read as byte ranges of its shard
files (tpu/broadcast.py::load_safetensors) and the ranged views under it
(client/reader.py): one expert-parallel rank's share of a tiny
DeepSeek-V2-shaped set, at a block size under which tensors share
blocks, straddle two and span three or more.

The files are written by a plain writer here (header length, JSON
header sorted by name, the bytes) and read back by a plain parser of
their own bytes: the reference the program's output is held to."""

import asyncio
import gc
import json
import os

import ml_dtypes
import numpy as np
import pytest

import jax

from curvine_tpu.testing import MiniCluster
from curvine_tpu.tpu.broadcast import (
    SAFETENSORS_INDEX, load_safetensors, load_safetensors_to_device,
)
from curvine_tpu.worker import shm as wshm

pytestmark = pytest.mark.skipif(
    not wshm.shm_supported(),
    reason="memfd_create/SCM_RIGHTS not available on this platform")

BLOCK = 64 * 1024
ROOT = "/hf/dsv2"
H, DENSE, EXPERT, VOCAB, EXPERTS, RANKS = 64, 160, 48, 1000, 8, 4
BF16 = np.dtype(ml_dtypes.bfloat16)


def tensors() -> list[tuple[str, tuple[int, ...]]]:
    """A dense layer and two MoE layers with DeepSeek-V2's names (no
    q_lora: q_proj whole, the kv path through its latent)."""
    out = [("model.embed_tokens.weight", (VOCAB, H))]
    for layer in range(3):
        p = f"model.layers.{layer}"
        out += [(f"{p}.self_attn.q_proj.weight", (3 * H, H)),
                (f"{p}.self_attn.kv_a_proj_with_mqa.weight", (H // 2, H)),
                (f"{p}.self_attn.kv_a_layernorm.weight", (H // 4,)),
                (f"{p}.self_attn.kv_b_proj.weight", (2 * H, H // 4)),
                (f"{p}.self_attn.o_proj.weight", (H, H))]
        if layer == 0:
            out += [(f"{p}.mlp.{n}.weight", s) for n, s in (
                ("gate_proj", (DENSE, H)), ("up_proj", (DENSE, H)),
                ("down_proj", (H, DENSE)))]
        else:
            for e in range(EXPERTS):
                out += [(f"{p}.mlp.experts.{e}.{n}.weight", s) for n, s in (
                    ("gate_proj", (EXPERT, H)), ("up_proj", (EXPERT, H)),
                    ("down_proj", (H, EXPERT)))]
            out.append((f"{p}.mlp.gate.weight", (EXPERTS, H)))
            out += [(f"{p}.mlp.shared_experts.{n}.weight", s) for n, s in (
                ("gate_proj", (2 * EXPERT, H)), ("up_proj", (2 * EXPERT, H)),
                ("down_proj", (H, 2 * EXPERT)))]
        out += [(f"{p}.input_layernorm.weight", (H,)),
                (f"{p}.post_attention_layernorm.weight", (H,))]
    out += [("model.norm.weight", (H,)), ("lm_head.weight", (VOCAB, H))]
    return out


def rank_keeps(rank: int):
    """Experts rank*E/R .. (rank+1)*E/R - 1 of every MoE layer, and every
    tensor that is not an expert's (replicated in the group)."""
    per = EXPERTS // RANKS

    def keep(name: str) -> bool:
        parts = name.split(".")
        if "experts" not in parts:
            return True
        return rank * per <= int(parts[parts.index("experts") + 1]) \
            < (rank + 1) * per
    return keep


def shard_bytes(named: dict[str, np.ndarray]) -> bytes:
    """One shard as safetensors lays it out: tensors sorted by name, the
    header padded with spaces to a multiple of 8."""
    header, body, at = {"__metadata__": {"format": "pt"}}, [], 0
    for name in sorted(named):
        raw = named[name].tobytes()
        header[name] = {"dtype": "BF16", "shape": list(named[name].shape),
                        "data_offsets": [at, at + len(raw)]}
        body.append(raw)
        at += len(raw)
    h = json.dumps(header).encode()
    h += b" " * (-len(h) % 8)
    return len(h).to_bytes(8, "little") + h + b"".join(body)


def parse(data: bytes) -> dict[str, tuple[np.ndarray, int, int]]:
    """The plain reader: name → (array, first byte, end) from a shard's
    own bytes."""
    n = int.from_bytes(data[:8], "little")
    header = json.loads(data[8:8 + n])
    out = {}
    for name, t in header.items():
        if name == "__metadata__":
            continue
        b, e = (8 + n + x for x in t["data_offsets"])
        out[name] = (np.frombuffer(data[b:e], BF16).reshape(t["shape"]), b, e)
    return out


def make_set(seed: int = 7, shard_at: int = 400_000):
    """→ ({shard file: bytes}, weight_map): state-dict order, a new shard
    where the next tensor would pass `shard_at` bytes."""
    rng = np.random.default_rng(seed)
    shards, cur, size = [], {}, 0
    for name, shape in tensors():
        a = rng.integers(0, 1 << 16, shape, dtype=np.uint16).view(BF16)
        if cur and size + a.nbytes > shard_at:
            shards.append(cur)
            cur, size = {}, 0
        cur[name] = a
        size += a.nbytes
    shards.append(cur)
    files, weight_map = {}, {}
    for i, named in enumerate(shards):
        fname = f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"
        files[fname] = shard_bytes(named)
        weight_map.update(dict.fromkeys(named, fname))
    return files, weight_map


async def write_set(c, files: dict, weight_map: dict, root: str = ROOT):
    await c.meta.mkdir(root)
    for fname, data in files.items():
        await c.write_all(f"{root}/{fname}", data)
    await c.write_all(f"{root}/{SAFETENSORS_INDEX}", json.dumps(
        {"metadata": {"total_size": 0}, "weight_map": weight_map}).encode())


def grown(counters: dict, before: dict):
    return lambda k: counters.get(k, 0) - before.get(k, 0)


def cluster(tmp_path):
    return MiniCluster(workers=1, base_dir=str(tmp_path), block_size=BLOCK)


@pytest.mark.parametrize("placed", ["device", "host"])
async def test_a_rank_loads_its_share_bit_exact(tmp_path, placed):
    files, weight_map = make_set()
    ref = {}
    for fname, data in files.items():
        ref.update({k: (fname, *v) for k, v in parse(data).items()})
    keep = rank_keeps(1)
    share = [n for n in weight_map if keep(n)]
    # the layout this test is about: tensors that share a block, that
    # straddle two, and that span three or more
    spans = [(b // BLOCK, (e - 1) // BLOCK) for _, _, b, e in ref.values()]
    assert sum(lo == hi for lo, hi in spans) > 50
    assert any(hi == lo + 1 for lo, hi in spans)
    assert any(hi >= lo + 2 for lo, hi in spans)
    assert len(files) >= 2
    async with cluster(tmp_path) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        await write_set(c, files, weight_map)
        c.tracer.store.clear()
        before = dict(c.counters)
        dev = jax.devices()[1]
        if placed == "device":
            out = await load_safetensors_to_device(c, ROOT, dev, select=keep)
        else:
            out = await load_safetensors(c, ROOT, select=keep)
        grew = grown(c.counters, before)
        assert list(out) == share                  # the rank's names exactly
        for name, arr in out.items():
            want = ref[name][1]
            assert arr.dtype == BF16 and arr.shape == want.shape, name
            assert np.asarray(arr).tobytes() == want.tobytes(), name
            if placed == "device":
                assert arr.devices() == {dev}
            else:
                assert isinstance(arr, np.ndarray) and arr.flags.owndata
        # every block a share's tensor lies in (and the index's) granted,
        # mapped and verified once; no byte from the socket
        touched = {(ref[n][0], b // BLOCK) for n in share
                   for b in range(ref[n][2], ref[n][3], BLOCK)} \
            | {(ref[n][0], (ref[n][3] - 1) // BLOCK) for n in share}
        headers = {(f, 0) for f in files}
        assert grew("read.block_fetches") == len(touched | headers) + 1
        assert grew("read.blocks_mapped") == grew("read.block_fetches")
        assert grew("read.files") == len(files) + 1        # and the index
        assert grew("read.primed.files") == len(files)
        assert grew("ckpt.index.n") == 1 and grew("ckpt.index.s") > 0
        assert grew("ckpt.headers.n") == 1 and grew("ckpt.headers.s") > 0
        assert grew("ckpt.bytes") == sum(out[n].nbytes for n in share)
        assert grew("ckpt.restores") == 1
        assert grew("read.checksum_mismatch") == 0
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == 0
        if placed == "device":
            assert grew("ckpt.place.n") == len(share)
        spans = [s for s in c.tracer.store.drain(8192)
                 if s["op"] == "ckpt.tensor"]
        assert len(spans) == len(share)
        for s in spans:
            a = s["attrs"]
            fname, _, b, e = ref[a["name"]]
            assert a["shard"] == fname and a["offset"] == b
            assert a["bytes"] == e - b
            assert a["blocks"] == (e - 1) // BLOCK - b // BLOCK + 1
            assert a["served_by"] == "shm"
        await c.close()


def spoil(files: dict, weight_map: dict, how: str):
    """The set with one thing wrong in it → (files, weight_map, the
    shard and the tensor the refusal has to name)."""
    files = dict(files)
    fname = sorted(files)[0]        # the shard of many tensors
    data = files[fname]
    n = int.from_bytes(data[:8], "little")
    header = json.loads(data[8:8 + n])
    names = sorted(k for k in header if k != "__metadata__")
    victim = names[len(names) // 2]
    if how == "missing_shard":
        del files[fname]
        return files, weight_map, fname, None
    if how == "out_of_range":
        b, _e = header[victim]["data_offsets"]
        header[victim]["data_offsets"] = [b, len(data)]
        header[victim]["shape"] = [len(data) - b - 8 - n]
        header[victim]["dtype"] = "U8"
    elif how == "overlap":
        # the tensor before it, two bytes later: into the victim's first
        prev = names[names.index(victim) - 1]
        b, e = header[prev]["data_offsets"]
        assert e == header[victim]["data_offsets"][0]
        header[prev]["data_offsets"] = [b + 2, e + 2]
    elif how == "unknown_dtype":
        header[victim]["dtype"] = "F4"
    elif how == "not_in_header":
        del header[victim]
    h = json.dumps(header).encode()
    h += b" " * (-len(h) % 8)
    files[fname] = len(h).to_bytes(8, "little") + h + data[8 + n:]
    return files, weight_map, fname, victim


@pytest.mark.parametrize("how", ["missing_shard", "out_of_range", "overlap",
                                 "unknown_dtype", "not_in_header"])
async def test_a_bad_index_or_header_fails_the_restore_whole(tmp_path, how):
    files, weight_map, fname, victim = spoil(*make_set(), how)
    async with cluster(tmp_path) as mc:
        c = mc.client()
        await write_set(c, files, weight_map)
        before = dict(c.counters)
        with pytest.raises(ValueError) as refused:
            await load_safetensors_to_device(c, ROOT, jax.devices()[0])
        said = str(refused.value)
        assert fname in said and (victim is None or victim in said), said
        grew = grown(c.counters, before)
        # refused before a tensor was placed or its bytes were asked for
        assert grew("ckpt.place.n") == 0 and grew("ckpt.bytes") == 0
        assert grew("read.zero_copy_bytes") == 0
        await c.close()


@pytest.mark.parametrize("blocks", [1, 4])
async def test_64_views_of_one_block_fetch_it_once(tmp_path, blocks):
    payload = os.urandom(blocks * BLOCK - 100)
    async with cluster(tmp_path) as mc:
        c = mc.client()
        await c.write_all("/v/one", payload)
        r = await c.open("/v/one")
        base = (blocks - 1) * BLOCK // 2 // BLOCK * BLOCK   # a middle block
        before = dict(c.counters)
        offs = [base + 997 * i % (BLOCK - 300) for i in range(64)]
        views = await asyncio.gather(*(r.mmap_view(o, 300) for o in offs))
        for o, v in zip(offs, views):
            assert bytes(v) == payload[o:o + 300]
        grew = grown(c.counters, before)
        assert grew("read.block_fetches") == 1 == grew("read.blocks_mapped")
        assert grew("read.phase.grant.n") == 1
        assert grew("read.verify.bytes") == min(BLOCK, len(payload) - base)
        assert grew("read.zero_copy_bytes") == 64 * 300
        del views
        await r.close()
        await c.close()


def _fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _maps(name: str = "memfd:cv-") -> int:
    with open("/proc/self/maps") as f:
        return sum(name in line for line in f)


async def test_a_ranged_view_outlives_its_reader_and_eviction(tmp_path):
    """Views of one multi-block file — one straddling two blocks, one
    inside the second — hold the blocks under them past the reader's
    close and the worker's invalidation of the exports, each block let
    go with the last view over it; a loop of open / views / close leaves
    neither descriptors nor mappings behind."""
    payload = os.urandom(4 * BLOCK + 5000)
    async with cluster(tmp_path) as mc:
        c = mc.client()
        await c.write_all("/v/life", payload)

        async def views():
            r = await c.open("/v/life")
            a = await r.mmap_view(BLOCK - 700, 1400)       # straddles 0|1
            b = await r.mmap_view(BLOCK + 4096, 8192)      # inside 1
            bids = [lb.block.id for lb in r.blocks.block_locs]
            await r.close()
            return a, b, bids

        gc.collect()
        base = _maps()
        a, b, bids = await views()
        for bid in bids:
            mc.workers[0].shm.invalidate(bid)          # the worker's fds go
        gc.collect()
        assert _maps() == base + 2                     # blocks 0 and 1
        assert bytes(a) == payload[BLOCK - 700:BLOCK + 700]
        del a
        gc.collect()
        assert _maps() == base + 1                     # `b` holds block 1
        assert bytes(b) == payload[BLOCK + 4096:BLOCK + 4096 + 8192]
        del b
        gc.collect()
        assert _maps() == base
        for _ in range(3):                             # exports made again
            await views()
        gc.collect()
        fds, maps = _fds(), _maps()
        for _ in range(30):
            a, b, _bids = await views()
            assert a[-1] == payload[BLOCK + 699] and b[0] == payload[
                BLOCK + 4096]
            del a, b
        gc.collect()
        assert _maps() == maps == base
        assert _fds() <= fds
        await c.close()
