"""The safetensors generator against the issue's figures and against
itself. At the published size only the listing, the shard layout and
the headers are made (no tensor: 5.68 GB would not be written here);
at a tiny size the shards are made whole and read back by a parse of
their own bytes that shares nothing with the writer."""

import json
import os

import numpy as np
import pytest

from perfbench import harness

CONFIG = "hf-deepseek-v2-lite"


def load():
    with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    gen = harness.load_module(os.path.join(
        harness.HERE, "generators", config["generator"] + ".py"))
    return gen, config


def nbytes(shape) -> int:
    return 2 * int(np.prod(shape))


def independent_parse(data: bytes) -> dict:
    """name → (dtype, shape, the tensor's bytes), by the format's
    description alone."""
    n = int.from_bytes(data[:8], "little")
    header = json.loads(data[8:8 + n].decode("utf-8").rstrip(" "))
    header.pop("__metadata__", None)
    start = 8 + n
    return {k: (v["dtype"], v["shape"], data[start + v["data_offsets"][0]:
                                             start + v["data_offsets"][1]])
            for k, v in header.items()}


def test_the_published_sizes_are_the_issues():
    gen, config = load()
    specs = gen.tensors(config)
    assert len(specs) == 825
    assert sum(nbytes(s) for _, s in specs) == 5_679_662_080
    held = gen.held_experts(config)
    assert held == set(range(16, 32))
    share = [(n, s) for n, s in specs
             if gen.expert_of(n) is None or gen.expert_of(n) in held]
    assert len(share) == 249
    assert sum(nbytes(s) for _, s in share) == 2_357_773_312
    # the catalog's widths, each tensor as the issue lists it
    sizes = {n.split(".", 3)[-1] if n.startswith("model.layers") else n:
             nbytes(s) for n, s in specs}
    assert sizes["self_attn.kv_a_layernorm.weight"] == 1024
    assert sizes["input_layernorm.weight"] == 4096
    assert sizes["mlp.gate.weight"] == 256 << 10
    assert sizes["self_attn.kv_a_proj_with_mqa.weight"] == 2304 << 10
    assert sizes["self_attn.kv_b_proj.weight"] == 4 << 20
    assert sizes["mlp.experts.0.down_proj.weight"] == 5632 << 10
    assert sizes["self_attn.o_proj.weight"] == 8 << 20
    assert sizes["mlp.shared_experts.up_proj.weight"] == 11 << 20
    assert sizes["self_attn.q_proj.weight"] == 12 << 20
    assert sizes["model.embed_tokens.weight"] == sizes["lm_head.weight"] \
        == 400 << 20
    # one dense layer, then MoE layers of 64 experts each
    assert sum(".mlp.experts." in n for n, _ in specs) == 4 * 64 * 3
    assert [n for n, _ in specs if n.startswith("model.layers.0.mlp")] == [
        f"model.layers.0.mlp.{p}.weight"
        for p in ("gate_proj", "up_proj", "down_proj")]


def test_the_published_shards_and_headers():
    gen, config = load()
    specs = gen.tensors(config)
    layout = gen.shard_layout(config, specs)
    assert len(layout) == 2
    assert sorted(i for g in layout for i in g) == list(range(len(specs)))
    # state-dict order cut once: the first shard is a prefix of it
    assert sorted(layout[0]) == list(range(len(layout[0])))
    block = config["cluster"]["block_size"]
    for g in layout:
        names = [specs[i][0] for i in g]
        assert names == sorted(names)
        data = sum(nbytes(specs[i][1]) for i in g)
        assert data <= config["max_shard_size"] == 5_000_000_000
        head = gen.header_bytes([(specs[i][0], specs[i][1],
                                  nbytes(specs[i][1])) for i in g])
        n = int.from_bytes(head[:8], "little")
        assert len(head) == 8 + n and n % 8 == 0
        parsed = gen.parse(head)
        assert list(parsed) == names
        at = len(head)
        for i, (dtype, shape, b, e) in zip(g, parsed.values()):
            assert dtype == "BF16" and tuple(shape) == specs[i][1]
            assert (b, e) == (at, at + nbytes(shape))
            at = e
    # the layout the cell is about: tensors share blocks, straddle them
    first = layout[0]
    offs = np.cumsum([0] + [nbytes(specs[i][1]) for i in first])
    lo, hi = offs[:-1] // block, (offs[1:] - 1) // block
    assert (hi > lo).sum() > 50 and (hi - lo).max() >= 6


@pytest.fixture(scope="module")
def tiny():
    gen, config = load()
    config = dict(config, hidden_size=64, num_attention_heads=2,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  kv_lora_rank=32, intermediate_size=160,
                  moe_intermediate_size=48, n_routed_experts=8,
                  vocab_size=1000, experts_held=2, max_shard_size=600_000)
    return gen, config


def test_a_tiny_set_round_trips(tiny):
    gen, config = tiny
    ds = gen.DataSet(2**31 + 5, config)
    assert len(ds.shards) >= 2 and ds.held == {2, 3}
    files = {name: b"".join(ds.shard_chunks(k))
             for k, name in enumerate(ds.shards)}
    seen = {}
    for name, data in files.items():
        back = independent_parse(data)
        assert list(back) == sorted(back)
        assert gen.parse(data).keys() == back.keys()
        for t, (dtype, shape, raw) in back.items():
            i = ds.index_of[t]
            assert dtype == "BF16" and tuple(shape) == ds.specs[i][1]
            assert raw == ds.tensor(i).tobytes()
            seen[t] = name
    assert sum(len(d) for d in files.values()) > ds.total_bytes
    index = json.loads(ds.index())
    assert index["weight_map"] == seen == ds.shard_of
    assert list(index["weight_map"]) == sorted(seen)
    assert index["metadata"]["total_size"] == ds.total_bytes
    assert ds.share == [n for n in sorted(seen) if ds.keeps(n)]
    assert not any(gen.expert_of(n) in (0, 1, 4, 5, 6, 7) for n in ds.share)
    assert {gen.expert_of(n) for n in ds.share} == {None, 2, 3}
    assert ds.share_bytes == sum(ds.tensor(ds.index_of[n]).nbytes
                                 for n in ds.share)


def test_the_seed_makes_the_bits(tiny):
    gen, config = tiny
    a, b = gen.DataSet(11, config), gen.DataSet(11, config)
    c = gen.DataSet(2**31 + 12, config)
    for k in range(len(a.shards)):
        assert b"".join(a.shard_chunks(k)) == b"".join(b.shard_chunks(k))
    i = a.index_of["lm_head.weight"]
    assert not np.array_equal(a.tensor(i), c.tensor(i))
    assert a.header(0) == c.header(0)            # the layout is the config's
