"""The FP8 safetensors generator against the issue's figures and against
itself. At the published size only the listing, the shard layout and the
share are reckoned (no tensor: 12 GB would not be written here); at a
tiny size the shards are made whole, read back by a parse of their own
bytes that shares nothing with the writer, and the plain expansion is
held to a second, per-block one."""

import json
import math
import os

import ml_dtypes
import numpy as np
import pytest

from perfbench import harness

CONFIG = "hf-deepseek-v3-fp8"
BYTES = {"BF16": 2, "F8_E4M3": 1, "F32": 4}


def load():
    with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    gen = harness.load_module(os.path.join(
        harness.HERE, "generators", config["generator"] + ".py"))
    return gen, config


def nbytes(shape, dtype) -> int:
    return BYTES[dtype] * math.prod(shape)


def test_the_published_sizes_are_the_issues():
    gen, config = load()
    listing = gen.tensors(config)
    assert len(listing) == 919
    assert sum(nbytes(s, d) for _, s, d in listing) == 12_035_801_632
    sizes = [nbytes(s, d) for _, s, d in listing]
    layout = gen.shard_layout(config, sizes, [n for n, _, _ in listing])
    assert [sum(sizes[i] for i in g) for g in layout] == [
        4_995_499_392, 4_992_342_688, 2_047_959_552]
    back, read = gen.share_of(config, listing)
    dtypes = {n: d for n, _, d in listing}
    shapes = {n: s for n, s, _ in listing}
    assert len(back) == 189 and len(read) == 341
    assert sum(nbytes(shapes[n], dtypes[n]) for n in read) \
        == 5_953_538_592
    fp8 = [n for n in back if dtypes[n] == "F8_E4M3"]
    assert len(fp8) == 152
    assert sum(nbytes(shapes[n], "F8_E4M3") for n in fp8) == 4_084_269_056
    assert sum(nbytes(shapes[n + gen.SCALE], "F32") for n in fp8) == 997_920
    on_chip = sum(2 * math.prod(shapes[n]) if dtypes[n] == "F8_E4M3"
                  else nbytes(shapes[n], dtypes[n]) for n in back)
    assert on_chip == 10_036_809_728               # 62.7% of 16 GB
    assert gen.kernel_bytes(config) == 12_253_805_088
    assert {shapes[n] for n in fp8} == {
        (576, 7168), (1536, 7168), (2048, 7168), (7168, 2048),
        (7168, 16384), (7168, 18432), (18432, 7168), (24576, 1536),
        (32768, 512)}
    # the first stage of rank 1: experts 8-15, no final norm, no head
    assert {gen.hf.expert_of(n) for n in back} == {None, *range(8, 16)}
    assert "lm_head.weight" not in back and "model.norm.weight" not in back
    # every scale of ⌈o/128⌉ × ⌈i/128⌉ right after its weight
    for k, (n, s, d) in enumerate(listing):
        if d == "F8_E4M3":
            assert listing[k + 1] == (n + gen.SCALE, (-(-s[0] // 128),
                                                     -(-s[1] // 128)), "F32")
    # the catalog's widths and counts stay: the router over all 256
    assert shapes["model.layers.3.mlp.gate.weight"] == (256, 7168)
    assert dtypes["model.layers.3.mlp.gate.e_score_correction_bias"] \
        == "F32"
    assert shapes["model.layers.0.self_attn.q_b_proj.weight"] == (24576, 1536)
    assert not gen.is_fp8("lm_head.weight") and not gen.is_fp8(
        "model.layers.3.mlp.gate.weight")


@pytest.fixture(scope="module")
def tiny():
    gen, config = load()
    config = dict(config, hidden_size=200, num_attention_heads=2,
                  q_lora_rank=96, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, kv_lora_rank=32, intermediate_size=160,
                  moe_intermediate_size=48, n_routed_experts=16,
                  experts_in_files=8, experts_held=2, vocab_size=300,
                  max_shard_size=400_000)
    return gen, config


def independent_parse(data: bytes) -> dict:
    n = int.from_bytes(data[:8], "little")
    header = json.loads(data[8:8 + n].decode("utf-8").rstrip(" "))
    header.pop("__metadata__", None)
    start = 8 + n
    return {k: (v["dtype"], v["shape"], data[start + v["data_offsets"][0]:
                                             start + v["data_offsets"][1]])
            for k, v in header.items()}


def by_block(wq: np.ndarray, scale: np.ndarray, b: int) -> np.ndarray:
    """A second plain expansion, one block at a time through ml_dtypes'
    own conversions."""
    w = wq.view(ml_dtypes.float8_e4m3fn).astype(np.float32)
    out = np.empty(w.shape, dtype=ml_dtypes.bfloat16)
    for r in range(scale.shape[0]):
        for c in range(scale.shape[1]):
            blk = (slice(r * b, (r + 1) * b), slice(c * b, (c + 1) * b))
            out[blk] = (w[blk] * scale[r, c]).astype(ml_dtypes.bfloat16)
    return out.view(np.uint16)


def test_a_tiny_set_round_trips(tiny):
    gen, config = tiny
    ds = gen.DataSet(2**31 + 7, config)
    assert len(ds.shards) >= 2 and ds.held == {2, 3}
    files = {name: b"".join(ds.shard_chunks(k))
             for k, name in enumerate(ds.shards)}
    seen, arrays = {}, {}
    for name, data in files.items():
        back = independent_parse(data)
        assert list(back) == sorted(back)
        assert gen.parse(data).keys() == back.keys()
        for t, (dtype, shape, raw) in back.items():
            i = ds.index_of[t]
            assert dtype == ds.dtypes[i] and tuple(shape) == ds.specs[i][1]
            assert raw == ds.tensor(i).tobytes()
            arrays[t] = (dtype, shape, raw)
            seen[t] = name
    index = json.loads(ds.index())
    assert index["weight_map"] == seen == ds.shard_of
    assert index["metadata"]["total_size"] == ds.total_bytes \
        == sum(len(r) for _, _, r in arrays.values())
    fp8 = [n for n, (d, _, _) in arrays.items() if d == "F8_E4M3"]
    assert fp8 and all(gen.is_fp8(n) for n in fp8)
    for n in fp8:
        codes = np.frombuffer(arrays[n][2], np.uint8)
        assert not ((codes & 0x7F) == 0x7F).any()          # no NaN code
        o, i = arrays[n][1]
        dtype, shape, raw = arrays[n + gen.SCALE]
        assert dtype == "F32" and shape == [-(-o // 128), -(-i // 128)]
        scale = np.frombuffer(raw, np.float32).reshape(shape)
        assert (scale > 0).all() and np.isfinite(scale).all()
        ref = gen.expand(codes.reshape(o, i), scale, (128, 128))
        assert np.array_equal(ref, by_block(codes.reshape(o, i), scale, 128))
        assert np.array_equal(np.concatenate(list(ds.reference(n))), ref)
        # every nonzero product a normal float32
        x = np.abs(ref.view(ml_dtypes.bfloat16).astype(np.float32))
        assert (x[x > 0] >= np.finfo(np.float32).tiny).all()
    ragged = [n for n in fp8 if any(d % 128 for d in arrays[n][1])]
    assert ragged
    # what the rank reads and is handed back
    assert ds.share == sorted(n for n in seen if ds.keeps(n)
                              and not n.endswith(gen.SCALE))
    assert ds.share_ranges == sorted(ds.share + [
        n + gen.SCALE for n in ds.share if n in fp8])
    assert ds.share_bytes == sum(len(arrays[n][2]) for n in ds.share_ranges)
    conf = json.loads(ds.config_json())
    assert conf["quantization_config"]["weight_block_size"] == [128, 128]
    assert "cluster" not in conf and conf["hidden_size"] == 200


def test_the_seed_makes_the_bits(tiny):
    gen, config = tiny
    a, b = gen.DataSet(11, config), gen.DataSet(11, config)
    c = gen.DataSet(2**31 + 12, config)
    for k in range(len(a.shards)):
        assert b"".join(a.shard_chunks(k)) == b"".join(b.shard_chunks(k))
    for name in ("model.layers.0.self_attn.q_a_proj.weight",
                 "model.layers.0.self_attn.q_a_proj.weight_scale_inv",
                 "model.embed_tokens.weight"):
        i = a.index_of[name]
        assert not np.array_equal(a.tensor(i), c.tensor(i))
    assert a.header(0) == c.header(0)            # the layout is the config's
