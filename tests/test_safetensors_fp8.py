"""A block-scaled float8 safetensors checkpoint as DeepSeek-V3 publishes
it, loaded with `load_safetensors(..., dequant=bfloat16)`: every
``F8_E4M3`` weight ``X.weight`` read with its ``F32`` scales
``X.weight_scale_inv`` and expanded to bf16 on the device it was placed
on (`pallas_ops.fp8_block_dequant`, interpret mode on the CPU mesh).

The files are written by a plain writer here and the expected weights
come from a plain reference of the format (numpy float32 and
ml_dtypes: f32(Wq) × the block's scale, rounded once to bf16), held to
the program's output bit for bit. Widths are small and ragged: a
200 × 300 weight at blocks of 128 has partial blocks both ways."""

import json

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from curvine_tpu.testing import MiniCluster
from curvine_tpu.tpu.broadcast import (
    SAFETENSORS_INDEX, load_safetensors, load_safetensors_to_device,
)
from curvine_tpu.tpu.pallas_ops import fp8_block_dequant
from curvine_tpu.worker import shm as wshm

pytestmark = pytest.mark.skipif(
    not wshm.shm_supported(),
    reason="memfd_create/SCM_RIGHTS not available on this platform")

BLOCK = 64 * 1024
ROOT = "/hf/dsv3"
B = 128                                    # weight_block_size, both ways
H, VOCAB, EXPERTS = 300, 500, 4
FP8 = np.dtype(ml_dtypes.float8_e4m3fn)
BF16 = np.dtype(ml_dtypes.bfloat16)
ST = {FP8: "F8_E4M3", BF16: "BF16", np.dtype(np.float32): "F32"}


def tensors() -> list[tuple[str, tuple[int, ...], np.dtype]]:
    """A dense layer and an MoE layer with DeepSeek-V3's names, q-LoRA
    branch included, in state-dict order: each fp8 linear followed by
    its scales; embedding, norms, router and head as stored."""
    out = [("model.embed_tokens.weight", (VOCAB, H), BF16)]

    def linear(name: str, o: int, i: int) -> None:
        out.extend([(f"{name}.weight", (o, i), FP8),
                    (f"{name}.weight_scale_inv",
                     (-(-o // B), -(-i // B)), np.dtype(np.float32))])

    for layer in range(2):
        p = f"model.layers.{layer}"
        linear(f"{p}.self_attn.q_a_proj", 200, H)
        out.append((f"{p}.self_attn.q_a_layernorm.weight", (200,), BF16))
        linear(f"{p}.self_attn.q_b_proj", 260, 200)
        linear(f"{p}.self_attn.kv_a_proj_with_mqa", 72, H)
        out.append((f"{p}.self_attn.kv_a_layernorm.weight", (64,), BF16))
        linear(f"{p}.self_attn.kv_b_proj", 260, 64)
        linear(f"{p}.self_attn.o_proj", H, 130)
        if layer == 0:
            for proj, (o, i) in (("gate_proj", (200, H)),
                                 ("up_proj", (200, H)),
                                 ("down_proj", (H, 200))):
                linear(f"{p}.mlp.{proj}", o, i)
        else:
            for e in range(EXPERTS):
                for proj, (o, i) in (("gate_proj", (72, H)),
                                     ("up_proj", (72, H)),
                                     ("down_proj", (H, 72))):
                    linear(f"{p}.mlp.experts.{e}.{proj}", o, i)
            out += [(f"{p}.mlp.gate.weight", (EXPERTS, H), BF16),
                    (f"{p}.mlp.gate.e_score_correction_bias", (EXPERTS,),
                     np.dtype(np.float32))]
            for proj, (o, i) in (("gate_proj", (72, H)),
                                 ("up_proj", (72, H)),
                                 ("down_proj", (H, 72))):
                linear(f"{p}.mlp.shared_experts.{proj}", o, i)
        out += [(f"{p}.input_layernorm.weight", (H,), BF16),
                (f"{p}.post_attention_layernorm.weight", (H,), BF16)]
    out += [("model.norm.weight", (H,), BF16),
            ("lm_head.weight", (VOCAB, H), BF16)]
    return out


def rank_keeps(name: str) -> bool:
    """Experts 2 and 3 of the MoE layer and everything that is not an
    expert's."""
    parts = name.split(".")
    return "experts" not in parts or int(
        parts[parts.index("experts") + 1]) in (2, 3)


def draw(rng, shape, dtype) -> np.ndarray:
    if dtype == FP8:
        raw = rng.integers(0, 256, shape, dtype=np.uint8)
        raw[(raw & 0x7F) == 0x7F] ^= 1        # no NaN code
        return raw.view(FP8)
    if dtype == BF16:
        return rng.standard_normal(shape, dtype=np.float32).astype(BF16)
    # scales as a published amax / 448 (and a router bias)
    return (rng.uniform(0.01, 2.0, shape) / 448).astype(np.float32)


def shard_bytes(named: dict[str, np.ndarray]) -> bytes:
    header, body, at = {"__metadata__": {"format": "pt"}}, [], 0
    for name in sorted(named):
        raw = named[name].tobytes()
        header[name] = {"dtype": ST[named[name].dtype],
                        "shape": list(named[name].shape),
                        "data_offsets": [at, at + len(raw)]}
        body.append(raw)
        at += len(raw)
    h = json.dumps(header).encode()
    h += b" " * (-len(h) % 8)
    return len(h).to_bytes(8, "little") + h + b"".join(body)


SPLIT = "model.layers.1.self_attn.kv_b_proj.weight"


def make_set(seed: int = 11):
    """→ (arrays, {shard file: bytes}, weight_map): two shards cut in
    state-dict order right after `SPLIT`, whose scales open the second."""
    rng = np.random.default_rng(seed)
    arrays = {n: draw(rng, s, d) for n, s, d in tensors()}
    order = list(arrays)
    cut = order.index(SPLIT) + 1
    assert order[cut] == SPLIT + "_scale_inv"
    files, weight_map = {}, {}
    for k, part in enumerate((order[:cut], order[cut:])):
        fname = f"model-{k + 1:05d}-of-00002.safetensors"
        files[fname] = shard_bytes({n: arrays[n] for n in part})
        weight_map.update(dict.fromkeys(part, fname))
    return arrays, files, weight_map


def config(block=(B, B)) -> bytes:
    q = {"quant_method": "fp8", "fmt": "e4m3", "activation_scheme":
         "dynamic"}
    if block is not None:
        q["weight_block_size"] = list(block)
    return json.dumps({"model_type": "deepseek_v3",
                       "quantization_config": q}).encode()


async def write_set(c, files, weight_map, conf=None):
    await c.meta.mkdir(ROOT)
    for fname, data in files.items():
        await c.write_all(f"{ROOT}/{fname}", data)
    await c.write_all(f"{ROOT}/{SAFETENSORS_INDEX}", json.dumps(
        {"metadata": {"total_size": 0}, "weight_map": weight_map}).encode())
    if conf is not None:
        await c.write_all(f"{ROOT}/config.json", conf)


def expand(wq: np.ndarray, scale: np.ndarray, block=(B, B)) -> np.ndarray:
    """The plain reference: float32 numpy, each scale repeated over its
    block and cropped to the weight, one product, rounded to bf16."""
    o, i = wq.shape
    big = np.repeat(np.repeat(scale, block[0], axis=0), block[1],
                    axis=1)[:o, :i]
    return (wq.astype(np.float32) * big).astype(BF16)


def grown(counters: dict, before: dict):
    return lambda k: counters.get(k, 0) - before.get(k, 0)


def cluster(tmp_path):
    return MiniCluster(workers=1, base_dir=str(tmp_path), block_size=BLOCK)


@pytest.mark.parametrize("keep", ["all", "rank"])
async def test_fp8_weights_come_back_as_the_bf16_their_scales_give(
        tmp_path, keep):
    arrays, files, weight_map = make_set()
    select = None if keep == "all" else rank_keeps
    want = [n for n in weight_map if not n.endswith("_scale_inv")
            and (select is None or select(n))]
    fp8 = [n for n in want if arrays[n].dtype == FP8]
    assert any(arrays[n].shape == (200, H) for n in fp8)  # ragged both ways
    assert weight_map[SPLIT] != weight_map[SPLIT + "_scale_inv"]
    async with cluster(tmp_path) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        await write_set(c, files, weight_map, config())
        c.tracer.store.clear()
        before = dict(c.counters)
        dev = jax.devices()[1]
        out = await load_safetensors_to_device(c, ROOT, dev, select=select,
                                               dequant=jnp.bfloat16)
        grew = grown(c.counters, before)
        assert list(out) == want                   # no scale handed back
        for name, arr in out.items():
            a = arrays[name]
            ref = expand(a, arrays[name + "_scale_inv"]) \
                if a.dtype == FP8 else a
            assert arr.dtype == ref.dtype and arr.shape == ref.shape, name
            assert np.asarray(arr).tobytes() == ref.tobytes(), name
            assert arr.devices() == {dev}
        scale_bytes = sum(arrays[n + "_scale_inv"].nbytes for n in fp8)
        fp8_bytes = sum(arrays[n].nbytes for n in fp8)
        assert grew("ckpt.dequant.n") == len(fp8)
        assert grew("ckpt.dequant.s") > 0
        assert grew("ckpt.dequant.bytes_in") == fp8_bytes + scale_bytes
        assert grew("ckpt.dequant.bytes_out") == 2 * fp8_bytes
        assert grew("ckpt.bytes") == scale_bytes + sum(
            arrays[n].nbytes for n in want)
        assert grew("ckpt.place.n") == len(want) + len(fp8)
        spans = c.tracer.store.drain(8192)
        tensor = {s["span_id"]: s for s in spans if s["op"] == "ckpt.tensor"}
        assert len(tensor) == len(want) + len(fp8)
        dq = [s for s in spans if s["op"] == "ckpt.dequant"]
        assert sorted(s["attrs"]["name"] for s in dq) == sorted(fp8)
        for s in dq:
            parent = tensor[s["parent"]]
            name = s["attrs"]["name"]
            assert parent["attrs"]["name"] == name
            o, i = arrays[name].shape
            assert s["attrs"]["shape"] == [o, i]
            assert s["attrs"]["blocks"] == -(-o // B) * -(-i // B)
        await c.close()


def spoil(arrays: dict, how: str):
    """A set with one pair wrong → (files, weight_map, config, the name
    the refusal has to give)."""
    arrays = dict(arrays)
    victim = "model.layers.0.self_attn.o_proj.weight"
    named = victim
    if how == "missing_scale":
        del arrays[victim + "_scale_inv"]
    elif how == "orphan_scale":
        arrays[victim] = arrays[victim].astype(np.float32).astype(BF16)
        named = victim + "_scale_inv"
    elif how == "misshapen_scale":
        arrays[victim + "_scale_inv"] = arrays[victim + "_scale_inv"][:, :1]
        named = victim + "_scale_inv"
    half = len(arrays) // 2
    order = list(arrays)
    files, weight_map = {}, {}
    for k, part in enumerate((order[:half], order[half:])):
        fname = f"model-{k + 1:05d}-of-00002.safetensors"
        files[fname] = shard_bytes({n: arrays[n] for n in part})
        weight_map.update(dict.fromkeys(part, fname))
    conf = config(None) if how == "no_block_size" else config()
    if how == "no_block_size":
        named = next(n for n in order if arrays[n].dtype == FP8)
    return files, weight_map, conf, named


@pytest.mark.parametrize("how", ["missing_scale", "orphan_scale",
                                 "misshapen_scale", "no_block_size"])
async def test_a_bad_pair_fails_the_load_whole(tmp_path, how):
    arrays, _, _ = make_set()
    files, weight_map, conf, named = spoil(arrays, how)
    async with cluster(tmp_path) as mc:
        c = mc.client()
        await write_set(c, files, weight_map, conf)
        before = dict(c.counters)
        with pytest.raises(ValueError) as refused:
            await load_safetensors_to_device(c, ROOT, jax.devices()[0],
                                             dequant=jnp.bfloat16)
        assert named in str(refused.value), str(refused.value)
        grew = grown(c.counters, before)
        assert grew("ckpt.place.n") == 0 and grew("ckpt.bytes") == 0
        assert grew("ckpt.dequant.n") == 0
        assert grew("read.zero_copy_bytes") == 0
        await c.close()


@pytest.mark.parametrize("placed", ["device", "host"])
async def test_without_dequant_fp8_and_scales_come_back_raw(tmp_path,
                                                             placed):
    arrays, files, weight_map = make_set()
    async with cluster(tmp_path) as mc:
        c = mc.client()
        await write_set(c, files, weight_map, config())
        before = dict(c.counters)
        if placed == "device":
            out = await load_safetensors_to_device(c, ROOT,
                                                   jax.devices()[0])
        else:
            out = await load_safetensors(c, ROOT)
        assert list(out) == list(weight_map)
        for name, arr in out.items():
            assert arr.dtype == arrays[name].dtype, name
            assert np.asarray(arr).tobytes() == arrays[name].tobytes()
        grew = grown(c.counters, before)
        assert grew("ckpt.dequant.n") == 0
        assert grew("ckpt.bytes") == sum(a.nbytes for a in arrays.values())
        with pytest.raises(ValueError, match="placer"):
            await load_safetensors(c, ROOT, dequant=jnp.bfloat16)
        await c.close()


@pytest.mark.parametrize("shape,block", [
    ((200, 300), (128, 128)), ((576, 640), (128, 128)),
    ((130, 2100), (128, 128)), ((64, 96), (32, 32))])
def test_the_kernel_matches_the_reference_and_a_bf16_product_does_not(
        shape, block):
    rng = np.random.default_rng(5)
    wq = draw(rng, shape, FP8)
    so, si = -(-shape[0] // block[0]), -(-shape[1] // block[1])
    scale = draw(rng, (so, si), np.dtype(np.float32))
    ref = expand(wq, scale, block)
    got = fp8_block_dequant(
        jax.device_put(wq.view(np.uint8), jax.devices()[0]),
        jnp.asarray(scale.reshape(-1)), block)
    assert got.dtype == jnp.bfloat16 and got.shape == shape
    assert np.asarray(got).tobytes() == ref.tobytes()
    # the same expansion with the scale rounded to bf16 and the product
    # taken in bf16: the exact comparison sees the lower precision
    big = np.repeat(np.repeat(scale.astype(BF16), block[0], axis=0),
                    block[1], axis=1)[:shape[0], :shape[1]]
    low = wq.astype(BF16) * big
    assert low.dtype == BF16
    assert (low.view(np.uint16) != ref.view(np.uint16)).any()


def test_every_code_decodes_to_its_float32_value():
    """All 256 e4m3fn codes, subnormals and both NaNs among them, at a
    scale of 1 and a float32 output: the kernel's integer decode is the
    format's own table."""
    codes = np.arange(256, dtype=np.uint8).reshape(2, 128)
    got = fp8_block_dequant(jax.device_put(codes, jax.devices()[0]),
                            jnp.ones((1,), jnp.float32), (32, 128),
                            jnp.float32)
    want = codes.view(FP8).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert np.isnan(np.asarray(got)).sum() == 2
