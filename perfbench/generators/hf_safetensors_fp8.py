"""A Hugging Face checkpoint in DeepSeek-V3's published FP8 format, made
from the seed: `hf_safetensors.py`'s layout (index, shards of at most
`max_shard_size` cut in state-dict order, tensors sorted by name in a
file; loaded from beside this file as that file loads `ckpt_manifest.py`)
with three dtypes in it, and `config.json` beside the index carrying
`quantization_config`.

The listing is `hf_safetensors.tensors` (the q-LoRA branch where
`q_lora_rank` is set) over the experts the files hold (`experts_in_files`,
of `n_routed_experts`), with every attention and MLP linear `X.weight`
stored as `F8_E4M3` and followed by its `F32` scales
`X.weight_scale_inv` of ⌈o/b⌉ × ⌈i/b⌉ (b = `weight_block_size`), and
every router's `F32` `e_score_correction_bias` after its `gate.weight`.
Embedding, head, norms and routers stay `BF16`.

Bits: a BF16 tensor by `ckpt_manifest.py`'s recipe; an fp8 weight is
(base8[o : o + n] XOR k) with the NaN codes 0x7F / 0xFF moved to 0x7E /
0xFE (e4m3fn has no infinity: every other code is a finite number), base8
one seeded byte string as long as the largest fp8 weight; a scale block
is amax / 448 with log2(amax) uniform in [-7, 1] (every nonzero product
with an fp8 value a normal float32); a router bias is normal(0, 0.01).

The plain side: `expand(wq, scale, block)` is the bf16 a weight stands
for, in float32 numpy — each code's float32 value (ml_dtypes' table of
the 256), times its block's scale (`np.repeat` over the block, cropped
to the weight), rounded once to bf16 to nearest even — nothing of the
program; `expand_rows` gives it a row of blocks at a time. `parse`
(from `hf_safetensors.py`) reads a shard from its own bytes. `DataSet.share` lists what one rank of the first pipeline stage
is handed back (no scale), `DataSet.share_ranges` what it reads (its fp8
weights' scales too), `kernel_bytes(config)` what expanding its fp8
weights moves in the device's memory."""

from __future__ import annotations

import json
import math
import os

import ml_dtypes
import numpy as np

from perfbench import harness

hf = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "hf_safetensors.py"))

INDEX = hf.INDEX
CONFIG = "config.json"
SCALE = "_scale_inv"
BYTES = {"BF16": 2, "F8_E4M3": 1, "F32": 4}
# the first pipeline stage holds the embedding and its layers; the final
# norm and the head lie on the last
LAST_STAGE = ("model.norm.weight", "lm_head.weight")
# keys of the configuration file that describe the benchmark, not the
# model: left out of the config.json written beside the index
BENCH_KEYS = ("name", "source", "also", "generator", "published",
              "experts_held", "expert_rank", "expert_parallel_ranks",
              "experts_in_files", "pipeline_stage", "max_shard_size",
              "reduced", "reduced_why", "assumed", "cluster", "data_root",
              "guarantees", "deployment")
_DECODE = np.arange(256, dtype=np.uint8).view(
    ml_dtypes.float8_e4m3fn).astype(np.float32)


def block_of(config: dict) -> tuple[int, int]:
    return tuple(int(b) for b in
                 config["quantization_config"]["weight_block_size"])


def is_fp8(name: str) -> bool:
    """An attention or MLP linear: q_a / q_b / kv_a(_with_mqa) / kv_b /
    o / gate / up / down projections, the experts' and shared ones."""
    parts = name.split(".")
    return parts[-1] == "weight" and parts[-2].endswith(
        ("_proj", "_proj_with_mqa"))


def tensors(config: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, safetensors dtype) of every tensor in the files, in
    state-dict order."""
    bo, bi = block_of(config)
    held = int(config["experts_in_files"])
    out = []
    for name, shape in hf.tensors(config):
        e = hf.expert_of(name)
        if e is not None and e >= held:
            continue
        if is_fp8(name):
            out.append((name, shape, "F8_E4M3"))
            out.append((name + SCALE, (-(-shape[0] // bo),
                                       -(-shape[1] // bi)), "F32"))
            continue
        out.append((name, shape, "BF16"))
        if name.endswith(".mlp.gate.weight"):
            out.append((name[:-len("weight")] + "e_score_correction_bias",
                        (int(config["n_routed_experts"]),), "F32"))
    return out


def keeps(config: dict):
    """The rank's selection, asked of weights (the loader brings a
    weight's scales with it): the first stage's tensors that are not a
    routed expert's, and its own experts'."""
    if config["pipeline_stage"] != "first":
        raise ValueError(f"pipeline_stage {config['pipeline_stage']!r}: "
                         f"only the first stage is listed")
    held = hf.held_experts(config)

    def keep(name: str) -> bool:
        e = hf.expert_of(name)
        return name not in LAST_STAGE and (e is None or e in held)
    return keep


def share_of(config: dict, listing=None) -> tuple[list[str], list[str]]:
    """(names handed back, names read) of the rank's share, in the
    index's (sorted) order: the read ones add each fp8 weight's scales."""
    listing = tensors(config) if listing is None else listing
    dtypes = {n: d for n, _, d in listing}
    keep = keeps(config)
    back = sorted(n for n in dtypes if not n.endswith(SCALE) and keep(n))
    read = sorted(back + [n + SCALE for n in back
                          if dtypes[n] == "F8_E4M3"])
    return back, read


def kernel_bytes(config: dict) -> int:
    """Bytes the expansion of one load's fp8 weights must move in the
    device's memory: each weight read at 1 B and its scales at 4 B an
    entry, each written at 2 B an entry."""
    listing = tensors(config)
    shapes = {n: s for n, s, _ in listing}
    fp8 = {n for n, _, d in listing if d == "F8_E4M3"}
    return sum(3 * math.prod(shapes[n]) + 4 * math.prod(shapes[n + SCALE])
               for n in share_of(config, listing)[0] if n in fp8)


def shard_layout(config: dict, nbytes: list[int],
                 names: list[str]) -> list[list[int]]:
    """`hf_safetensors.shard_layout` with each tensor at its own dtype's
    size: the state dict cut where the next tensor would pass
    `max_shard_size`, each shard's tensors sorted by name."""
    cap = int(config["max_shard_size"])
    groups: list[list[int]] = [[]]
    size = 0
    for i, n in enumerate(nbytes):
        if groups[-1] and size + n > cap:
            groups.append([])
            size = 0
        groups[-1].append(i)
        size += n
    return [sorted(g, key=lambda i: names[i]) for g in groups]


def header_bytes(entries: list[tuple[str, tuple[int, ...], str, int]]
                 ) -> bytes:
    """A shard's length prefix and header for (name, shape, dtype,
    nbytes) in file order."""
    header: dict = {"__metadata__": {"format": "pt"}}
    at = 0
    for name, shape, dtype, nbytes in entries:
        header[name] = {"dtype": dtype, "shape": list(shape),
                        "data_offsets": [at, at + nbytes]}
        at += nbytes
    raw = json.dumps(header, separators=(",", ":")).encode()
    raw += b" " * (-len(raw) % 8)
    return len(raw).to_bytes(8, "little") + raw


parse = hf.parse


def expand_rows(wq: np.ndarray, scale: np.ndarray,
                block: tuple[int, int]):
    """The bf16 an fp8 weight stands for, one row of blocks at a time
    (uint16 bit patterns [≤ bo, i]): float32(wq) × its block's scale, one
    float32 product, rounded to bf16 to nearest even. `wq`: the fp8 codes
    as uint8 [o, i]; `scale`: float32 [⌈o/bo⌉, ⌈i/bi⌉]."""
    o, i = wq.shape
    bo, bi = block
    # each scale over its block's columns, cropped to the weight's
    cols = np.repeat(scale.astype(np.float32), bi, axis=1)[:, :i]
    for rb in range(-(-o // bo)):
        x = _DECODE[wq[rb * bo:(rb + 1) * bo]]
        x *= cols[rb]                       # and over its block's rows
        u = x.view(np.uint32)
        t = u >> 16                         # to nearest, ties to even
        t &= np.uint32(1)
        t += np.uint32(0x7FFF)
        t += u
        t >>= 16
        yield t.astype(np.uint16)


def expand(wq: np.ndarray, scale: np.ndarray,
           block: tuple[int, int]) -> np.ndarray:
    """`expand_rows` whole: uint16 [o, i]."""
    return np.concatenate(list(expand_rows(wq, scale, block)))


class DataSet(hf.DataSet):
    """The files of `tensors(config)` and the bits in them, from the
    seed; `reference(name)` is what the loader has to hand back."""

    def __init__(self, seed: int, config: dict):
        listing = tensors(config)
        self.specs = [(n, s) for n, s, _ in listing]
        self.dtypes = [d for _, _, d in listing]
        self.sizes = [math.prod(s) for _, s in self.specs]
        self.nbytes = [BYTES[d] * n for d, n in zip(self.dtypes,
                                                    self.sizes)]
        self.block = block_of(config)
        self.seed = seed
        rng = np.random.default_rng([seed, 3])
        longest = max(n for n, d in zip(self.sizes, self.dtypes)
                      if d == "BF16")
        longest8 = max(n for n, d in zip(self.sizes, self.dtypes)
                       if d == "F8_E4M3")
        self.base = rng.integers(0, 1 << 16, longest + 4096,
                                 dtype=np.uint16)
        self.base8 = rng.integers(0, 1 << 8, longest8 + 4096,
                                  dtype=np.uint8)
        self.offsets = [int(rng.integers(0, (
            self.base8 if d == "F8_E4M3" else self.base).size - n + 1))
            for n, d in zip(self.sizes, self.dtypes)]
        self.keys = rng.integers(0, 1 << 16, len(self.specs),
                                 dtype=np.uint16)
        names = [n for n, _ in self.specs]
        self.index_of = {name: i for i, name in enumerate(names)}
        self.shard_tensors = shard_layout(config, self.nbytes, names)
        self.shards = [f"model-{k + 1:05d}-of-{len(self.shard_tensors):05d}"
                       f".safetensors" for k in range(len(self.shard_tensors))]
        self.shard_of = {names[i]: self.shards[k]
                         for k, g in enumerate(self.shard_tensors) for i in g}
        self.total_bytes = sum(self.nbytes)
        self.held = hf.held_experts(config)
        self.keeps = keeps(config)
        self.share, self.share_ranges = share_of(config, listing)
        self.share_bytes = sum(self.nbytes[self.index_of[n]]
                               for n in self.share_ranges)
        self.model_config = {k: v for k, v in config.items()
                             if k not in BENCH_KEYS}

    def tensor(self, i: int) -> np.ndarray:
        """Tensor i's stored bits in its shape: uint16 for BF16, uint8
        for F8_E4M3, float32 for F32."""
        d, shape = self.dtypes[i], self.specs[i][1]
        if d == "BF16":
            return super().tensor(i)
        if d == "F8_E4M3":
            o, n = self.offsets[i], self.sizes[i]
            bits = self.base8[o:o + n] ^ np.uint8(self.keys[i] & 0xFF)
            bits[(bits & 0x7F) == 0x7F] ^= np.uint8(1)
            return bits.reshape(shape)
        rng = np.random.default_rng([self.seed, 5, i])
        if self.specs[i][0].endswith(SCALE):
            return (np.exp2(rng.uniform(-7.0, 1.0, shape))
                    / 448).astype(np.float32)
        return (rng.standard_normal(shape) * 0.01).astype(np.float32)

    def reference(self, name: str):
        """What the loader hands back for a tensor of the share, in
        pieces that laid end to end are it: an fp8 weight expanded by
        `expand_rows`, anything else its stored bits whole."""
        i = self.index_of[name]
        if self.dtypes[i] != "F8_E4M3":
            return [self.tensor(i)]
        return expand_rows(self.tensor(i),
                           self.tensor(self.index_of[name + SCALE]),
                           self.block)

    def header(self, k: int) -> bytes:
        return header_bytes([(self.specs[i][0], self.specs[i][1],
                              self.dtypes[i], self.nbytes[i])
                             for i in self.shard_tensors[k]])

    def config_json(self) -> bytes:
        """`config.json` as the checkpoint ships it: the model's own
        keys, `quantization_config` among them."""
        return (json.dumps(self.model_config, indent=2, sort_keys=True)
                + "\n").encode()
