"""A checkpoint as `save_checkpoint` lays it out, made from the seed:
`manifest.json` ({"tensors": [{name, dtype, shape}], "tree": skeleton})
beside one raw file `t<i:05d>.bin` per tensor. The plain side of the
restore cells: `tensors(config)` lists names and shapes from the
configuration's sizes, `DataSet.tensor(i)` is tensor i's bits. Nothing
here imports the program.

Tensor i = (base[o_i : o_i + n] XOR k_i) forced into finite bf16 bit
patterns (sign and mantissa free, exponent 112..127: magnitudes 2^-15..2),
with `base` one seeded uint16 string as long as the largest tensor and
(o_i, k_i) seeded per tensor: one pass over memory per tensor."""

from __future__ import annotations

import json

import numpy as np

_KEEP = np.uint16(0x87FF)       # sign, low 4 exponent bits, mantissa
_FORCE = np.uint16(0x3800)      # exponent = 0b0111xxxx


def tensors(config: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every tensor held here, in file order: the
    Hugging Face names of an OLMoE-style decoder, experts 0..held-1 of
    each layer."""
    h = int(config["hidden_size"])
    v = int(config["vocab_size"])
    f = int(config["intermediate_size"])
    e_held = int(config["num_experts"])
    e_all = int(config["published"]["num_experts"])
    out: list[tuple[str, tuple[int, ...]]] = [("model.embed_tokens.weight",
                                               (v, h))]
    for layer in range(int(config["num_hidden_layers"])):
        p = f"model.layers.{layer}"
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            out.append((f"{p}.self_attn.{proj}.weight", (h, h)))
        out.append((f"{p}.self_attn.q_norm.weight", (h,)))
        out.append((f"{p}.self_attn.k_norm.weight", (h,)))
        out.append((f"{p}.input_layernorm.weight", (h,)))
        out.append((f"{p}.post_attention_layernorm.weight", (h,)))
        out.append((f"{p}.mlp.gate.weight", (e_all, h)))     # router: whole
        for e in range(e_held):
            out.append((f"{p}.mlp.experts.{e}.gate_proj.weight", (f, h)))
            out.append((f"{p}.mlp.experts.{e}.up_proj.weight", (f, h)))
            out.append((f"{p}.mlp.experts.{e}.down_proj.weight", (h, f)))
    out.append(("model.norm.weight", (h,)))
    if not config.get("tie_word_embeddings", False):
        out.append(("lm_head.weight", (v, h)))
    return out


class DataSet:
    def __init__(self, seed: int, config: dict):
        if config.get("torch_dtype", "bfloat16") != "bfloat16":
            raise ValueError("ckpt_manifest writes bfloat16 tensors")
        self.specs = tensors(config)
        self.sizes = [int(np.prod(s)) for _, s in self.specs]
        rng = np.random.default_rng([seed, 3])
        longest = max(self.sizes)
        self.base = rng.integers(0, 1 << 16, longest + 4096,
                                 dtype=np.uint16)
        self.offsets = [int(rng.integers(0, longest + 4096 - n + 1))
                        for n in self.sizes]
        self.keys = rng.integers(0, 1 << 16, len(self.specs),
                                 dtype=np.uint16)
        self.total_bytes = 2 * sum(self.sizes)

    def __len__(self) -> int:
        return len(self.specs)

    def file_name(self, i: int) -> str:
        return f"t{i:05d}.bin"

    def tensor(self, i: int) -> np.ndarray:
        """Tensor i as uint16 bit patterns of bfloat16, in its shape."""
        o, n = self.offsets[i], self.sizes[i]
        bits = ((self.base[o:o + n] ^ self.keys[i]) & _KEEP) | _FORCE
        return bits.reshape(self.specs[i][1])

    def manifest(self) -> bytes:
        """JSON for `manifest.json`: the flat tensor list and a tree
        skeleton of one dict keyed by tensor name."""
        listing = [{"name": self.file_name(i), "dtype": "bfloat16",
                    "shape": list(shape)}
                   for i, (_, shape) in enumerate(self.specs)]
        tree = {"k": "dict", "v": {name: {"k": "leaf", "i": i}
                                   for i, (name, _) in
                                   enumerate(self.specs)}}
        return json.dumps({"tensors": listing, "tree": tree}).encode()
