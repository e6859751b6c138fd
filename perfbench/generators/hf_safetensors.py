"""A Hugging Face checkpoint as `save_pretrained` publishes it, made from
the seed: `model.safetensors.index.json` (`weight_map`: tensor → shard
file, keys sorted) beside shard files `model-0000k-of-0000n.safetensors`,
the state dict cut into shards in its own order where the next tensor
would pass `max_shard_size`, each shard an 8-byte little-endian header
length, a JSON header padded with spaces to a multiple of 8 (each
tensor's `dtype`, `shape` and `data_offsets` from the header's end, and
`__metadata__`), then the tensors' bytes sorted by name as safetensors
lays them out. Written by hand here: no `safetensors` package, nothing
of the program.

The plain side of the load cell: `tensors(config)` lists a DeepSeek-V2
decoder's names and shapes in state-dict order (`modeling_deepseek.py`
naming: one tensor an expert), `DataSet.tensor(i)` is tensor i's bits
by the recipe of `ckpt_manifest.py` (loaded from beside this file), and
`parse(data)` reads a shard from its own bytes with numpy and `json`
alone. `DataSet.share` lists the tensors one expert-parallel rank
loads: every tensor that is not a routed expert's, and its experts."""

from __future__ import annotations

import json
import os

import numpy as np

from perfbench import harness

plain = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ckpt_manifest.py"))

INDEX = "model.safetensors.index.json"


def tensors(config: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every tensor of the checkpoint, in state-dict
    order: embedding, each layer (attention, then the dense MLP or the
    routed experts, router and shared experts, then the two norms), the
    final norm, the untied head."""
    h = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    nope = int(config["qk_nope_head_dim"])
    rope = int(config["qk_rope_head_dim"])
    v_dim, kv_lora = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    q_lora = config["q_lora_rank"]
    experts = int(config["n_routed_experts"])
    moe_f = int(config["moe_intermediate_size"])
    shared_f = moe_f * int(config["n_shared_experts"])
    f, vocab = int(config["intermediate_size"]), int(config["vocab_size"])
    out = [("model.embed_tokens.weight", (vocab, h))]

    def mlp(prefix: str, width: int) -> None:
        out.extend([(f"{prefix}.gate_proj.weight", (width, h)),
                    (f"{prefix}.up_proj.weight", (width, h)),
                    (f"{prefix}.down_proj.weight", (h, width))])

    for layer in range(int(config["num_hidden_layers"])):
        p = f"model.layers.{layer}"
        if q_lora is None:
            out.append((f"{p}.self_attn.q_proj.weight",
                        (heads * (nope + rope), h)))
        else:
            out += [(f"{p}.self_attn.q_a_proj.weight", (int(q_lora), h)),
                    (f"{p}.self_attn.q_a_layernorm.weight", (int(q_lora),)),
                    (f"{p}.self_attn.q_b_proj.weight",
                     (heads * (nope + rope), int(q_lora)))]
        out += [(f"{p}.self_attn.kv_a_proj_with_mqa.weight",
                 (kv_lora + rope, h)),
                (f"{p}.self_attn.kv_a_layernorm.weight", (kv_lora,)),
                (f"{p}.self_attn.kv_b_proj.weight",
                 (heads * (nope + v_dim), kv_lora)),
                (f"{p}.self_attn.o_proj.weight", (h, heads * v_dim))]
        if layer >= int(config["first_k_dense_replace"]) \
                and layer % int(config["moe_layer_freq"]) == 0:
            for e in range(experts):
                mlp(f"{p}.mlp.experts.{e}", moe_f)
            out.append((f"{p}.mlp.gate.weight", (experts, h)))
            mlp(f"{p}.mlp.shared_experts", shared_f)
        else:
            mlp(f"{p}.mlp", f)
        out += [(f"{p}.input_layernorm.weight", (h,)),
                (f"{p}.post_attention_layernorm.weight", (h,))]
    out.append(("model.norm.weight", (h,)))
    if not config.get("tie_word_embeddings", False):
        out.append(("lm_head.weight", (vocab, h)))
    return out


def expert_of(name: str) -> int | None:
    """The routed expert a tensor belongs to, or None (shared experts,
    router, attention, norms, embedding, head)."""
    parts = name.split(".")
    if "experts" not in parts:
        return None
    return int(parts[parts.index("experts") + 1])


def held_experts(config: dict) -> set[int]:
    """The routed experts of the configuration's rank: `experts_held`
    of them from `expert_rank` × `experts_held`."""
    lo = int(config["expert_rank"]) * int(config["experts_held"])
    return set(range(lo, lo + int(config["experts_held"])))


def shard_layout(config: dict,
                 specs: list[tuple[str, tuple[int, ...]]]) -> list[list[int]]:
    """The shard files as lists of indices into `specs`, each in file
    order: the state dict cut in its own order where the next tensor
    would pass `max_shard_size` bytes (a tensor over it alone in a shard
    of its own), then each shard's tensors sorted by name."""
    cap = int(config["max_shard_size"])
    groups: list[list[int]] = [[]]
    size = 0
    for i, (_, shape) in enumerate(specs):
        n = 2 * int(np.prod(shape))
        if groups[-1] and size + n > cap:
            groups.append([])
            size = 0
        groups[-1].append(i)
        size += n
    return [sorted(g, key=lambda i: specs[i][0]) for g in groups]


def header_bytes(entries: list[tuple[str, tuple[int, ...], int]]) -> bytes:
    """A shard's length prefix and header for (name, shape, nbytes) of
    bf16 tensors in file order."""
    header: dict = {"__metadata__": {"format": "pt"}}
    at = 0
    for name, shape, nbytes in entries:
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [at, at + nbytes]}
        at += nbytes
    raw = json.dumps(header, separators=(",", ":")).encode()
    raw += b" " * (-len(raw) % 8)
    return len(raw).to_bytes(8, "little") + raw


def parse(data) -> dict[str, tuple[str, list[int], int, int]]:
    """A shard read from its own bytes (anything numpy can view as
    uint8), with numpy and json alone: name → (dtype, shape, first
    byte, end) in the file."""
    buf = np.frombuffer(data, dtype=np.uint8)
    n = int(buf[:8].view("<u8")[0])
    header = json.loads(buf[8:8 + n].tobytes())
    return {name: (t["dtype"], t["shape"], 8 + n + t["data_offsets"][0],
                   8 + n + t["data_offsets"][1])
            for name, t in header.items() if name != "__metadata__"}


class DataSet(plain.DataSet):
    """`ckpt_manifest.DataSet`'s `tensor` and seeded bit recipe over the
    listing of `tensors`, its draw repeated here line for line (its own
    `__init__` lists another model), and the files that hold them."""

    def __init__(self, seed: int, config: dict):
        if config.get("torch_dtype", "bfloat16") != "bfloat16":
            raise ValueError("hf_safetensors writes bfloat16 tensors")
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
        self.index_of = {name: i for i, (name, _) in enumerate(self.specs)}
        self.shard_tensors = shard_layout(config, self.specs)
        self.shards = [f"model-{k + 1:05d}-of-{len(self.shard_tensors):05d}"
                       f".safetensors" for k in range(len(self.shard_tensors))]
        self.shard_of = {self.specs[i][0]: self.shards[k]
                         for k, g in enumerate(self.shard_tensors) for i in g}
        self.total_bytes = 2 * sum(self.sizes)      # of tensors, in the files
        self.held = held_experts(config)
        self.share = [name for name in sorted(self.shard_of)
                      if self.keeps(name)]
        self.share_bytes = sum(2 * self.sizes[self.index_of[n]]
                               for n in self.share)

    def keeps(self, name: str) -> bool:
        """The rank's selection: every tensor that is not a routed
        expert's, and its own experts'."""
        e = expert_of(name)
        return e is None or e in self.held

    def header(self, k: int) -> bytes:
        """Shard k's length prefix and header."""
        return header_bytes([(self.specs[i][0], self.specs[i][1],
                              2 * self.sizes[i])
                             for i in self.shard_tensors[k]])

    def shard_chunks(self, k: int):
        """Shard k's bytes, in order: the header, then each tensor's."""
        yield self.header(k)
        for i in self.shard_tensors[k]:
            yield self.tensor(i).tobytes()

    def index(self) -> bytes:
        """`model.safetensors.index.json` as `save_pretrained` writes it
        (keys sorted, two-space indent)."""
        return (json.dumps({"metadata": {"total_size": self.total_bytes},
                            "weight_map": self.shard_of},
                           indent=2, sort_keys=True) + "\n").encode()
