"""A checkpoint as a JAX trainer saves it, made from the seed: the layout
and the seeded bit-pattern recipe of `ckpt_manifest.py` (loaded from
beside this file, not copied), with a layer's experts **stacked** — one
tensor `(num_experts, ...)` a projection a layer, as a trainer that holds
them under `PartitionSpec("expert", None, None)` writes them — where
Hugging Face keeps one tensor an expert. The plain side of the restore
under another layout: `tensors(config)` lists names and shapes,
`DataSet.tensor(i)` is tensor i's bits, `DataSet.layout_of(i)` the
configuration's `layout` entry for it. Nothing here imports the
program."""

from __future__ import annotations

import os

import numpy as np

from perfbench import harness

plain = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ckpt_manifest.py"))

PROJECTIONS = ("gate_proj", "up_proj", "down_proj")


def tensors(config: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every tensor, in file order: the dense tensors
    of `ckpt_manifest.tensors` under their names, and after each layer's
    router its three stacked expert tensors."""
    h = int(config["hidden_size"])
    f = int(config["intermediate_size"])
    e = int(config["num_experts"])
    dense = plain.tensors(dict(config, num_experts=0,
                               published={"num_experts": e}))
    out = []
    for name, shape in dense:
        out.append((name, shape))
        if name.endswith(".mlp.gate.weight"):
            p = name[:-len(".gate.weight")] + ".experts."
            out.append((p + "gate_proj.weight", (e, f, h)))
            out.append((p + "up_proj.weight", (e, f, h)))
            out.append((p + "down_proj.weight", (e, h, f)))
    return out


def stacked(name: str) -> bool:
    return any(name.endswith(f".mlp.experts.{proj}.weight")
               for proj in PROJECTIONS)


class DataSet(plain.DataSet):
    """`ckpt_manifest.DataSet` over the stacked listing: its `tensor`,
    `manifest`, `file_name` and bit masks as they are, its seeded draw
    repeated here line for line (its own `__init__` lists the unstacked
    tensors)."""

    def __init__(self, seed: int, config: dict):
        if config.get("torch_dtype", "bfloat16") != "bfloat16":
            raise ValueError("ckpt_manifest_stacked writes bfloat16 tensors")
        self.layout = config["layout"]
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

    def layout_of(self, i: int) -> list:
        """The configuration's `layout` entry for tensor i: the axes of
        a PartitionSpec, as a list."""
        kind = "experts" if stacked(self.specs[i][0]) else "dense"
        return list(self.layout["tensors"][kind])
