"""A checkout's worth of benchmark files at a size a test run can hold:
BENCHMARK.json and perfbench/ copied to a scratch root, the two
configurations cut to kilobytes. Cells are then found from those files
exactly as a real run finds them."""

from __future__ import annotations

import asyncio
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def edit_json(path: str, change) -> None:
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(dest: str) -> str:
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(dest, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)

    def small_cluster(c):
        c["cluster"]["tier_bytes"] = 256 << 20
        c["cluster"]["block_size"] = 1 << 20

    def cosmoflow(c):
        small_cluster(c)
        c.update(record_length=70001, record_length_stdev=1800,
                 record_length_resize=51200, num_files_train=24)

    def olmoe(c):
        small_cluster(c)
        # the embedding (9000 x 64 bf16) spans two 1 MiB blocks
        c.update(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
                 vocab_size=9000, num_experts=2)
        c["published"]["num_experts"] = 8

    conf = os.path.join(dest, "perfbench", "configs")
    edit_json(os.path.join(conf, "dlio-cosmoflow.json"), cosmoflow)
    edit_json(os.path.join(conf, "ckpt-olmoe-1b-7b.json"), olmoe)
    edit_json(os.path.join(dest, "perfbench", "traffic", "train-feed.json"),
              lambda t: t.update(warm_batches=4))
    return dest


def fake_reduction(monkeypatch) -> None:
    """A CPU trace has no device plane: stand a fixed reduction in for
    the trace's, after checking that the window's span is in the trace."""
    from perfbench import trace_reduce

    def reduce_dir(trace_dir, chips, span_names):
        tr = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        t0, t1 = trace_reduce.window_of(tr)
        return trace_reduce.Reduced(
            window_s=(t1 - t0) / 1e9, busy_s=0.01, h2d_bytes=1e6,
            h2d_seconds=0.001, h2d_union_s=0.001, d2d_union_s=0.0,
            device_ops=[["host-to-device_transfer", 0.001]],
            idle_gaps=[["feed.next", 0.5]], chips=chips)

    monkeypatch.setattr(trace_reduce, "reduce_dir", reduce_dir)


def run(root: str, workload: str, seed: int = 2**31 + 11,
        seconds: float = 0.5, trace: bool = False) -> dict:
    """The rest of a run after the look for a chip, on CPU devices."""
    import jax
    from perfbench import harness
    from perfbench import run as prun
    cell = harness.load_cell(root, workload)
    return asyncio.run(prun.run_cell(cell, seed, seconds, trace,
                                     jax.devices()[:cell.chips]))
