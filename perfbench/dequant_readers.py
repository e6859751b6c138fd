"""Arithmetic of the readers of an fp8 load's expansion on the chip
(`load_safetensors(..., dequant=...)`: each F8_E4M3 weight and its F32
block scales placed as they are stored and expanded to bf16 by one
kernel, `fp8_block_dequant`, on the weight's device).

- The kernel's share of its HBM roofline: the bytes the expansion of a
  load must move (each fp8 code read at 1 B, each scale at 4 B, each
  bf16 written at 2 B; `kernel_bytes` of the cell's generator, from the
  configuration's shapes, not from the program's counters) times the
  loads completed in the window, over the window's device seconds of the
  kernel's operations (the trace's `fp8_block_dequant*` ops, a name
  taken from a traced TPU v5e run and no other program of the cell's)
  times the chip's published HBM bandwidth; in %. HBM bounds it: the
  kernel does one multiply an element.
- The loop's cost to dispatch one weight's expansion: Δckpt.dequant.s
  over Δckpt.dequant.n (client counters), in ms.
- The share of the bytes read that the expansion took in: Δ
  ckpt.dequant.bytes_in (fp8 weights and their scales) over Δckpt.bytes
  (every range read): the engagement count, 0.686 at the cell's shapes.

A program without the counters, a window with no expansion, or a trace
with no such op gives nothing to read: None, never 0."""

from __future__ import annotations

KERNEL = "fp8_block_dequant"


def kernel_seconds(trace) -> float:
    return sum(s for name, s in trace.device_ops
               if name.split(".")[0] == KERNEL)


def roofline_pct(run):
    if run.trace is None:
        return None
    busy = kernel_seconds(run.trace)
    if busy <= 0 or run.window.units <= 0:
        return None
    import jax
    from perfbench.peaks import peaks_of
    gen = run.cell.module("generators", run.cell.config["generator"])
    moved = gen.kernel_bytes(run.cell.config) * run.window.units
    peak = peaks_of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / (busy * peak)


def dispatch_ms(run):
    n = run.delta("client", "ckpt.dequant.n")
    if n <= 0:
        return None
    return run.delta("client", "ckpt.dequant.s") / n * 1e3


def byte_share(run):
    read = run.delta("client", "ckpt.bytes")
    if "ckpt.dequant.bytes_in" not in run.after["client"] or read <= 0:
        return None
    return run.delta("client", "ckpt.dequant.bytes_in") / read
