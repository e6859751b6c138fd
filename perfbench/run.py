#!/usr/bin/env python3
"""One cell, once:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Brings the system up (cv master child, embedded worker, client), makes the
cell's data from --seed, warms every shape, measures one window that opens
and closes on a completed unit of work, then compares what the timed path
left on the device with the seeded plain reference. The last line of
standard output is the result. No TPU, fewer chips than the cell asks
for, or no program beside this directory: a non-zero exit and no result."""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # set-up is counted from here

import argparse
import asyncio
import dataclasses
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


@dataclasses.dataclass
class Env:
    """What a driver is given: the cell, the seed, the chips, the
    cluster, and where to put spans."""
    cell: harness.Cell
    seed: int
    devices: list
    conf: object
    worker: object
    spans: harness.Spans

    def new_client(self):
        from curvine_tpu.client import CurvineClient
        return CurvineClient(self.conf)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


async def run_cell(cell: harness.Cell, seed: int, seconds: float,
                   trace: bool, devices: list, t0: float = _T0,
                   emit=say) -> dict:
    """Everything after the look for a chip. Returns the result as a
    dict (see harness.result_line); tests drive this on CPU devices."""
    import jax
    from perfbench.cluster import build_native, cluster
    from perfbench.compile_watch import compile_watch

    watch = compile_watch()
    spans = harness.Spans(annotate=trace)
    workdir = tempfile.mkdtemp(prefix="perfbench-")
    spec = cell.config["cluster"]
    tier_dir = tempfile.mkdtemp(prefix="perfbench-",
                                dir=spec["tier_parent"])
    trace_dir = os.path.join(workdir, "trace")
    try:
        native_s = build_native()
        async with cluster(workdir, tier_dir, spec) as (conf, worker):
            up_s = time.perf_counter() - t0
            env = Env(cell, seed, devices, conf, worker, spans)
            driver = cell.module("drivers", cell.traffic["driver"]) \
                .Driver(env)
            await driver.prepare()
            window = harness.Window(seconds)
            units = driver.units()
            tracing = False
            try:
                if trace:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                    tracing = True
                # the unit that opens the window
                await anext(units)
                window.complete(0)
                if trace:
                    # (a TraceAnnotation's time starts where it is made)
                    mark = jax.profiler.TraceAnnotation(harness.WINDOW_SPAN)
                    mark.__enter__()
                compiled_before = watch.snapshot()
                before = driver.counters()
                setup_s = window.opened - t0
                async for amount in units:
                    if window.complete(amount):
                        break
                if trace:
                    mark.__exit__(None, None, None)
                after = driver.counters()
                compiled_after = watch.snapshot()
            finally:
                if tracing:
                    jax.profiler.stop_trace()
                await units.aclose()
            peak = memory_peak(devices)
            await driver.release()
            t_ref = time.perf_counter()
            verdict = await asyncio.to_thread(driver.compare)
            reference_s = time.perf_counter() - t_ref
            notes = driver.setup_notes()
        master_said = master_complaints(os.path.join(workdir, "master.out"))
        run = harness.Run(
            cell=cell, window=window, setup_s=setup_s, spans=spans,
            before=before, after=after,
            compile_setup=compiled_before,
            compile_window={k: compiled_after[k] - compiled_before[k]
                            for k in compiled_after},
            memory_peak_bytes=peak, trace=None, notes=notes)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        breakdown = None
        if trace:
            from perfbench import trace_reduce
            run.trace = trace_reduce.reduce_dir(trace_dir, len(devices),
                                                spans.names())
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            breakdown = run.trace.breakdown()
        metrics = harness.read_metrics(run, trace)
        compared = dict(verdict["compared"])
        compared["window_compiles"] = (run.compile_window["compiles"], 0)
        correct = all(v <= lim for v, lim in compared.values())
        gaps = window.gaps()
        emit(f"[run] {cell.name} seed {seed}: window {window.duration:.3f} s"
             f", {window.units} units (longest gap {max(gaps):.3f} s, "
             f"{sum(g > 0.25 for g in gaps)} over 0.25 s), set-up {setup_s:.3f} s (native "
             f"{native_s:.2f}, cluster up at {up_s:.2f}, data "
             f"{notes['write_s']:.2f}), reference "
             f"{reference_s:.2f} s, compiles {compiled_after}")
        emit(f"[run] units by third of the window {thirds(window)}; gaps "
             f"over 0.25 s as (seconds after cluster up, length) "
             f"{stalls(window, t0 + up_s)}")
        for line in master_said:
            emit(f"[master] {line}")
        return {"correct": correct, "attempted": window.units,
                "failed": verdict["failed"], "metrics": metrics, "device": device,
                "compared": compared, "breakdown": breakdown,
                "units": {m["name"]: m["unit"] for m in
                          cell.end_to_end + cell.per_layer}}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(tier_dir, ignore_errors=True)


def thirds(window: harness.Window) -> list[int]:
    """Units completed in each third of the window: runs that differ
    while their thirds agree differ from process to process, and a
    longer window would not bring them together."""
    out = [0, 0, 0]
    for t in window.stamps:
        out[min(2, int(3 * (t - window.opened) / window.duration))] += 1
    return out


def stalls(window: harness.Window, since: float) -> list[tuple]:
    """Where each gap over 0.25 s began, in seconds after `since`: a
    stall that comes at the same age of the cluster in run after run is a
    periodic task of the program, one that does not is the host."""
    prev, out = window.opened, []
    for t in window.stamps:
        if t - prev > 0.25:
            out.append((round(prev - since, 2), round(t - prev, 3)))
        prev = t
    return out


def master_complaints(path: str, most: int = 6) -> list[str]:
    """The master child's slow-op, warning and error lines: whether a
    stall the client saw was the master's own."""
    try:
        with open(path, errors="replace") as f:
            said = [ln.strip()[:300] for ln in f
                    if "slow-op" in ln or "WARNING" in ln or "ERROR" in ln]
    except OSError:
        return []
    return said[-most:]


def find_chips(chips: int):
    """The cell's chips, or None: JAX has to find a TPU with at least as
    many devices as the cell asks for. Never a CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"perfbench: JAX found no device: {e}")
        return None
    if devices[0].platform != "tpu":
        say(f"perfbench: JAX found platform {devices[0].platform!r}, not a "
            f"TPU — refusing to run")
        return None
    if len(devices) < chips:
        say(f"perfbench: the cell asks for {chips} chips, JAX found "
            f"{len(devices)}")
        return None
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (the
    path is part of the key), unless JAX_COMPILATION_CACHE_DIR names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def execute(argv: list[str] | None = None) -> tuple[int, dict | None]:
    """Parse, look for the chips, run the cell: (exit code, result)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "curvine_tpu")):
        say("perfbench: no curvine_tpu package beside this directory — "
            "nothing to measure")
        return 2, None
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        say(f"perfbench: {e}")
        return 2, None
    devices = find_chips(cell.chips)
    if devices is None:
        return 2, None
    say(f"[setup] {len(devices)} chips found at "
        f"{time.perf_counter() - _T0:.2f} s")
    enable_compile_cache()
    from perfbench.peaks import peaks_of
    peaks_of(devices[0].device_kind)        # an unknown chip is an error
    try:
        return 0, asyncio.run(run_cell(cell, args.seed, args.seconds,
                                       bool(args.trace), devices))
    except Exception as e:  # noqa: BLE001 — the boundary: report, fail
        import traceback
        traceback.print_exc()
        say(f"perfbench: FAILED: {type(e).__name__}: {e}")
        return 1, None


def report(res: dict) -> None:
    """Each number compared beside its limit as the last lines of
    standard error, then the result as the last line of standard output."""
    sys.stderr.flush()
    harness.print_compared(res["compared"])
    sys.stderr.flush()
    print(harness.result_line(res), flush=True)


def main(argv: list[str] | None = None) -> int:
    rc, res = execute(argv)
    if res is not None:
        report(res)
    return rc


if __name__ == "__main__":
    sys.exit(main())
