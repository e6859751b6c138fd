"""What every cell shares: the window rule, spans, a cell found from its
files, and the result line. Nothing here knows a configuration, a traffic
mix or a per-layer metric by name."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_SPAN = "perfbench.window"     # the window, in the profiler's trace


# ------------------------------------------------------------------ window

class Window:
    """The one rule for every rate and tail. The window opens at the
    completion of a unit of work after warm-up and closes at the first
    completion at or after `seconds`; the unit that opens it is not
    counted, every later one is. All work over all that time: a stall
    inside the window lengthens it and lowers the rate."""

    def __init__(self, seconds: float, clock=time.perf_counter):
        self.seconds = float(seconds)
        self.clock = clock
        self.opened: float | None = None
        self.closed: float | None = None
        self.stamps: list[float] = []
        self.amounts: list[float] = []

    def complete(self, amount: float) -> bool:
        """A unit of `amount` (bytes) completed now. True once closed."""
        now = self.clock()
        if self.closed is not None:
            raise RuntimeError("the window has closed")
        if self.opened is None:
            self.opened = now
            return False
        self.stamps.append(now)
        self.amounts.append(float(amount))
        if now - self.opened >= self.seconds:
            self.closed = now
        return self.closed is not None

    @property
    def duration(self) -> float:
        if self.opened is None or self.closed is None:
            raise RuntimeError("the window has not closed")
        return self.closed - self.opened

    @property
    def units(self) -> int:
        return len(self.stamps)

    def rate(self) -> float:
        """Amount per second over the window's whole duration."""
        return sum(self.amounts) / self.duration

    def gaps(self) -> list[float]:
        """Seconds between consecutive completions, the opening one
        included: every gap of the window."""
        prev, out = self.opened, []
        for t in self.stamps:
            out.append(t - prev)
            prev = t
        return out


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; raises on an empty list."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's measure of how widely runs spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# ------------------------------------------------------------------- spans

class Spans:
    """Benchmark spans around calls into a layer, kept in memory. With
    `annotate` on (the traced run) each span is also written into the
    profiler's trace, so an idle gap of the device can be laid against
    what the host was doing. Off (the timed run) `span()` costs one
    attribute test."""

    def __init__(self, annotate: bool = False, clock=time.perf_counter):
        self.on = annotate
        self.clock = clock
        self.records: list[tuple[str, float, float, dict]] = []

    def span(self, name: str, **attrs):
        return _Span(self, name, attrs) if self.on else _NULL

    def add(self, name: str, t0: float, t1: float, **attrs) -> None:
        if self.on:
            self.records.append((name, t0, t1, attrs))

    def names(self) -> set[str]:
        return {r[0] for r in self.records}

    def named(self, name: str, t0: float | None = None,
              t1: float | None = None) -> list[tuple[float, float, dict]]:
        """Spans of that name, clipped to [t0, t1]."""
        out = []
        for n, a, b, attrs in self.records:
            if n != name:
                continue
            a = a if t0 is None else max(a, t0)
            b = b if t1 is None else min(b, t1)
            if b > a:
                out.append((a, b, attrs))
        return out

    def wrap(self, obj, method: str, name: str) -> None:
        """Put a span around every call of an async method of one object
        (an instance attribute: the class is left alone)."""
        if not self.on:
            return
        inner = getattr(obj, method)
        spans = self

        async def spanned(*a, **kw):
            with spans.span(name):
                return await inner(*a, **kw)

        setattr(obj, method, spanned)


class _Span:
    def __init__(self, spans: Spans, name: str, attrs: dict):
        self.spans, self.name, self.attrs = spans, name, attrs

    def __enter__(self):
        import jax.profiler
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = self.spans.clock()
        return self

    def __exit__(self, *exc):
        t1 = self.spans.clock()
        self.ann.__exit__(*exc)
        self.spans.records.append((self.name, self.t0, t1, self.attrs))
        return False


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def merged(intervals) -> list[tuple[float, float]]:
    """(start, end, ...) intervals → disjoint ones, in order."""
    out: list[tuple[float, float]] = []
    for a, b in sorted((a, b) for a, b, *_ in intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_seconds(intervals) -> float:
    """Total length covered by (start, end, ...) intervals, overlaps once."""
    return sum(b - a for a, b in merged(intervals))


# -------------------------------------------------------------------- cells

@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with the files its names lead to."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]        # the metrics this cell reports
    per_layer: list[dict]
    root: str                     # the checkout

    def module(self, kind: str, name: str):
        """perfbench/<kind>/<name>.py, loaded by path: names may hold
        dots and dashes."""
        return load_module(os.path.join(self.root, "perfbench", kind,
                                        name + ".py"))


def load_module(path: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: no such benchmark file")
    modname = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def _reports(metric: dict, cell: str, moved_here: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if moved_here is None:            # an end-to-end metric without a list
        return True
    return metric["moves"] in moved_here


def load_cell(root: str, workload: str) -> Cell:
    """Find a cell from BENCHMARK.json and the files its names point to.
    Everything a later PR adds is an entry there and files here."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(root, "perfbench", "traffic",
                                w["traffic"] + ".json")
    with open(traffic_path) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, None)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer,
                root)


# -------------------------------------------------------------- the result

def result_line(res: dict) -> str:
    """The run's last line of standard output, from what `run_cell`
    returns. `compared` comes last: each number that decided `correct`
    beside its limit."""
    out = {"correct": bool(res["correct"]),
           "attempted": int(res["attempted"]), "failed": int(res["failed"]),
           "metrics": {k: {"value": v, "unit": res["units"][k]}
                       for k, v in res["metrics"].items()},
           "device": res["device"]}
    if res.get("breakdown") is not None:
        out["breakdown"] = res["breakdown"]
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in res["compared"].items()}
    return json.dumps(out)


def print_compared(compared: dict, file=sys.stderr) -> None:
    for k, (v, lim) in compared.items():
        verdict = "ok" if v <= lim else "OVER"
        print(f"[compared] {k} = {v} (limit {lim}) {verdict}", file=file)


# ------------------------------------------------------- a run, as read

@dataclasses.dataclass
class Run:
    """What one run leaves for the metric readers: the window, the
    counters at its two ends, the benchmark's spans, the compile watch,
    and (traced run) the reduced trace."""
    cell: Cell
    window: Window
    setup_s: float
    spans: Spans
    before: dict
    after: dict
    compile_setup: dict
    compile_window: dict
    memory_peak_bytes: int
    trace: object | None
    notes: dict

    def delta(self, group: str, key: str) -> float:
        """Growth of one counter of one group ("client", "worker",
        "stages") over the window."""
        return self.after[group].get(key, 0) - self.before[group].get(key, 0)

    def moved(self, key: str) -> float:
        """Growth of one of the driver's own tallies over the window."""
        return self.after[key] - self.before[key]

    def spans_in_window(self, name: str):
        return self.spans.named(name, self.window.opened, self.window.closed)


def read_metrics(run: Run, traced: bool) -> dict:
    """Each metric through its own reader: the cell's per-layer metrics
    (perfbench/layer_metrics/<name>.py) of a traced run, its end-to-end
    metrics (perfbench/end_to_end/<name>.py) otherwise. A reader that
    finds nothing to read returns None and the metric is left out."""
    kind, specs = ("layer_metrics", run.cell.per_layer) if traced \
        else ("end_to_end", run.cell.end_to_end)
    out = {}
    for m in specs:
        value = run.cell.module(kind, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = float(value)
    return out
