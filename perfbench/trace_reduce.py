"""From the profiler's xplane to the numbers the metric readers use:
device busy time as the union of device work, host-to-device transfer
bytes and time, chip-to-chip copy time, and the device's idle gaps laid
against the benchmark spans that covered them.

The reduction works on a plain structure, `Trace`: planes → lines →
events (name, start_ns, duration_ns, stats). `load_xplane` makes it from
an .xplane.pb with nothing but JAX; `load_json` from the recorded fixtures
(the same structure thinned to the events read here, gzipped JSON), which
is how the tests check this file without a chip.

What a TPU v5e trace holds (looked at by hand, PR 25's first traced
runs, JAX 0.9.0 / libtpu 0.0.34):

- one plane per chip, "/device:TPU:<n>". Its line "XLA Ops" has one
  event per executed HLO operation; "XLA Modules" regroups the same time
  by program; "Async XLA Ops" holds what runs beside them (async copies
  and collectives). Transfers from the host are NOT on this plane.
- "/host:CPU", one line per thread. A host-to-device transfer is three
  events that share one flow id: `tpu::System::TransferToDevice` (stats
  `size`, `chip_id`, `_p` = id) where a PJRT task issues it,
  `…=>IssueEvent` (`_c` = id) when it is handed to the chip, and
  `…=>IssueEvent=>Done` (`_c` = id) when the chip reports it done. The
  transfer's time on the link is IssueEvent.start → Done.start; its bytes
  are `size`, the padded size it has on the device (a u8[1, n] batch is
  tiled T(4,128): four times its logical bytes).
- the benchmark's TraceAnnotations are on the line "python3" of the same
  plane; all planes share one clock, in ns from the start of the trace."""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

from perfbench.harness import WINDOW_SPAN, merged as _union

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_LINE = "python3"
WORK_LINES = ("XLA Ops", "Async XLA Ops")
H2D_CALL = "tpu::System::TransferToDevice"
H2D_ISSUE = H2D_CALL + "=>IssueEvent"
H2D_DONE = H2D_ISSUE + "=>Done"
# an operation of a device plane that moves data between chips
D2D = re.compile(r"all-reduce|all-gather|all-to-all|collective-permute|"
                 r"reduce-scatter|collective-broadcast|\bsend\b|\brecv\b|"
                 r"send-done|recv-done|device-to-device|d2d", re.I)
# host events that move data between chips (none seen yet: PR 25's
# four-chip trace holds only transfers from the host)
D2D_HOST = re.compile(r"TransferDeviceToDevice|DeviceToDevice|"
                      r"CopyToDevice|CrossHostTransfer", re.I)


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    planes: dict          # plane name → {line name → [Event]}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    if isinstance(v, (int, float, str)):
                        stats[k] = v
                events.append(Event(e.name, float(e.start_ns),
                                    float(e.duration_ns), stats))
    return Trace(planes)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return Trace({p: {ln: [Event(*e) for e in evs]
                      for ln, evs in lines.items()}
                  for p, lines in raw.items()})


def dump_json(trace: Trace, path: str, keep=None) -> None:
    """Write a trace as the fixture format; `keep(plane, line, event)`
    thins it."""
    raw = {p: {ln: [[e.name, e.start, e.dur, e.stats] for e in evs
                    if keep is None or keep(p, ln, e)]
               for ln, evs in lines.items()}
           for p, lines in trace.planes.items()}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)


def _clip(events, t0: float, t1: float):
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            yield a, b, e


def _length(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def _tidy(name: str) -> str:
    """An HLO line → the operation's own name: "%fusion.2 = (u32[]…"
    → "fusion.2"."""
    m = re.match(r"%?([A-Za-z0-9_.-]+)", name)
    return (m.group(1) if m else name)[:64]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float             # union of device work, mean over chips
    h2d_bytes: float          # all chips
    h2d_seconds: float        # summed link time of the transfers
    h2d_union_s: float        # mean over chips
    d2d_union_s: float        # mean over chips
    device_ops: list          # [[name, seconds]], longest first
    idle_gaps: list           # [[covering span, seconds]], longest first
    chips: int

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops[:10],
                "idle_gaps": self.idle_gaps[:10]}


def window_of(trace: Trace) -> tuple[float, float]:
    """[start, end) of the benchmark's window on the trace's clock."""
    for events in trace.planes.get(HOST_PLANE, {}).values():
        for e in events:
            if e.name == WINDOW_SPAN:
                return e.start, e.end
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def host_transfers(trace: Trace):
    """(chip, start, end, bytes) of every host-to-device transfer: the
    three events of one flow id joined."""
    size, issued, done = {}, {}, {}
    for events in trace.planes.get(HOST_PLANE, {}).values():
        for e in events:
            if e.name == H2D_CALL:
                size[e.stats.get("_p")] = (e.stats.get("size", 0),
                                           e.stats.get("chip_id", 0))
            elif e.name == H2D_ISSUE:
                issued[e.stats.get("_c")] = e.start
            elif e.name == H2D_DONE:
                done[e.stats.get("_c")] = e.start
    out = []
    for flow, start in issued.items():
        if flow in done and flow in size and done[flow] > start:
            nbytes, chip = size[flow]
            out.append((int(chip), start, done[flow], float(nbytes)))
    return out


def host_spans(trace: Trace, names: set[str], t0: float, t1: float):
    """The benchmark's annotations of those names, clipped to the window."""
    out = []
    for e in trace.planes.get(HOST_PLANE, {}).get(SPAN_LINE, []):
        if e.name in names:
            a, b = max(e.start, t0), min(e.end, t1)
            if b > a:
                out.append((a, b, e.name))
    return out


def reduce(trace: Trace, chips: int, span_names: set[str]) -> Reduced:
    t0, t1 = window_of(trace)
    planes = sorted((int(DEVICE_PLANE.match(p).group(1)), p)
                    for p in trace.planes if DEVICE_PLANE.match(p))
    if len(planes) < chips:
        raise ValueError(f"the trace holds {len(planes)} device planes, "
                         f"the cell ran on {chips} chips")
    planes = planes[:chips]
    transfers = host_transfers(trace)
    d2d_host = [(max(e.start, t0), min(e.end, t1))
                for events in trace.planes.get(HOST_PLANE, {}).values()
                for e in events if D2D_HOST.search(e.name)
                and min(e.end, t1) > max(e.start, t0)]
    busy = h2d_union = d2d_union = h2d_bytes = h2d_seconds = 0.0
    by_name: dict[str, float] = {}
    first_busy = None
    for chip, plane in planes:
        work, d2d = [], list(d2d_host)
        for line in WORK_LINES:
            for a, b, e in _clip(trace.planes[plane].get(line, []), t0, t1):
                work.append((a, b))
                name = _tidy(e.name)
                by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
                if D2D.search(e.name):
                    d2d.append((a, b))
        h2d = []
        for c, a, b, nbytes in transfers:
            if c != chip:
                continue
            ca, cb = max(a, t0), min(b, t1)
            if cb > ca:
                h2d.append((ca, cb))
                h2d_seconds += (cb - ca) / 1e9
                h2d_bytes += nbytes * (cb - ca) / (b - a)
        if h2d:
            by_name["host-to-device_transfer"] = by_name.get(
                "host-to-device_transfer", 0.0) + sum(
                    b - a for a, b in h2d) / 1e9
        merged = _union(work + h2d)
        if first_busy is None:
            first_busy = merged
        busy += _length(merged) / 1e9
        h2d_union += _length(_union(h2d)) / 1e9
        d2d_union += _length(_union(d2d)) / 1e9
    n = len(planes)
    # idle gaps of the first chip, by the shortest benchmark span that
    # covers each gap's middle
    spans = host_spans(trace, span_names, t0, t1)
    gaps: dict[str, float] = {}
    prev = t0
    for a, b in list(first_busy or []) + [(t1, t1)]:
        if a > prev:
            mid = (prev + a) / 2
            cover = [(sb - sa, nm) for sa, sb, nm in spans if sa <= mid < sb]
            name = min(cover)[1] if cover else "_no_benchmark_span_"
            gaps[name] = gaps.get(name, 0.0) + (a - prev) / 1e9
        prev = max(prev, b)
    return Reduced(
        window_s=(t1 - t0) / 1e9, busy_s=busy / n, h2d_bytes=h2d_bytes,
        h2d_seconds=h2d_seconds, h2d_union_s=h2d_union / n,
        d2d_union_s=d2d_union / n,
        device_ops=[[k, v] for k, v in
                    sorted(by_name.items(), key=lambda kv: -kv[1])],
        idle_gaps=[[k, v] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])],
        chips=n)


def reduce_dir(trace_dir: str, chips: int,
               span_names: set[str]) -> Reduced:
    return reduce(load_xplane(find_xplane(trace_dir)), chips, span_names)


def describe(trace: Trace, top: int = 8) -> str:
    """A look at a trace by hand: planes, lines, counts, the longest
    event names of each line and their stats' keys."""
    out = []
    for plane, lines in trace.planes.items():
        out.append(f"PLANE {plane}")
        for line, events in lines.items():
            total = sum(e.dur for e in events) / 1e9
            out.append(f"  LINE {line!r}: {len(events)} events, "
                       f"{total:.6f} s")
            agg: dict[str, list] = {}
            for e in events:
                ent = agg.setdefault(e.name, [0, 0.0, e])
                ent[0] += 1
                ent[1] += e.dur
            for name, (cnt, dur, e) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"      {name[:70]!r} x{cnt} {dur / 1e9:.6f} s "
                           f"first@{e.start:.0f} stats={e.stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(load_xplane(sys.argv[1])))
