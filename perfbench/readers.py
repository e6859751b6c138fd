"""Arithmetic shared by the per-layer metric readers in
layer_metrics/<name>.py. Each reader takes a harness.Run and returns a
number, or None where the run holds nothing to read (the timed run keeps
no spans and no trace; a cell may lack the counter)."""

from __future__ import annotations

from perfbench.harness import percentile, union_seconds


def span_union_share(run, name: str):
    """Share of the window covered by spans of that name."""
    if not run.spans.on:
        return None
    return union_seconds(run.spans_in_window(name)) / run.window.duration


def span_sum_share(run, name: str, lanes: int):
    """Summed span time over the window times `lanes` concurrent lanes:
    how much of the lanes' time went into that span."""
    if not run.spans.on:
        return None
    total = sum(b - a for a, b, _ in run.spans_in_window(name))
    return total / (run.window.duration * lanes)


def counter_rate(run, group: str, key: str, scale: float = 1.0):
    """Growth of a program counter over the window, per second. A
    counter the program has not touched yet counts 0."""
    return run.delta(group, key) / run.window.duration / scale


def zero_copy_share(run):
    """Bytes served as zero-copy views of a sealed shm mapping, over all
    bytes the driver's reads fetched in the window."""
    fetched = run.moved("fetched_bytes")
    if fetched <= 0:
        return None
    return run.delta("client", "read.zero_copy_bytes") / fetched


def stage_share(run, stage: str):
    """A StepProfiler stage's seconds in the window over the window."""
    key = f"stage.{stage}"
    if key not in run.after["stages"]:
        return None
    return run.delta("stages", key) / run.window.duration


def gap_p50_ms(run):
    return percentile(run.window.gaps(), 50.0) * 1e3


def whole_restores(run):
    """(start, end, tensor spans) of each restore that lies whole inside
    the window."""
    out = []
    tensors = run.spans.named("restore.tensor")
    for a, b, _ in run.spans.named("restore.whole"):
        if a >= run.window.opened and b <= run.window.closed + 1e-6:
            out.append((a, b, [t for t in tensors if a <= t[0] and t[1] <= b]))
    return out


def tensors_per_s(run):
    if not run.spans.on:
        return None
    return len(run.spans_in_window("restore.tensor")) / run.window.duration


def multiblock_share(run):
    """Share of the restores' wall time during which only tensors larger
    than one block were still outstanding (from the last single-block
    tensor's close to the last multi-block tensor's)."""
    if not run.spans.on:
        return None
    wall = only_multi = 0.0
    for a, b, tensors in whole_restores(run):
        wall += b - a
        small = [t[1] for t in tensors if not t[2]["multiblock"]]
        multi = [t[1] for t in tensors if t[2]["multiblock"]]
        if multi:
            only_multi += max(0.0, max(multi) - max(small, default=a))
    return only_multi / wall if wall > 0 else None


# ------------------------------------------------------- from the trace

def h2d_gbps(run):
    """Bytes (as laid out on the device) of the trace's host-to-device
    transfers over the time in which one was in flight, chip by chip: the
    link's rate while it is used."""
    t = run.trace
    if t is None or t.h2d_union_s <= 0 or t.h2d_bytes <= 0:
        return None
    return t.h2d_bytes / (t.h2d_union_s * t.chips) / 1e9


def link_busy_share(run):
    """Union of the host-to-device transfers over the traced window,
    averaged over the chips."""
    t = run.trace
    if t is None or t.h2d_union_s <= 0:
        return None
    return t.h2d_union_s / t.window_s


def ici_copy_share(run):
    """Union of device-to-device copies and collectives over the traced
    window, averaged over the chips. 0 is a reading: nothing crossed."""
    t = run.trace
    if t is None:
        return None
    return t.d2d_union_s / t.window_s


def device_idle_share(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s


def peak_hbm_gb(run):
    if run.memory_peak_bytes <= 0:
        return None
    return run.memory_peak_bytes / 1e9
