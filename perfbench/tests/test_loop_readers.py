"""The readers of the event loops' own clock and of the fetch hand-off's
two halves (PR 37): their entries in BENCHMARK.json, the exact quotient
each makes of a window's counters, and their silence on a program that
keeps no such counter (the commit before PR 37)."""

import pytest

from perfbench import harness
from perfbench.tests import tiny

FEEDS = ["feed-cosmoflow", "feed-cosmoflow-ufs", "feed-unet3d"]
RESTORES = ["restore-olmoe-chip", "broadcast-olmoe-host4"]
LOOP = "rpc/ transport and the client's loop"
# name → (layer, better, unit, cells)
ENTRIES = {
    "client.loop_busy_share.feed": (LOOP, "lower", "share", FEEDS),
    "client.loop_busy_share.restore": (LOOP, "lower", "share", RESTORES),
    "client.loop_offcpu_share.feed": (LOOP, "lower", "share", FEEDS),
    "client.loop_offcpu_share.restore": (LOOP, "lower", "share", RESTORES),
    "worker.loop_busy_share.feed": ("worker/ tier store", "lower", "share",
                                    FEEDS),
    "worker.loop_busy_share.restore": ("worker/ tier store", "lower",
                                       "share", RESTORES),
    "client.resume_queue_ms.restore": ("client/ read ladder", "lower", "ms",
                                       RESTORES),
    "client.resume_wake_ms.restore": ("client/ read ladder", "lower", "ms",
                                      RESTORES),
    "client.fetch_cpu_share.restore": ("client/ read ladder", "higher",
                                       "share", RESTORES),
}
WINDOW_S = 4.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("root")))


def _run(cell, before: dict, after: dict):
    """A window of WINDOW_S seconds; `before` / `after` map a group
    ("client", "worker") to its counters at the two ends."""
    window = harness.Window(1.0, clock=iter((0.0, WINDOW_S)).__next__)
    window.complete(0)
    window.complete(10)
    empty = {"client": {}, "worker": {}, "stages": {}, "fetched_bytes": 0,
             "delivered": 0}
    return harness.Run(cell=cell, window=window, setup_s=0.0,
                       spans=harness.Spans(), before=dict(empty, **before),
                       after=dict(empty, **after, fetched_bytes=10),
                       compile_setup={}, compile_window={},
                       memory_peak_bytes=0, trace=None, notes={})


def _reader(root, name):
    cell = harness.load_cell(root, ENTRIES[name][3][0])
    return cell, cell.module("layer_metrics", name).read


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry(root, name):
    layer, better, unit, cells = ENTRIES[name]
    for cell_name in cells:
        cell = harness.load_cell(root, cell_name)
        entry = next(m for m in cell.per_layer if m["name"] == name)
        assert entry["source"] == "program_counter"
        assert entry["layer"] == layer and entry["better"] == better
        assert entry["unit"] == unit and entry["workloads"] == cells
        assert entry["moves"] == ("into_hbm_gbps" if cells == FEEDS
                                  else "restore_gbps")
    # the reshard cell's metrics are a benchmark PR's to change
    cell = harness.load_cell(root, "reshard-olmoe-host4")
    assert name not in {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("kind", ["feed", "restore"])
@pytest.mark.parametrize("group", ["client", "worker"])
def test_busy_share(root, kind, group):
    cell, read = _reader(root, f"{group}.loop_busy_share.{kind}")
    assert read(_run(cell, {group: {"loop.busy_s": 1.0}},
                     {group: {"loop.busy_s": 3.0}})) == 2.0 / WINDOW_S
    # the other group's loop is not this one
    other = "worker" if group == "client" else "client"
    assert read(_run(cell, {other: {"loop.busy_s": 1.0}},
                     {other: {"loop.busy_s": 3.0}})) is None
    assert read(_run(cell, {}, {})) is None


@pytest.mark.parametrize("kind", ["feed", "restore"])
def test_offcpu_share(root, kind):
    cell, read = _reader(root, f"client.loop_offcpu_share.{kind}")
    before = {"loop.busy_s": 1.0, "loop.cpu_s": 0.5}
    assert read(_run(cell, {"client": before},
                     {"client": {"loop.busy_s": 3.0, "loop.cpu_s": 1.5}})) \
        == 1.0 / WINDOW_S
    # the CPU clock is read every 10 ms, the wall every iteration: a
    # window that ends between two CPU reads is floored at 0, not below
    assert read(_run(cell, {"client": before},
                     {"client": {"loop.busy_s": 1.2, "loop.cpu_s": 0.8}})) \
        == 0.0
    assert read(_run(cell, {"client": {"loop.busy_s": 1.0}},
                     {"client": {"loop.busy_s": 3.0}})) is None
    assert read(_run(cell, {}, {})) is None


@pytest.mark.parametrize("part", ["queue", "wake"])
def test_resume_halves(root, part):
    cell, read = _reader(root, f"client.resume_{part}_ms.restore")
    key = f"read.resume.{part}.s"
    assert read(_run(cell, {"client": {key: 1.0, "read.files": 916}},
                     {"client": {key: 1.916, "read.files": 1832}})) \
        == pytest.approx(1.0, rel=1e-12)
    # the parent commit: resume counted whole, its halves not
    assert read(_run(cell, {"client": {"read.phase.resume.s": 1.0,
                                       "read.files": 916}},
                     {"client": {"read.phase.resume.s": 2.0,
                                 "read.files": 1832}})) is None
    # no file opened in the window
    assert read(_run(cell, {"client": {key: 1.0, "read.files": 916}},
                     {"client": {key: 1.0, "read.files": 916}})) is None


def test_fetch_cpu_share(root):
    cell, read = _reader(root, "client.fetch_cpu_share.restore")
    # the CPU clock read on some steps: their CPU over their own wall
    after = {"read.phase.grant.s": 24.0, "read.phase.grant.cpu_s": 0.5,
             "read.phase.grant.cpu_wall_s": 3.0,
             "read.phase.map.s": 8.0, "read.phase.map.cpu_s": 0.4,
             "read.phase.map.cpu_wall_s": 1.0,
             "read.phase.verify.s": 8.0, "read.phase.verify.cpu_s": 0.6,
             "read.phase.verify.cpu_wall_s": 1.0,
             "read.phase.resume.s": 9.0}
    assert read(_run(cell, {}, {"client": after})) == 1.5 / 5.0
    before = {k: v / 2 for k, v in after.items()}
    assert read(_run(cell, {"client": before}, {"client": after})) \
        == pytest.approx(0.75 / 2.5, rel=1e-12)
    # the parent commit: the steps' wall counted, their CPU not
    assert read(_run(cell, {}, {"client": {"read.phase.grant.s": 3.0}})) \
        is None
    # no step's CPU clock read in the window
    assert read(_run(cell, {"client": after}, {"client": after})) is None
