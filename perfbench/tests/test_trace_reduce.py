"""The reduction from a trace to numbers, on a trace recorded on a v5e
(PR 25's first traced run of feed-cosmoflow, thinned to the events the
reduction reads) and on hand-made traces where the answer is known."""

import os

import pytest

from perfbench import harness
from perfbench import trace_reduce as tr

FIXTURE = os.path.join(harness.HERE, "fixtures",
                       "feed-cosmoflow.v5e.trace.json.gz")
SPANS = {"client.fetch", "master.rpc", "feed.next", "consume"}


def test_recorded_trace():
    trace = tr.load_json(FIXTURE)
    r = tr.reduce(trace, 1, SPANS)
    assert r.chips == 1 and r.window_s == pytest.approx(8.068553067)
    # 665 batches of u8[1, 2828486], tiled T(4,128): 11,314,176 bytes each
    assert r.h2d_bytes == pytest.approx(665 * 11314176)
    assert r.h2d_seconds == pytest.approx(0.738531292)
    assert r.h2d_union_s == pytest.approx(0.66230757)       # two overlap
    assert r.h2d_union_s <= r.h2d_seconds
    assert 9e9 < r.h2d_bytes / r.h2d_union_s < 12e9          # a PCIe link
    # operations by "XLA Ops" alone: "XLA Modules" regroups the same time
    ops = dict(r.device_ops)
    assert ops["fusion.2"] == pytest.approx(0.033658388)
    assert ops["host-to-device_transfer"] == pytest.approx(r.h2d_seconds)
    assert not any(name.startswith("jit_fold") for name in ops)
    assert r.busy_s == pytest.approx(0.665487898)
    assert r.h2d_union_s <= r.busy_s <= r.h2d_union_s + 0.0344
    assert r.d2d_union_s == 0
    gaps = dict(r.idle_gaps)
    assert set(gaps) <= SPANS | {"_no_benchmark_span_"}     # no shard_args
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.idle_gaps[0][0] == "client.fetch"
    assert len(r.breakdown()["device_ops"]) <= 10


def ev(name, start, dur, **stats):
    return tr.Event(name, start, dur, stats)


def transfer(flow, chip, start, end, size):
    return [ev(tr.H2D_CALL, start - 5, 3, size=size, chip_id=chip, _p=flow),
            ev(tr.H2D_ISSUE, start, 2, chip_id=chip, _c=flow),
            ev(tr.H2D_DONE, end, 1, size=size, chip_id=chip, _c=flow)]


def two_chip_trace():
    host = {"python3": [ev(tr.WINDOW_SPAN, 1000, 1000),
                        ev("restore", 900, 2000),
                        ev("master.rpc", 1000, 300),
                        ev("shard_args", 1000, 1000)],
            "pjrt": transfer(1, 0, 1100, 1200, 4000)
            + transfer(2, 0, 1150, 1300, 6000)       # overlaps the first
            + transfer(3, 1, 1900, 2100, 8000)       # half outside
            + transfer(4, 1, 100, 200, 999)}         # before the window
    chip0 = {"XLA Ops": [ev("%fusion.1 = u32[] fusion(...)", 1250, 100),
                         ev("%all-reduce.3 = f32[8] all-reduce(...)",
                            1500, 100)],
             "XLA Modules": [ev("jit_f(1)", 1250, 350)]}
    chip1 = {"XLA Ops": [ev("%copy.2 = ...", 1000, 50)],
             "Async XLA Ops": [ev("%collective-permute-start.1 = ...",
                                  1600, 200)]}
    return tr.Trace({"/host:CPU": host, "/device:TPU:0": chip0,
                     "/device:TPU:1": chip1, "/device:TPU:2": {}})


def test_hand_made_two_chips():
    r = tr.reduce(two_chip_trace(), 2, {"restore", "master.rpc"})
    assert r.window_s == pytest.approx(1e-6)
    # chip 0: transfers 1100-1300, ops 1250-1350 and 1500-1600 → 350 ns;
    # chip 1: copy 1000-1050, permute 1600-1800, transfer 1900-2000 → 350
    assert r.busy_s == pytest.approx(350e-9)
    assert r.h2d_union_s == pytest.approx((200 + 100) / 2 * 1e-9)
    assert r.h2d_seconds == pytest.approx((100 + 150 + 100) * 1e-9)
    assert r.h2d_bytes == pytest.approx(4000 + 6000 + 8000 / 2)
    assert r.d2d_union_s == pytest.approx((100 + 200) / 2 * 1e-9)
    gaps = dict(r.idle_gaps)                # of chip 0
    assert gaps["master.rpc"] == pytest.approx(100e-9)      # 1000-1100
    assert gaps["restore"] == pytest.approx((150 + 400) * 1e-9)
    assert "shard_args" not in gaps


def test_what_is_missing_is_an_error():
    trace = two_chip_trace()
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce(trace, 4, set())
    del trace.planes["/host:CPU"]["python3"][0]
    with pytest.raises(ValueError, match="perfbench.window"):
        tr.reduce(trace, 2, set())
