"""The one rule for every rate and tail."""

import pytest

from perfbench.harness import (Window, percentile, spread, union_seconds)


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def drive(window, clock, steps):
    """steps: (seconds to pass, amount completed). → closed?"""
    closed = False
    for dt, amount in steps:
        clock.now += dt
        closed = window.complete(amount)
    return closed


def test_opens_and_closes_on_a_completion():
    clock = Clock()
    w = Window(10.0, clock)
    assert not drive(w, clock, [(3.0, 5)])         # opens; not counted
    assert w.opened == 103.0 and w.units == 0
    assert not drive(w, clock, [(4.0, 5), (4.0, 5)])
    assert drive(w, clock, [(4.0, 5)])             # 12 s ≥ 10 s: closes
    assert w.closed == 115.0 and w.duration == 12.0
    assert w.units == 3 and w.rate() == pytest.approx(15 / 12.0)
    assert w.gaps() == [4.0, 4.0, 4.0]
    with pytest.raises(RuntimeError):
        w.complete(1)


def test_one_unit_more_or_less_does_not_move_the_rate():
    """A fixed-length window over units of 4 s reads 2 or 3 of them; the
    aligned one reads the same rate wherever it started."""
    rates = []
    for seconds in (9.0, 11.0, 12.0):
        clock = Clock()
        w = Window(seconds, clock)
        while not drive(w, clock, [(4.0, 8)]):
            pass
        rates.append(w.rate())
    assert rates == [2.0, 2.0, 2.0]


def test_a_stall_inside_the_window_lowers_the_rate():
    def run(stall):
        clock = Clock()
        w = Window(10.0, clock)
        steps = [(1.0, 1)] * 5 + [(1.0 + stall, 1)] + [(1.0, 1)] * 20
        for step in steps:
            if drive(w, clock, [step]):
                break
        return w
    steady, stalled = run(0.0), run(6.0)
    assert steady.rate() == pytest.approx(1.0)
    assert stalled.rate() < 0.7 * steady.rate()
    assert max(stalled.gaps()) == pytest.approx(7.0)
    assert percentile(stalled.gaps(), 99.0) > 5.0


def test_not_closed_has_no_rate():
    w = Window(1.0, Clock())
    w.complete(0)
    with pytest.raises(RuntimeError):
        w.rate()


def test_percentile_spread_union():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile([1, 2], 99) == pytest.approx(1.99)
    with pytest.raises(ValueError):
        percentile([], 50)
    assert spread([10, 10, 10, 10, 10, 10]) == 0
    assert spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
