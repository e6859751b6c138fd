"""The event loop's own clock: how busy each asyncio loop is, and how
much of that it spent on a CPU.

One ``LoopMeter`` a loop, installed on first use (idempotently) by
taking the place of the loop's selector: ``select`` times itself and
everything else is the selector's own. What the loop does between two
selects is running callbacks, so the wall time outside ``select`` is
the loop's busy time. The loop thread's CPU clock (user + system, so a
page fault counts as CPU) is read at a select entry at least
``CPU_EVERY_S`` after the last read, not on every iteration: a read is
a system call (5.9 us on the chip host, in 10 ms steps there). busy −
cpu is the loop holding work without a CPU — waiting for the GIL,
blocked in a call, descheduled.

Each sink attached to a loop — a plain counters dict — gets the three
counters ``loop.busy_s``, ``loop.cpu_s`` and ``loop.runs`` (iterations)
while it is attached: a `CurvineClient` attaches its ``counters`` from
its first async entry point to ``close()``, an `RpcServer` its
registry's from ``start()`` to ``stop()``. A loop's time counts into
every sink on it. Always on, like `Timed`'s counters: no conf field.
Attach and detach settle what is owed up to that instant, so a reading
taken on the loop leaves out only the iteration in progress (and of the
CPU, what the last 10 ms hold)."""

from __future__ import annotations

import asyncio
import threading
import time

BUSY, CPU, RUNS = "loop.busy_s", "loop.cpu_s", "loop.runs"
CPU_EVERY_S = 0.01

_perf = time.perf_counter
_thread_cpu = time.thread_time
# what a selector is asked besides select(): bound once, not looked up
# through __getattr__ on every reader the loop adds or drops
_DELEGATED = ("register", "unregister", "modify", "get_key", "get_map",
              "close")


class LoopMeter:
    """Stands in for one loop's selector (``loop._selector``)."""

    def __init__(self, selector):
        self._selector = selector
        self._select = selector.select
        for name in _DELEGATED:
            setattr(self, name, getattr(selector, name))
        self._thread = threading.get_ident()
        self.sinks: tuple = ()
        self._mark = self._cpu_at = _perf()
        self._cpu = _thread_cpu()

    def __getattr__(self, name):
        return getattr(self._selector, name)

    def select(self, timeout=None):
        now = _perf()
        busy = now - self._mark
        for s in self.sinks:
            s[BUSY] = s.get(BUSY, 0.0) + busy
            s[RUNS] = s.get(RUNS, 0) + 1
        if now - self._cpu_at >= CPU_EVERY_S:
            self._charge_cpu(now)
        try:
            return self._select(timeout)
        finally:
            self._mark = _perf()

    def _charge_cpu(self, now: float) -> None:
        cpu = _thread_cpu()
        spent, self._cpu, self._cpu_at = cpu - self._cpu, cpu, now
        for s in self.sinks:
            s[CPU] = s.get(CPU, 0.0) + spent

    def _settle(self) -> None:
        """Charge the sinks what is owed up to now (on the loop thread
        only: elsewhere the thread CPU clock is another thread's)."""
        if threading.get_ident() != self._thread:
            return
        now = _perf()
        for s in self.sinks:
            s[BUSY] = s.get(BUSY, 0.0) + now - self._mark
        self._mark = now
        self._charge_cpu(now)

    def attach(self, sink: dict) -> "LoopMeter":
        if not any(s is sink for s in self.sinks):
            self._settle()
            for k in (BUSY, CPU, RUNS):     # all three, from the start
                sink.setdefault(k, 0)
            self.sinks = self.sinks + (sink,)
        return self

    def detach(self, sink: dict) -> None:
        if any(s is sink for s in self.sinks):
            self._settle()
            self.sinks = tuple(s for s in self.sinks if s is not sink)


def meter_of() -> "LoopMeter | None":
    """The running loop's meter, installed on first use; None for a loop
    with no selector to stand in for."""
    loop = asyncio.get_running_loop()
    sel = getattr(loop, "_selector", None)
    if sel is None:
        return None
    if not isinstance(sel, LoopMeter):
        sel = loop._selector = LoopMeter(sel)
    return sel


def attach(sink: dict) -> "LoopMeter | None":
    """Count the running loop into ``sink`` until ``detach``; the meter,
    or None where the loop cannot be metered."""
    m = meter_of()
    return m.attach(sink) if m is not None else None
