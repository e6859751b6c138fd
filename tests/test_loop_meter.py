"""The event loop's own clock (obs/loop_meter.py): busy seconds outside
the selector's select(), the loop thread's CPU seconds beside them, and
the sinks each loop counts into — a client from its first call to its
close, a server's registry while it runs. Each test holds its own time
limit; no sleep is longer than 0.3 s."""

import asyncio
import threading
import time

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.obs import loop_meter
from curvine_tpu.obs.loop_meter import BUSY, CPU, RUNS
from curvine_tpu.sdk.filesystem import LoopThread
from curvine_tpu.testing import MiniCluster

LIMIT_S = 30


def _spin(seconds: float) -> None:
    """Pure Python work that holds the GIL, never a sleep."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


async def _metered(body) -> dict:
    """Run ``body(sink)`` with ``sink`` attached to the running loop;
    the sink and the window's wall in it."""
    sink: dict = {}
    m = loop_meter.attach(sink)
    assert m is not None and loop_meter.meter_of() is m
    t0 = time.perf_counter()
    await asyncio.wait_for(body(sink), LIMIT_S)
    await asyncio.sleep(0)         # a select after the body: counted
    m.detach(sink)
    sink["wall"] = time.perf_counter() - t0
    return sink


async def test_a_loop_that_mostly_sleeps_reads_idle():
    async def body(sink):
        for _ in range(5):
            await asyncio.sleep(0.05)

    s = await _metered(body)
    assert s[RUNS] >= 5
    assert 0 <= s[BUSY] / s["wall"] < 0.2
    assert 0 <= s[CPU] <= s[BUSY] + 0.01


async def test_callbacks_that_spin_read_busy():
    async def body(sink):
        loop = asyncio.get_running_loop()
        for _ in range(20):
            loop.call_soon(_spin, 0.01)
        await asyncio.sleep(0)
        await asyncio.sleep(0)

    s = await _metered(body)
    assert s[BUSY] >= 0.19
    assert s[BUSY] / s["wall"] > 0.8


def _off_cpu_share(spinning_thread: bool) -> float:
    """(busy − cpu) / busy of a loop whose callback spins 0.2 s, with or
    without a pure-Python thread beside it taking the GIL away."""
    stop = threading.Event()

    def rival():
        while not stop.is_set():
            pass

    async def body(sink):
        t = None
        if spinning_thread:
            t = threading.Thread(target=rival, daemon=True)
            t.start()
        try:
            _spin(0.2)
            await asyncio.sleep(0)    # the select that charges the CPU
        finally:
            stop.set()
            if t is not None:
                t.join(LIMIT_S)

    s = asyncio.run(_metered(body))
    return (s[BUSY] - s[CPU]) / s[BUSY]


def test_busy_without_a_cpu_is_the_gil_held_elsewhere():
    assert _off_cpu_share(spinning_thread=True) > 0.2
    # alone, busy is on the CPU; the least of three tries, so that a
    # host busy with other tests does not deschedule the reading away
    assert min(_off_cpu_share(spinning_thread=False)
               for _ in range(3)) < 0.1


async def test_a_client_counts_its_loop_until_close(tmp_path):
    conf = ClusterConf()
    conf.obs.enabled = False            # the meter is not tracing
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           conf=conf) as mc:
        c = mc.client()
        assert BUSY not in c.counters    # nothing run yet
        await asyncio.wait_for(c.write_all("/lm/a", b"x" * 4096), LIMIT_S)
        r = await c.open("/lm/a")
        assert await r.read_all() == b"x" * 4096
        await r.close()
        asyncio.get_running_loop().call_soon(_spin, 0.05)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert c.counters[BUSY] >= 0.05 and c.counters[RUNS] > 0
        assert 0 <= c.counters[CPU]
        # the server's registries count the same loop while they run
        assert mc.master.metrics.counters[BUSY] >= c.counters[BUSY]
        assert mc.workers[0].metrics.counters[BUSY] >= 0.05
        await c.close()
        after = {k: c.counters[k] for k in (BUSY, CPU, RUNS)}
        asyncio.get_running_loop().call_soon(_spin, 0.05)
        await asyncio.sleep(0.02)
        assert {k: c.counters[k] for k in (BUSY, CPU, RUNS)} == after
        assert mc.master.metrics.counters[BUSY] >= after[BUSY] + 0.05


async def test_two_loops_count_apart():
    lt = LoopThread(name="lm-test")
    try:
        theirs: dict = {}

        async def attach_there():
            return loop_meter.attach(theirs)

        their_meter = lt.run(attach_there(), timeout=LIMIT_S)

        async def body(sink):
            lt.loop.call_soon_threadsafe(_spin, 0.2)
            await asyncio.sleep(0.3)

        ours = await _metered(body)
        assert their_meter is not loop_meter.meter_of()
        # one more iteration there, so its spin is charged
        lt.run(asyncio.sleep(0), timeout=LIMIT_S)
        assert theirs[BUSY] >= 0.19
        assert ours[BUSY] < 0.1
        # the meter is installed once a loop, whoever attaches
        assert lt.run(attach_there(), timeout=LIMIT_S) is their_meter
    finally:
        lt.close()
        assert not lt.thread.is_alive()
        lt.loop.close()
