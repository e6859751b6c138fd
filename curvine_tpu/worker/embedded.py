"""A WorkerServer inside the process that owns the chip.

One process per chip: HBM tier-0 (`worker.hbm_capacity > 0`) claims every
local chip, so the worker that holds it runs in the JAX consumer's own
process. That consumer blocks — compiling the 1B train step holds its
thread for tens of seconds, a training loop never yields — so the worker
gets an event loop and a thread of its own. On the consumer's loop its
heartbeats starve, the master declares it lost after
`master.worker_lost_timeout_ms`, and the consumer's next read fails with
BlockNotFound (seen on the v5e during the first compile)."""

from __future__ import annotations

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.sdk.filesystem import LoopThread
from curvine_tpu.worker.server import WorkerServer


class EmbeddedWorker:
    """`start()` builds and starts a WorkerServer on a dedicated loop
    thread; `worker` is the server (its `hbm` tier is what in-process
    consumers `get` device arrays from); `stop()` shuts both down."""

    def __init__(self, conf: ClusterConf):
        self.conf = conf
        self.worker: WorkerServer | None = None
        self._lt: LoopThread | None = None

    def start(self) -> WorkerServer:
        async def up() -> WorkerServer:
            w = WorkerServer(self.conf)     # on the loop it will live on
            await w.start()
            return w

        self._lt = LoopThread(name="curvine-worker")
        try:
            self.worker = self._lt.run(up())
        except BaseException:
            self._lt.close()
            self._lt = None
            raise
        return self.worker

    def stop(self) -> None:
        if self._lt is None:
            return
        try:
            if self.worker is not None:
                self._lt.run(self.worker.stop())
        finally:
            self._lt.close()
            self._lt = None
            self.worker = None
