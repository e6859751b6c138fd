"""Async job management (distributed UFS→cache load).

Parity: curvine-server/src/master/job/ (job_manager, job_runner, job_store,
job_worker_client). A load job enumerates files under a mounted UFS path,
creates one task per file, and dispatches tasks to live workers
(RpcCode.SUBMIT_TASK). Workers run the transfer and report progress back
(RpcCode.REPORT_TASK → JobManager.report_task)."""

from __future__ import annotations

import asyncio
import collections
import itertools
import logging
import uuid

from curvine_tpu.common import errors as err
from curvine_tpu.common.types import JobInfo, JobState, TaskInfo, now_ms
from curvine_tpu.rpc import RpcCode
from curvine_tpu.rpc.client import ConnectionPool
from curvine_tpu.rpc.frame import pack

log = logging.getLogger(__name__)

_LIVE = (JobState.PENDING, JobState.RUNNING)
# finished jobs kept queryable (job_status after the fact); older ones
# leave the table and the store. A client that loads on every miss
# submits a job a file: without a bound the table grows with the traffic.
MAX_FINISHED_JOBS = 1024


class JobManager:
    def __init__(self, fs, mounts, dispatch_interval_s: float = 0.2):
        self.fs = fs
        self.mounts = mounts
        self.jobs: dict[str, JobInfo] = {}
        # ids of finished jobs, oldest first (see _retire)
        self._finished: collections.deque[str] = collections.deque()
        # path -> id of the live load job of exactly that path
        self._live_loads: dict[str, str] = {}
        self.pool = ConnectionPool(size=1)
        self.dispatch_interval_s = dispatch_interval_s
        self._pending: asyncio.Queue[TaskInfo] = asyncio.Queue()
        self._rr = itertools.count()
        # rolling prefetch windows (docs/caching.md): (path, epoch) ->
        # job_id of the active kind="prefetch" job. The shard order and
        # high-water plan index live ONLY in RAM (job._order/_next) —
        # recovery recomputes them from the persisted (seed, epoch)
        self._prefetch: dict[tuple[str, int], str] = {}

    def submit(self, kind: str, path: str, recursive: bool = True,
               replicas: int = 1) -> JobInfo:
        if kind not in ("load", "export", "ec_convert"):
            raise err.Unsupported(f"job kind {kind!r}")
        job = JobInfo(job_id=uuid.uuid4().hex[:16], kind=kind, path=path,
                      state=JobState.PENDING, create_ms=now_ms(),
                      recursive=recursive, replicas=replicas)
        self.jobs[job.job_id] = job
        if kind == "load":
            self._live_loads[path] = job.job_id
        self._persist(job)
        self._plan(job)
        return job

    def submit_load_if_absent(self, path: str,
                              replicas: int = 1) -> tuple[str, str]:
        """A client's auto-cache on a miss: (job id, outcome). One live
        load a path, whoever asked first and however many ask meanwhile
        ("deduped"); else a new single-file load ("submitted")."""
        live = self.live_load(path)
        if live is not None:
            return live.job_id, "deduped"
        return self.submit("load", path, recursive=False,
                           replicas=replicas).job_id, "submitted"

    def live_load(self, path: str) -> JobInfo | None:
        """The load job of exactly `path` that has not finished, if any:
        what a client's auto-cache asks before it submits another."""
        job = self.jobs.get(self._live_loads.get(path, ""))
        return job if job is not None and job.state in _LIVE else None

    def live_loads(self) -> int:
        return len(self._live_loads)

    def _retire(self, job: JobInfo) -> None:
        """A job has reached a final state: it stays queryable until
        MAX_FINISHED_JOBS newer ones have finished, then leaves the
        table and the store."""
        if getattr(job, "_retired", False):
            return
        job._retired = True
        if self._live_loads.get(job.path) == job.job_id:
            del self._live_loads[job.path]
        self._finished.append(job.job_id)
        while len(self._finished) > MAX_FINISHED_JOBS:
            old = self._finished.popleft()
            if self.jobs.pop(old, None) is not None:
                try:
                    self.fs._log("job_del", {"job_id": old})
                except err.CurvineError as e:
                    log.warning("dropping finished job %s failed: %s",
                                old, e)

    def _plan(self, job: JobInfo) -> None:
        if job.kind == "load":
            fut = asyncio.ensure_future(
                self._plan_load(job, job.recursive, job.replicas))
        elif job.kind == "ec_convert":
            fut = asyncio.ensure_future(
                self._plan_ec_convert(job, job.recursive))
        elif job.kind == "prefetch":
            fut = asyncio.ensure_future(self._plan_prefetch(job))
        else:
            fut = asyncio.ensure_future(self._plan_export(job, job.recursive))
        fut.add_done_callback(lambda f: self._plan_done(job, f))

    # ---------------- epoch-aware prefetch ----------------

    def advise_prefetch(self, path: str, cursor: int = 0, window: int = 8,
                        epoch: int = 0, seed: int = 0) -> JobInfo:
        """PREFETCH_WINDOW entry: the client advises where its read
        cursor is (shard index into the deterministic epoch order) and
        how far ahead to warm. One rolling job per (path, epoch); an
        advancing cursor extends the planned window incrementally —
        already-warmed shards are never re-planned. Only the bounds are
        journaled; the order itself is a pure function of
        (sorted shard list, seed, epoch) via common/epoch.py."""
        window = max(1, int(window))
        cursor = max(0, int(cursor))
        key = (path, int(epoch))
        job = None
        jid = self._prefetch.get(key)
        if jid is not None:
            job = self.jobs.get(jid)
            if job is not None and job.state not in (JobState.PENDING,
                                                     JobState.RUNNING):
                job = None
        if job is None:
            # a new epoch retires this path's windows two epochs back —
            # the boundary pair (tail of e, head of e+1) stays active
            for (p, e), oid in list(self._prefetch.items()):
                if p == path and e < int(epoch) - 1:
                    old = self.jobs.get(oid)
                    if old is not None and old.state in (
                            JobState.PENDING, JobState.RUNNING):
                        old.state = JobState.COMPLETED
                        old.finish_ms = now_ms()
                        self._persist(old)
                    del self._prefetch[(p, e)]
            job = JobInfo(job_id=uuid.uuid4().hex[:16], kind="prefetch",
                          path=path, state=JobState.PENDING,
                          create_ms=now_ms(), cursor=cursor, window=window,
                          epoch=int(epoch), seed=int(seed))
            self.jobs[job.job_id] = job
            self._prefetch[key] = job.job_id
            self._persist(job)
            self._plan(job)
            return job
        moved = cursor > job.cursor or window != job.window
        job.cursor = max(job.cursor, cursor)
        job.window = window
        if moved:
            self._persist(job)           # bounds only — tasks stay local
            asyncio.ensure_future(self._extend_prefetch(job))
        return job

    async def _plan_prefetch(self, job: JobInfo) -> None:
        """(Re)build the in-RAM epoch order and plan the current window.
        On recovery this runs with job.tasks empty and job.cursor at the
        persisted read position: ONLY [cursor, cursor+window) is planned
        — unlike load jobs, a restart never re-walks the dataset."""
        from curvine_tpu.common.epoch import epoch_shard_order
        try:
            st = self.fs.file_status(job.path)
            if st.is_dir:
                shards = [s.path for s in self.fs.list_status(job.path)
                          if not s.is_dir]
            else:
                shards = [st.path]
            order = epoch_shard_order(shards, job.seed or None, job.epoch)
            if job.state not in (JobState.PENDING, JobState.RUNNING):
                return                # cancelled mid-plan: stay cancelled
            job._order = order                      # RAM only
            job._next = job.cursor                  # next index to plan
            job.total_files = len(order)
            if not order:
                job.state = JobState.COMPLETED
                job.finish_ms = now_ms()
                self._persist(job)
                return
            job.state = JobState.RUNNING
            await self._extend_prefetch(job)
        except Exception as e:  # noqa: BLE001 — job fails with message
            log.warning("prefetch job %s planning failed: %s",
                        job.job_id, e)
            job.state = JobState.FAILED
            job.message = str(e) or type(e).__name__
            job.finish_ms = now_ms()
            self._persist(job)

    async def _extend_prefetch(self, job: JobInfo) -> None:
        """Queue warm tasks for order[_next, min(cursor+window, total))."""
        order = getattr(job, "_order", None)
        if order is None or job.state not in (JobState.PENDING,
                                              JobState.RUNNING):
            return
        hi = min(job.cursor + job.window, len(order))
        for idx in range(getattr(job, "_next", job.cursor), hi):
            task = TaskInfo(task_id=uuid.uuid4().hex[:16],
                            job_id=job.job_id, path=order[idx],
                            kind="prefetch")
            job.tasks.append(task)
            await self._pending.put(task)
        job._next = max(getattr(job, "_next", job.cursor), hi)
        self._maybe_finish(job)

    def _plan_done(self, job: JobInfo, fut: asyncio.Future) -> None:
        """Backstop for a planner coroutine that died OUTSIDE its own
        try block (e.g. a broken ufs import). Without this the exception
        sits in the discarded future and the job reads PENDING forever."""
        if fut.cancelled():
            return
        e = fut.exception()
        if e is None or job.state not in (JobState.PENDING,
                                          JobState.RUNNING):
            return
        log.warning("%s job %s planner crashed: %s", job.kind,
                    job.job_id, e)
        job.state = JobState.FAILED
        job.message = str(e) or type(e).__name__
        job.finish_ms = now_ms()
        self._persist(job)

    def _persist(self, job: JobInfo) -> None:
        """Journal the job record (sans per-file tasks — a resumed
        master RE-PLANS instead of replaying task lists). Replicates to
        HA followers like any other namespace mutation."""
        wire = job.to_wire()
        wire["tasks"] = []
        try:
            self.fs._log("job_put", {"job": wire})
        except err.CurvineError as e:
            log.warning("persisting job %s failed: %s", job.job_id, e)
        if job.state not in _LIVE:
            # every path to a final state persists it, so this is the
            # one place a job joins the bounded history
            self._retire(job)

    def recover(self) -> int:
        """Resume interrupted jobs from the durable store (called when
        this master starts leading): PENDING/RUNNING jobs re-plan;
        finished ones stay queryable; finished jobs older than 7 days are
        pruned. Returns the number of jobs resumed."""
        resumed = 0
        cutoff = now_ms() - 7 * 24 * 3600 * 1000
        finished = []
        for wire in list(self.fs.store.iter_jobs()):
            job = JobInfo.from_wire(wire)
            if job.state in _LIVE:
                # the DURABLE state is the truth: re-plan even when an
                # in-RAM record exists (a demoted tenure drained its task
                # queue, so those tasks are gone). Load/export tasks are
                # idempotent, so a duplicate dispatch wastes work at most.
                job.state = JobState.PENDING
                job.tasks = []
                self.jobs[job.job_id] = job
                if job.kind == "prefetch":
                    # re-attach the rolling window so the client's next
                    # advise extends THIS job; _plan_prefetch resumes
                    # from the persisted cursor, not the dataset start
                    self._prefetch[(job.path, job.epoch)] = job.job_id
                elif job.kind == "load":
                    self._live_loads[job.path] = job.job_id
                self._plan(job)
                resumed += 1
                log.info("resuming %s job %s on %s", job.kind,
                         job.job_id, job.path)
            else:
                if job.finish_ms and job.finish_ms < cutoff:
                    try:
                        self.fs._log("job_del", {"job_id": job.job_id})
                    except err.CurvineError:
                        pass
                    self.jobs.pop(job.job_id, None)
                    continue
                if job.job_id not in self.jobs:
                    self.jobs[job.job_id] = job
                    finished.append(job)
        # finished jobs known only from the store join the bounded
        # history, oldest first
        for job in sorted(finished, key=lambda j: j.finish_ms):
            self._retire(job)
        return resumed

    async def _plan_export(self, job: JobInfo, recursive: bool) -> None:
        """Enumerate cached files under job.path → one export task each.
        Parity: curvine-cli/src/cmds/export.rs job flow."""
        try:
            self.mounts.resolve(job.path)   # must be under a mount
            files: list = []

            def walk(path: str) -> None:
                for st in self.fs.list_status(path):
                    if st.is_dir:
                        if recursive:
                            walk(st.path)
                    else:
                        files.append(st)

            st = self.fs.file_status(job.path)
            if st.is_dir:
                walk(job.path)
            else:
                files.append(st)
            if job.state != JobState.PENDING:
                return                # cancelled mid-plan: stay cancelled
            for f in files:
                task = TaskInfo(task_id=uuid.uuid4().hex[:16],
                                job_id=job.job_id, path=f.path,
                                kind="export", total_len=f.len)
                job.tasks.append(task)
                await self._pending.put(task)
            job.state = JobState.RUNNING if files else JobState.COMPLETED
            if not files:
                job.finish_ms = now_ms()
                self._persist(job)
        except Exception as e:  # noqa: BLE001 — job fails with message
            log.warning("export job %s planning failed: %s", job.job_id, e)
            job.state = JobState.FAILED
            job.message = str(e) or type(e).__name__
            job.finish_ms = now_ms()
            self._persist(job)

    async def _plan_load(self, job: JobInfo, recursive: bool,
                         replicas: int) -> None:
        """Enumerate UFS files under job.path → one task per file."""
        try:
            # inside the try: a missing/broken ufs backend must surface
            # as a FAILED job with a message, not a swallowed ImportError
            from curvine_tpu.ufs import create_ufs
            mount, ufs_uri = self.mounts.resolve(job.path)
            ufs = create_ufs(ufs_uri, properties=mount.properties)
            files = []
            st = await ufs.stat(ufs_uri)
            if st is None:
                raise err.FileNotFound(ufs_uri)
            if st.is_dir:
                async for f in ufs.walk(ufs_uri, recursive=recursive):
                    if not f.is_dir:
                        files.append(f)
            else:
                files.append(st)
            if job.state != JobState.PENDING:
                return                # cancelled mid-plan: stay cancelled
            for f in files:
                _, cv_path = self.mounts.reverse(f.path)
                task = TaskInfo(task_id=uuid.uuid4().hex[:16],
                                job_id=job.job_id, path=cv_path,
                                total_len=f.len)
                job.tasks.append(task)
                await self._pending.put(task)
            job.state = JobState.RUNNING
            if not files:
                job.state = JobState.COMPLETED
                job.finish_ms = now_ms()
                self._persist(job)
        except Exception as e:  # noqa: BLE001 — job fails with message
            log.warning("load job %s planning failed: %s", job.job_id, e)
            job.state = JobState.FAILED
            job.message = str(e) or type(e).__name__
            job.finish_ms = now_ms()
            self._persist(job)

    async def _plan_ec_convert(self, job: JobInfo, recursive: bool) -> None:
        """Walk job.path for complete, cold files marked with an EC
        storage class (policy.ec, `cv ec set-policy`) and plan one
        stripe per block: allocate + durably register cell ids
        (fs.ec_plan), place the k+m cells on distinct workers, and hand
        a converting worker the full plan. Blocks already striped are
        skipped, so the job is idempotent and resume-safe."""
        from curvine_tpu.common.conf import ECConf
        from curvine_tpu.common.ec import ECProfile
        try:
            econf = getattr(self, "ec_conf", None) or ECConf()
            cold_ms = econf.convert_cold_s * 1000
            files = []

            def walk(path: str) -> None:
                for st in self.fs.list_status(path):
                    if st.is_dir:
                        if recursive:
                            walk(st.path)
                    elif st.is_complete and st.storage_policy.ec:
                        files.append(st)

            st = self.fs.file_status(job.path)
            if st.is_dir:
                walk(job.path)
            elif st.is_complete and st.storage_policy.ec:
                files.append(st)
            if job.state != JobState.PENDING:
                return                # cancelled mid-plan: stay cancelled
            now = now_ms()
            planned = 0
            for f in files:
                if cold_ms and f.mtime > now - cold_ms:
                    continue          # still warm
                profile = ECProfile.parse(f.storage_policy.ec)
                plans = self._plan_file_stripes(f, profile)
                if not plans:
                    continue
                task = TaskInfo(task_id=uuid.uuid4().hex[:16],
                                job_id=job.job_id, path=f.path,
                                kind="ec_convert", total_len=f.len,
                                payload={"profile": profile.name,
                                         "blocks": plans})
                job.tasks.append(task)
                await self._pending.put(task)
                planned += 1
            job.state = JobState.RUNNING if planned else JobState.COMPLETED
            if not planned:
                job.finish_ms = now_ms()
                self._persist(job)
        except Exception as e:  # noqa: BLE001 — job fails with message
            log.warning("ec_convert job %s planning failed: %s",
                        job.job_id, e)
            job.state = JobState.FAILED
            job.message = str(e) or type(e).__name__
            job.finish_ms = now_ms()
            self._persist(job)

    def _plan_file_stripes(self, f, profile) -> list[dict]:
        """Per-block stripe plans for one file: journal cell ids, pick
        k+m target workers (distinct when the cluster allows — the
        placement policy spreads; smaller clusters wrap round-robin)."""
        node = self.fs.tree.resolve(f.path)
        if node is None:
            return []
        plans = []
        for bid in node.blocks:
            stripe = self.fs.ec_stripes.get(bid)
            if stripe is not None and stripe.get("state") == "committed":
                continue              # already striped
            meta = self.fs.blocks.get(bid)
            if meta is None or meta.len == 0 or not meta.locs:
                continue              # nothing to stripe / no source copy
            k, m = profile.k, profile.m
            cell_size = profile.cell_size(meta.len)
            workers = self.fs.workers.live_workers()
            chosen = self.fs.policy.choose(workers, k + m,
                                           needed=cell_size, min_count=1)
            targets = [chosen[i % len(chosen)] for i in range(k + m)]
            cell_ids = self.fs.ec_plan(bid, profile.name, k, m, cell_size)
            sources = []
            for wid in meta.locs:
                try:
                    w = self.fs.workers.get(wid)
                except err.CurvineError:
                    continue
                if w.state.value in (0, 2):
                    sources.append(w.address.to_wire())
            plans.append({
                "block_id": bid, "block_len": meta.len,
                "cell_size": cell_size, "sources": sources,
                "cells": [{"index": i, "block_id": cid,
                           "addr": targets[i].address.to_wire()}
                          for i, cid in enumerate(cell_ids)]})
        return plans

    async def run(self, leader_gate=None) -> None:
        was_leader = False
        while True:
            is_leader = leader_gate is None or leader_gate()
            if is_leader and not was_leader:
                self.recover()        # startup or just promoted: resume
            was_leader = is_leader
            try:
                task = await asyncio.wait_for(self._pending.get(), 1.0)
            except asyncio.TimeoutError:
                continue              # gate re-check tick
            if not is_leader:
                continue              # followers never dispatch
            job = self.jobs.get(task.job_id)
            if job is None or job.state in (JobState.CANCELLED, JobState.FAILED):
                continue
            try:
                await self._dispatch(task)
            except Exception as e:  # noqa: BLE001
                task.state = JobState.FAILED
                task.message = str(e)
                self._maybe_finish(job)

    async def _dispatch(self, task: TaskInfo) -> None:
        workers = self.fs.workers.live_workers()
        if not workers:
            # transient right after a master (re)start: workers register
            # on their next heartbeat — retry with backoff before failing
            task.attempts += 1
            if task.attempts <= 20:
                async def requeue():
                    await asyncio.sleep(min(0.5 * task.attempts, 3.0))
                    await self._pending.put(task)
                asyncio.ensure_future(requeue())
                return
            raise err.NoAvailableWorker("no live workers for load task")
        w = workers[next(self._rr) % len(workers)]
        task.worker_id = w.address.worker_id
        task.state = JobState.RUNNING
        conn = await self.pool.get(
            f"{w.address.ip_addr or w.address.hostname}:{w.address.rpc_port}")
        await conn.call(RpcCode.SUBMIT_TASK, data=pack({"task": task.to_wire()}))

    def report_task(self, task_wire: dict) -> None:
        t = TaskInfo.from_wire(task_wire)
        job = self.jobs.get(t.job_id)
        if job is None:
            raise err.JobNotFound(t.job_id)
        for i, existing in enumerate(job.tasks):
            if existing.task_id == t.task_id:
                job.tasks[i] = t
                break
        self._maybe_finish(job)

    def _maybe_finish(self, job: JobInfo) -> None:
        if job.state not in (JobState.RUNNING, JobState.PENDING):
            return
        if not job.tasks:
            # reachable mid-resume (tasks reset, re-plan in flight): an
            # empty set must not read as 'all tasks completed'
            return
        if job.kind == "prefetch" \
                and getattr(job, "_next", 0) < job.total_files:
            # the window hasn't reached the end of the epoch order yet —
            # the job is rolling, not done, even with all current tasks
            # complete (the client's next advise extends it)
            return
        states = {t.state for t in job.tasks}
        if states <= {JobState.COMPLETED}:
            job.state = JobState.COMPLETED
            job.finish_ms = now_ms()
            self._persist(job)
        elif JobState.FAILED in states and not (
                states & {JobState.PENDING, JobState.RUNNING}):
            job.state = JobState.FAILED
            job.finish_ms = now_ms()
            job.message = "; ".join(t.message for t in job.tasks
                                    if t.state == JobState.FAILED)[:500]
            self._persist(job)

    def status(self, job_id: str) -> JobInfo:
        job = self.jobs.get(job_id)
        if job is None:
            raise err.JobNotFound(job_id)
        return job

    def cancel(self, job_id: str) -> None:
        job = self.status(job_id)
        if job.state in (JobState.PENDING, JobState.RUNNING):
            job.state = JobState.CANCELLED
            job.finish_ms = now_ms()
            self._persist(job)
