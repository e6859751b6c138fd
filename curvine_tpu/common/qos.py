"""Multi-tenant QoS: admission control and overload shedding.

The front door (S3 gateway, native clients) derives a *tenant id* and
carries it in the RPC header beside ``deadline_ms``/``trace_ctx``
(``TENANT_KEY``), so the master and worker dispatch loops see who is
calling. Admission is checked *before* a request queues — the tail-
latency literature is unambiguous that overload must be rejected at the
door, with the server telling clients how to back off (Dean & Barroso,
"The Tail at Scale", CACM 2013; Zhou et al., DAGOR, SoCC 2018):

  * **Token-bucket quotas**, hierarchical: global → tenant → op-class
    (meta / read / write). A rejection is the retryable ``Throttled``
    error carrying ``retry_after_ms`` — the instant the bucket will
    have a token again — which the gateway surfaces as HTTP 503 +
    ``Retry-After`` (S3 ``SlowDown``) and ``RetryPolicy`` honors
    instead of blind exponential backoff.
  * **Inflight caps** per tenant bound queue memory independently of
    rate.
  * **Overload shedding**: a load monitor (admitted-inflight depth +
    the fraction of recent completions slower than ``obs.slow_op_ms``)
    raises a shed level under pressure; tenants whose priority is below
    the level are rejected first (lowest priority first, DAGOR-style).
  * **Dead-on-arrival drop**: a request whose remaining deadline budget
    is smaller than the op class's estimated service time is failed
    immediately — the PR 2 "expired" fast-fail generalized to "will
    expire".

Everything here is synchronous and allocation-light: the un-throttled
hot path is a handful of float compares. The controller is injected
into ``RpcServer`` as
``server.qos`` the same way ``obs``/``metrics``/``watchdog`` are.
"""

from __future__ import annotations

import contextlib
import contextvars
import time

from curvine_tpu.common.errors import RpcTimeout, Throttled

# reserved header field carrying the caller's tenant id (rides the same
# rail as deadline_ms / trace_ctx; stamped once at the front door)
TENANT_KEY = "tenant"
DEFAULT_TENANT = "default"

# op classes for the third bucket layer
META, READ, WRITE = "meta", "read", "write"
OP_CLASSES = (META, READ, WRITE)

# ambient tenant identity (mirrors obs.trace.current_ctx): the gateway
# sets it per HTTP request, native clients set it once from conf; every
# outbound RPC stamps it into the header in Connection._launch
_tenant_var: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "curvine_tenant", default=None)
# process-wide fallback for single-tenant client processes where the
# constructing task is not an ancestor of the calling tasks
_process_tenant: str | None = None


def current_tenant() -> str | None:
    t = _tenant_var.get()
    return t if t is not None else _process_tenant


def set_process_tenant(name: str | None) -> None:
    global _process_tenant
    _process_tenant = name or None


@contextlib.contextmanager
def tenant_scope(name: str | None):
    tok = _tenant_var.set(name)
    try:
        yield
    finally:
        _tenant_var.reset(tok)


def classify(code: int) -> str | None:
    """Map an RpcCode to its op class, or None for cluster-internal
    codes that are exempt from tenant admission (heartbeats, raft,
    replication, shard 2PC, metrics/span collection): throttling the
    control plane under overload would turn congestion into outage."""
    return _OP_CLASS.get(int(code))


def _op_class_table() -> dict[int, str]:
    from curvine_tpu.rpc.codes import RpcCode as C
    reads = {C.OPEN_FILE, C.FILE_STATUS, C.LIST_STATUS, C.EXISTS,
             C.GET_BLOCK_LOCATIONS, C.GET_BLOCK_LOCATIONS_BATCH,
             C.GET_LOCK, C.LIST_LOCK,
             C.LIST_OPTIONS, C.CONTENT_SUMMARY, C.GET_MOUNT_TABLE,
             C.GET_MOUNT_INFO, C.GET_JOB_STATUS,
             C.READ_BLOCK, C.GET_BLOCK_INFO, C.SC_READ_REPORT}
    writes = {C.MKDIR, C.DELETE, C.CREATE_FILE, C.APPEND_FILE, C.RENAME,
              C.ADD_BLOCK, C.COMPLETE_FILE, C.SET_ATTR, C.SYMLINK, C.LINK,
              C.RESIZE_FILE, C.FREE, C.CREATE_FILES_BATCH,
              C.ADD_BLOCKS_BATCH, C.COMPLETE_FILES_BATCH, C.META_BATCH,
              C.SET_LOCK, C.MOUNT, C.UNMOUNT, C.UPDATE_MOUNT,
              C.SUBMIT_JOB, C.CANCEL_JOB, C.PREFETCH_WINDOW,
              C.WRITE_BLOCK, C.WRITE_BLOCKS_BATCH, C.WRITE_COMMITS_BATCH,
              C.DELETE_BLOCK, C.SC_WRITE_OPEN, C.SC_WRITE_COMMIT,
              C.SC_WRITE_ABORT}
    # ASSIGN_WORKER sits on the write path (placement for a new block)
    writes.add(C.ASSIGN_WORKER)
    metas = {C.GET_MASTER_INFO, C.HEARTBEAT}
    table: dict[int, str] = {}
    for c in reads:
        table[int(c)] = READ
    for c in writes:
        table[int(c)] = WRITE
    for c in metas:
        table[int(c)] = META
    # META is the *namespace* class: cheap point lookups. Reclassify the
    # pure-metadata reads there so a scan-heavy tenant (LIST_STATUS) and
    # a stat-heavy tenant share one bucket, distinct from data reads.
    for c in (C.FILE_STATUS, C.EXISTS, C.LIST_STATUS, C.LIST_OPTIONS,
              C.CONTENT_SUMMARY, C.GET_LOCK, C.LIST_LOCK,
              C.GET_MOUNT_TABLE, C.GET_MOUNT_INFO, C.GET_JOB_STATUS):
        table[int(c)] = META
    return table


_OP_CLASS: dict[int, str] = {}


def _ensure_table() -> None:
    # built lazily to avoid a qos ↔ codes import cycle at module load
    if not _OP_CLASS:
        _OP_CLASS.update(_op_class_table())


class TokenBucket:
    """Classic token bucket on a monotonic clock. ``rate <= 0`` means
    unlimited (the bucket always admits — the conf default, so wiring
    QoS in changes nothing until quotas are set)."""

    __slots__ = ("rate", "burst", "tokens", "_last")

    def __init__(self, rate: float, burst: float = 0.0,
                 now: float | None = None):
        self.rate = float(rate)
        # default burst: one second's worth of tokens (min 1)
        self.burst = float(burst) if burst > 0 else max(1.0, self.rate)
        self.tokens = self.burst
        self._last = time.monotonic() if now is None else now

    def _refill(self, now: float) -> None:
        dt = now - self._last
        if dt > 0:
            self.tokens = min(self.burst, self.tokens + dt * self.rate)
            self._last = now

    def try_acquire(self, n: float = 1.0,
                    now: float | None = None) -> float:
        """Take ``n`` tokens. Returns 0.0 on success, else the seconds
        until ``n`` tokens will be available (the retry-after hint)."""
        if self.rate <= 0:
            return 0.0
        if now is None:
            now = time.monotonic()
        self._refill(now)
        if self.tokens >= n:
            self.tokens -= n
            return 0.0
        return (n - self.tokens) / self.rate

    def debit(self, n: float, now: float | None = None) -> None:
        """Take ``n`` tokens of work already admitted, into debt where
        the bucket holds fewer: the calls that follow wait it out."""
        if self.rate > 0:
            self._refill(time.monotonic() if now is None else now)
            self.tokens -= n

    def refund(self, n: float = 1.0) -> None:
        """Give back tokens taken by an inner level that then rejected
        (hierarchical acquire must not charge for work never admitted)."""
        if self.rate > 0:
            self.tokens = min(self.burst, self.tokens + n)


class TenantState:
    """Per-tenant buckets, inflight count and stats."""

    __slots__ = ("name", "priority", "inflight_cap", "bucket", "classes",
                 "inflight", "admitted", "throttled", "shed",
                 "_win_start", "_win_count", "last_qps", "tier0_bytes")

    def __init__(self, name: str, qps: float, burst: float, priority: int,
                 inflight_cap: int, shares: dict[str, float],
                 now: float | None = None, tier0_bytes: int = 0):
        self.name = name
        self.priority = priority
        self.inflight_cap = inflight_cap
        # tier-0 cache partition (docs/caching.md): byte quota for this
        # tenant's committed blocks on the MEM-and-faster tiers; 0 = no
        # partition. Enforced by BlockStore eviction preferring
        # over-quota tenants' blocks first — a soft partition, so idle
        # capacity stays usable by anyone.
        self.tier0_bytes = tier0_bytes
        self.bucket = TokenBucket(qps, burst, now=now)
        # op-class sub-buckets: each class may use share × tenant rate;
        # the tenant bucket still caps the sum, so shares of 1.0 mean
        # "any mix, up to the tenant rate" while smaller shares carve
        # guaranteed headroom for the other classes
        self.classes = {
            oc: TokenBucket(qps * shares.get(oc, 1.0),
                            burst * shares.get(oc, 1.0), now=now)
            for oc in OP_CLASSES} if qps > 0 else {}
        self.inflight = 0
        self.admitted = 0
        self.throttled = 0
        self.shed = 0
        self._win_start = time.monotonic() if now is None else now
        self._win_count = 0
        self.last_qps = 0.0

    def note_admit(self, now: float) -> bool:
        """Returns True when the 1s qps window rolled — the hot path
        publishes gauges only then, so steady-state admits stay a few
        float ops with no per-request metrics traffic."""
        self.admitted += 1
        self.inflight += 1
        self._win_count += 1
        dt = now - self._win_start
        if dt >= 1.0:
            self.last_qps = self._win_count / dt
            self._win_start = now
            self._win_count = 0
            return True
        return False


class AdmitToken:
    """Returned by a successful admit; released when the request leaves
    the server (dispatch finally block / gateway middleware finally)."""

    __slots__ = ("tenant", "op_class", "released")

    def __init__(self, tenant: TenantState, op_class: str):
        self.tenant = tenant
        self.op_class = op_class
        self.released = False


class AdmissionController:
    """Hierarchical token-bucket admission + DAGOR-style shedding.

    One instance per server process (master, worker, gateway), injected
    into ``RpcServer.qos``. All methods are synchronous — admission runs
    inline in the connection receive loop, *before* the dispatch task is
    created, which is what makes the shed-before-queue contract real.
    """

    def __init__(self, *, enabled: bool = True,
                 global_qps: float = 0.0, global_burst: float = 0.0,
                 tenant_default_qps: float = 0.0,
                 tenant_default_burst: float = 0.0,
                 tenant_default_priority: int = 5,
                 tenant_inflight_cap: int = 0,
                 shares: dict[str, float] | None = None,
                 shed_enabled: bool = True,
                 shed_inflight_hi: int = 512,
                 shed_slow_frac: float = 0.5,
                 shed_adjust_interval_s: float = 0.25,
                 shed_retry_after_ms: int = 250,
                 doa_enabled: bool = True,
                 doa_margin: float = 1.0,
                 slow_op_ms: int = 1000,
                 metrics=None):
        _ensure_table()
        self.enabled = enabled
        self.metrics = metrics
        self.global_bucket = TokenBucket(global_qps, global_burst)
        self.default_qps = tenant_default_qps
        self.default_burst = tenant_default_burst
        self.default_priority = tenant_default_priority
        self.default_inflight_cap = tenant_inflight_cap
        self.shares = dict(shares or {})
        self.tenants: dict[str, TenantState] = {}
        self._overrides: dict[str, dict] = {}
        # ---- load monitor / shedding ----
        self.shed_enabled = shed_enabled
        self.shed_inflight_hi = shed_inflight_hi
        self.shed_slow_frac = shed_slow_frac
        self.shed_adjust_interval_s = shed_adjust_interval_s
        self.shed_retry_after_ms = shed_retry_after_ms
        self.shed_level = 0          # tenants with priority < level shed
        self.total_inflight = 0
        self.slow_op_s = slow_op_ms / 1000.0
        self._win_done = 0
        self._win_slow = 0
        self._last_adjust = time.monotonic()
        # ---- dead-on-arrival drop ----
        self.doa_enabled = doa_enabled
        self.doa_margin = doa_margin
        # EWMA service-time estimate per op class (seconds); zero until
        # enough completions have been observed — DOA never fires on a
        # cold estimate
        self._est: dict[str, float] = {oc: 0.0 for oc in OP_CLASSES}
        self._est_n: dict[str, int] = {oc: 0 for oc in OP_CLASSES}
        # shed-before-queue sentinel: incremented if a Throttled ever
        # escapes a *handler* (i.e. after admission); the storm harness
        # asserts this stays 0
        self.shed_after_queue = 0

    @classmethod
    def from_conf(cls, qc, slow_op_ms: int = 1000,
                  metrics=None) -> "AdmissionController":
        ctrl = cls(
            enabled=qc.enabled,
            global_qps=qc.global_qps, global_burst=qc.global_burst,
            tenant_default_qps=qc.tenant_default_qps,
            tenant_default_burst=qc.tenant_default_burst,
            tenant_default_priority=qc.tenant_default_priority,
            tenant_inflight_cap=qc.tenant_inflight_cap,
            shares={META: qc.meta_share, READ: qc.read_share,
                    WRITE: qc.write_share},
            shed_enabled=qc.shed_enabled,
            shed_inflight_hi=qc.shed_inflight_hi,
            shed_slow_frac=qc.shed_slow_frac,
            shed_adjust_interval_s=qc.shed_adjust_interval_s,
            shed_retry_after_ms=qc.shed_retry_after_ms,
            doa_enabled=qc.doa_enabled, doa_margin=qc.doa_margin,
            slow_op_ms=slow_op_ms, metrics=metrics)
        for spec in qc.tenants:
            # "name:qps[:priority[:inflight_cap[:tier0_mb]]]" —
            # env/TOML friendly; tier0_mb is the tier-0 cache partition
            # in MiB (0/absent = no partition)
            parts = str(spec).split(":")
            if not parts or not parts[0]:
                continue
            name = parts[0]
            kw: dict = {}
            try:
                if len(parts) > 1 and parts[1]:
                    kw["qps"] = float(parts[1])
                if len(parts) > 2 and parts[2]:
                    kw["priority"] = int(parts[2])
                if len(parts) > 3 and parts[3]:
                    kw["inflight_cap"] = int(parts[3])
                if len(parts) > 4 and parts[4]:
                    kw["tier0_bytes"] = int(float(parts[4]) * 1024 * 1024)
            except ValueError:
                continue
            ctrl.set_quota(name, **kw)
        return ctrl

    # ---------------- quota management ----------------

    def set_quota(self, name: str, qps: float | None = None,
                  burst: float | None = None, priority: int | None = None,
                  inflight_cap: int | None = None,
                  tier0_bytes: int | None = None) -> None:
        ov = self._overrides.setdefault(name, {})
        if qps is not None:
            ov["qps"] = qps
        if burst is not None:
            ov["burst"] = burst
        if priority is not None:
            ov["priority"] = priority
        if inflight_cap is not None:
            ov["inflight_cap"] = inflight_cap
        if tier0_bytes is not None:
            ov["tier0_bytes"] = tier0_bytes
        self.tenants.pop(name, None)     # rebuilt lazily with new quota

    def tier0_quota(self, name: str) -> int | None:
        """Tier-0 cache partition for `name` in bytes, or None when the
        tenant has no partition configured (BlockStore.tier0_quota hook)."""
        ov = self._overrides.get(name)
        if ov is None:
            return None
        q = ov.get("tier0_bytes", 0)
        return int(q) if q else None

    def _tenant(self, name: str) -> TenantState:
        ts = self.tenants.get(name)
        if ts is None:
            ov = self._overrides.get(name, {})
            qps = ov.get("qps", self.default_qps)
            ts = TenantState(
                name, qps,
                ov.get("burst", self.default_burst or 0.0),
                ov.get("priority", self.default_priority),
                ov.get("inflight_cap", self.default_inflight_cap),
                self.shares,
                tier0_bytes=ov.get("tier0_bytes", 0))
            self.tenants[name] = ts
        return ts

    # ---------------- admission ----------------

    def admit(self, tenant_name: str | None, op_class: str,
              deadline_remaining_s: float | None = None) -> AdmitToken:
        """The front-door check. Raises ``Throttled`` (quota/inflight/
        shed) or ``RpcTimeout`` (dead on arrival) — both retryable — or
        returns a token the server releases when the request completes.
        """
        now = time.monotonic()
        ts = self._tenant(tenant_name or DEFAULT_TENANT)

        # 1. dead on arrival: the caller's remaining budget cannot cover
        #    the estimated service time — doing the work only burns
        #    server capacity the live requests need
        if (self.doa_enabled and deadline_remaining_s is not None):
            est = self._est.get(op_class, 0.0)
            if est > 0.0 and deadline_remaining_s < est * self.doa_margin:
                self._count("qos.doa_dropped")
                raise RpcTimeout(
                    f"{ts.name}/{op_class}: remaining budget "
                    f"{deadline_remaining_s * 1000:.0f}ms < estimated "
                    f"service time {est * 1000:.0f}ms (dead on arrival)")

        # 2. overload shedding, lowest priority first
        if self.shed_enabled:
            self._maybe_adjust(now)
            if self.shed_level > 0 and ts.priority < self.shed_level:
                ts.shed += 1
                self._throttle(ts, "overload shed",
                               self.shed_retry_after_ms / 1000.0)

        # 3. inflight cap (bounds queue memory independently of rate)
        if ts.inflight_cap > 0 and ts.inflight >= ts.inflight_cap:
            self._throttle(ts, f"inflight cap {ts.inflight_cap}",
                           self.shed_retry_after_ms / 1000.0)

        # 4. hierarchical buckets: global → tenant → op-class; refund
        #    outer levels when an inner one rejects
        wait = self.global_bucket.try_acquire(1.0, now)
        if wait > 0.0:
            self._throttle(ts, "global quota", wait)
        wait = ts.bucket.try_acquire(1.0, now)
        if wait > 0.0:
            self.global_bucket.refund(1.0)
            self._throttle(ts, "tenant quota", wait)
        cls_bucket = ts.classes.get(op_class)
        if cls_bucket is not None:
            wait = cls_bucket.try_acquire(1.0, now)
            if wait > 0.0:
                self.global_bucket.refund(1.0)
                ts.bucket.refund(1.0)
                self._throttle(ts, f"{op_class} quota", wait)

        rolled = ts.note_admit(now)
        self.total_inflight += 1
        if rolled and self.metrics is not None:
            self.metrics.gauge(f"tenant.{ts.name}.inflight", ts.inflight)
            self.metrics.gauge(f"tenant.{ts.name}.qps",
                               round(ts.last_qps, 1))
        return AdmitToken(ts, op_class)

    def charge(self, tenant_name: str | None, op_class: str,
               n: float) -> None:
        """A list-taking call is admitted as one request and costs one
        an item: its handler, which alone has read the list, charges the
        other ``n`` here, global → tenant → op-class. Nothing is
        refused — the call is already being served — so a list longer
        than a bucket leaves it in debt."""
        if not self.enabled or n <= 0:
            return
        now = time.monotonic()
        ts = self._tenant(tenant_name or DEFAULT_TENANT)
        for bucket in (self.global_bucket, ts.bucket,
                       ts.classes.get(op_class)):
            if bucket is not None:
                bucket.debit(n, now)

    def admit_msg(self, code: int, header: dict) -> AdmitToken | None:
        """RPC-dispatch entry: classify the code, pull tenant + deadline
        off the header. Returns None for exempt (cluster-internal)
        codes — they bypass tenant accounting entirely."""
        if not self.enabled:
            return None
        op_class = _OP_CLASS.get(int(code))
        if op_class is None:
            return None
        remaining = None
        ms = header.get("deadline_ms")
        if ms is not None:
            remaining = float(ms) / 1000.0
        return self.admit(header.get(TENANT_KEY), op_class, remaining)

    def release(self, token: AdmitToken | None,
                elapsed_s: float | None = None) -> None:
        if token is None or token.released:
            return
        token.released = True
        ts = token.tenant
        ts.inflight -= 1
        self.total_inflight -= 1
        if elapsed_s is not None:
            self._note_done(token.op_class, elapsed_s)

    # ---------------- load monitor ----------------

    def _note_done(self, op_class: str, elapsed_s: float) -> None:
        # EWMA service-time estimate feeding the DOA drop
        n = self._est_n[op_class] = self._est_n.get(op_class, 0) + 1
        prev = self._est.get(op_class, 0.0)
        alpha = 0.2 if n > 8 else 1.0 / n    # fast warmup, then smooth
        self._est[op_class] = prev + alpha * (elapsed_s - prev)
        self._win_done += 1
        if elapsed_s >= self.slow_op_s:
            self._win_slow += 1

    def _maybe_adjust(self, now: float) -> None:
        """DAGOR-style feedback: every adjust interval, raise the shed
        level one step while overloaded, decay it one step when calm.
        Overload = admitted-inflight depth past the high-water mark OR
        a majority of recent completions slower than obs.slow_op_ms."""
        if now - self._last_adjust < self.shed_adjust_interval_s:
            return
        self._last_adjust = now
        slow = (self._win_done >= 8
                and self._win_slow / self._win_done >= self.shed_slow_frac)
        overloaded = self.total_inflight > self.shed_inflight_hi or slow
        if overloaded:
            self.shed_level = min(self.shed_level + 1, 100)
        elif self.shed_level > 0:
            self.shed_level -= 1
        self._win_done = self._win_slow = 0
        if self.metrics is not None:
            self.metrics.gauge("qos.shed_level", self.shed_level)

    # ---------------- bookkeeping ----------------

    def _throttle(self, ts: TenantState, why: str,
                  retry_after_s: float) -> None:
        ts.throttled += 1
        self._count("qos.throttled")
        if self.metrics is not None:
            self.metrics.inc(f"tenant.{ts.name}.throttled")
        raise Throttled(
            f"tenant {ts.name}: {why}",
            retry_after_ms=max(1, int(retry_after_s * 1000)))

    def _count(self, name: str) -> None:
        if self.metrics is not None:
            self.metrics.inc(name)

    def note_shed_after_queue(self) -> None:
        """A Throttled escaped a handler AFTER admission — a violation
        of the shed-before-queue contract (storm harness asserts 0)."""
        self.shed_after_queue += 1
        self._count("qos.shed_after_queue")

    def snapshot(self) -> dict:
        """Feeds /api/tenants, `cv report`, and the TENANT_STATS RPC."""
        return {
            "enabled": self.enabled,
            "shed_level": self.shed_level,
            "total_inflight": self.total_inflight,
            "shed_after_queue": self.shed_after_queue,
            "est_ms": {oc: round(v * 1000, 3)
                       for oc, v in self._est.items() if v > 0},
            "tenants": {
                ts.name: {
                    "qps": round(ts.last_qps, 1),
                    "quota_qps": ts.bucket.rate,
                    "priority": ts.priority,
                    "inflight": ts.inflight,
                    "inflight_cap": ts.inflight_cap,
                    "admitted": ts.admitted,
                    "throttled": ts.throttled,
                    "shed": ts.shed,
                    "tier0_bytes": ts.tier0_bytes,
                } for ts in self.tenants.values()},
        }
