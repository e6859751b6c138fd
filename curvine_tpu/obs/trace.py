"""Distributed tracing: Dapper-style context propagation + span stores.

A trace context ``(trace_id, span_id, sampled)`` rides the RPC header
under ``TRACE_KEY`` exactly the way the deadline budget rides
``deadline_ms`` (rpc/deadline.py): the client stamps it at op start,
every downstream hop re-stamps its own span id, and ``RpcServer``
dispatch picks it up per request. Each process keeps its finished spans
in a bounded ring buffer (``SpanStore``); the master collects spans from
itself + workers over ``GET_SPANS`` and ``/api/trace/<id>`` assembles
the tree.

Sampling is head-based (``obs.trace_sample_rate`` decides at the root;
children inherit the flag over the wire), with two always-record
backstops: a span that ended in error, and a span slower than
``obs.slow_op_ms`` (which additionally emits a structured slow-op log
line). Parity in spirit: the reference's pervasive prometheus wiring
(master_metrics.rs / worker_metrics.rs) plus Dapper §3 propagation.

While a ``jax.profiler`` session is open in the process, every span —
sampled or not — is also a ``TraceAnnotation`` named
``cv.<component>.<op>`` in the profiler's trace, and every recorded span
carries ``mono`` (``CLOCK_MONOTONIC``, one axis for all processes of a
host) beside the wall-clock ``start``. ``Timed`` pairs a span with an
always-on counter for the phases a benchmark reads.
"""

from __future__ import annotations

import contextvars
import logging
import random
import sys
import time
from collections import deque

log = logging.getLogger(__name__)

# reserved header field carrying [trace_id, span_id, sampled]
TRACE_KEY = "trace_ctx"

# ambient span context of the current task (contextvars give per-task
# isolation, so concurrent requests never see each other's spans)
_current: contextvars.ContextVar["SpanCtx | None"] = \
    contextvars.ContextVar("curvine_trace_ctx", default=None)


def current_ctx() -> "SpanCtx | None":
    """The ambient span context of the calling task, if any."""
    return _current.get()


def _annotate(component: str, op: str):
    """An open ``jax.profiler.TraceAnnotation`` ``cv.<component>.<op>``
    while a profiler session is running in this process, else None: the
    program's spans then lie on the profiler's timeline beside the
    device's idle gaps. JAX is looked up, never imported: a process that
    has not loaded it (`cv master`) has no session to write into and
    must stay JAX-free."""
    prof = sys.modules.get("jax.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return None
    ann = prof.TraceAnnotation(f"cv.{component}.{op}")
    ann.__enter__()
    return ann


def _new_trace_id() -> str:
    return f"{random.getrandbits(64):016x}"


def _new_span_id() -> int:
    # 48-bit ids: unique enough within one trace, msgpack-small
    return random.getrandbits(48) | 1


class SpanCtx:
    """What crosses the wire: identifies the caller's span so the
    callee's span can link to it as a parent."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: int, sampled: bool):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def stamp(self, header: dict) -> dict:
        header[TRACE_KEY] = [self.trace_id, self.span_id,
                             1 if self.sampled else 0]
        return header

    @classmethod
    def from_header(cls, header: dict | None) -> "SpanCtx | None":
        if not header:
            return None
        v = header.get(TRACE_KEY)
        if not v:
            return None
        try:
            return cls(str(v[0]), int(v[1]), bool(v[2]))
        except (TypeError, ValueError, IndexError, KeyError):
            return None          # hostile/foreign header: not a trace

    def __repr__(self) -> str:  # pragma: no cover
        return (f"SpanCtx({self.trace_id}, {self.span_id:#x}, "
                f"sampled={self.sampled})")


class SpanStore:
    """Per-process bounded ring of finished spans. ``deque.append`` with
    a maxlen is a single GIL-atomic op, so appends from the event loop
    and engine threads need no lock; old spans fall off the head."""

    def __init__(self, capacity: int = 8192):
        self.capacity = max(16, int(capacity))
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self.appended = 0

    def append(self, span: dict) -> None:
        self._ring.append(span)
        self.appended += 1

    def extend(self, spans) -> None:
        for s in spans:
            if isinstance(s, dict):
                self.append(s)

    def for_trace(self, trace_id: str) -> list[dict]:
        return [s for s in list(self._ring)
                if s.get("trace_id") == trace_id]

    def drain(self, max_n: int = 512) -> list[dict]:
        """Pop up to `max_n` oldest spans (client → master shipping)."""
        out = []
        while len(out) < max_n:
            try:
                out.append(self._ring.popleft())
            except IndexError:
                break
        return out

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)

    def stats(self) -> dict:
        return {"stored": len(self._ring), "appended": self.appended,
                "capacity": self.capacity}


class Span:
    """One timed operation. Usable as a context manager (sets the
    ambient context so nested spans and outbound RPCs link to it) or
    held manually and closed with ``finish()`` — e.g. when start and end
    happen in different tasks (streaming upload sinks)."""

    __slots__ = ("tracer", "ctx", "parent_id", "op", "attrs", "start",
                 "_t0", "status", "dur", "_token", "_finished", "_ann",
                 "detail")

    def __init__(self, tracer: "Tracer", ctx: SpanCtx, parent_id: int,
                 op: str, attrs: dict, detail: bool = False):
        self.tracer = tracer
        self.ctx = ctx
        self.parent_id = parent_id
        self.detail = detail
        self.op = op
        self.attrs = attrs
        self.start = time.time()
        self._t0 = time.perf_counter()
        self.status = "ok"
        self.dur = 0.0
        self._token = None
        self._finished = False
        # whatever the head sampling decided: the profiler's trace is
        # the caller's own, bounded by the session, not by the ring
        self._ann = _annotate(tracer.component, op)

    def set_attr(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    def error(self, cause="") -> "Span":
        self.status = "error"
        if cause:
            self.attrs["error"] = str(cause)[:200]
        return self

    def finish(self, status: str | None = None) -> None:
        if self._finished:
            return
        self._finished = True
        if status is not None:
            self.status = status
        self.dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.tracer._record(self)

    def __enter__(self) -> "Span":
        self._token = _current.set(self.ctx)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if et is not None and self.status == "ok":
            self.error(f"{et.__name__}: {ev}")
        self.finish()
        return False


class _NullSpan:
    """No-op span when tracing is disabled: zero allocation per op."""

    __slots__ = ()
    ctx = None
    status = "ok"

    def set_attr(self, key, value):
        return self

    def error(self, cause=""):
        return self

    def finish(self, status=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return False


NULL_SPAN = _NullSpan()


# A thread's CPU clock is a system call where the wall clock is not: on
# the chip host one read costs 5.9 us with the GIL held, at 10 ms
# resolution (PR 37). So a Timed that counts CPU reads the clock on one
# block in CPU_SAMPLE, drawn at random, and counts that block's wall
# beside its CPU: the sum of the one over the other is the blocks' share
# on a CPU, at an eighth of the reads.
CPU_SAMPLE = 8
_draw = random.Random().random


class Timed:
    """One measurement, two sinks. ``with Timed(counters, key, span):``
    adds the block's seconds to ``counters[key + ".s"]`` and one to
    ``counters[key + ".n"]`` — always on, what `/metrics` and the
    benchmark read — and runs the block under ``span``, which is what
    `cv trace`, the slow-op log and the profiler's timeline show.
    ``cpu=True`` also counts, on a sample of the blocks (`CPU_SAMPLE`),
    the CPU seconds of the thread that runs the block
    (`time.thread_time`: user + system) into ``key + ".cpu_s"`` and the
    same blocks' wall into ``key + ".cpu_wall_s"`` — for a block that
    never yields: one that awaits would count the other tasks the loop
    ran meanwhile."""

    __slots__ = ("counters", "_s", "_n", "_cpu", "span", "_t0", "_c0")

    def __init__(self, counters: dict, key: str, span=NULL_SPAN,
                 cpu: bool = False):
        self.counters = counters
        self._s = key + ".s"
        self._n = key + ".n"
        self._cpu = key if cpu else None
        self.span = span

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time() if self._cpu is not None \
            and _draw() * CPU_SAMPLE < 1 else None
        return self.span.__enter__()

    def __exit__(self, et, ev, tb) -> bool:
        self.span.__exit__(et, ev, tb)
        c = self.counters
        if self._c0 is None:
            wall = time.perf_counter() - self._t0
        else:
            # read inside the wall clock's two reads: cpu <= wall
            cpu = time.thread_time() - self._c0
            wall = time.perf_counter() - self._t0
            for k, v in ((".cpu_s", cpu), (".cpu_wall_s", wall)):
                c[self._cpu + k] = c.get(self._cpu + k, 0.0) + v
        c[self._s] = c.get(self._s, 0.0) + wall
        c[self._n] = c.get(self._n, 0) + 1
        return False


class Tracer:
    """Per-component tracing front end: sampling decisions, span
    creation, the bounded store, and the slow-op backstop."""

    def __init__(self, component: str, sample_rate: float = 0.01,
                 slow_op_ms: int = 1_000, capacity: int = 8192,
                 metrics=None, enabled: bool = True):
        self.component = component
        self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
        self.slow_s = max(0.0, slow_op_ms / 1000.0)
        self.store = SpanStore(capacity)
        self.metrics = metrics
        self.enabled = enabled
        self.last_trace_id: str | None = None

    @classmethod
    def from_conf(cls, component: str, obs_conf, metrics=None) -> "Tracer":
        return cls(component,
                   sample_rate=obs_conf.trace_sample_rate,
                   slow_op_ms=obs_conf.slow_op_ms,
                   capacity=obs_conf.span_store_size,
                   metrics=metrics, enabled=obs_conf.enabled)

    # ---------------- span creation ----------------

    def start_trace(self, op: str, attrs: dict | None = None,
                    sampled: bool | None = None):
        """A new root span; head sampling decided here (or forced)."""
        if not self.enabled:
            return NULL_SPAN
        if sampled is None:
            sampled = random.random() < self.sample_rate
        ctx = SpanCtx(_new_trace_id(), _new_span_id(), sampled)
        self.last_trace_id = ctx.trace_id
        return Span(self, ctx, 0, op, dict(attrs or {}))

    def span(self, op: str, attrs: dict | None = None, parent=None,
             detail: bool = False):
        """A child of ``parent`` (a SpanCtx, e.g. from the wire) or of
        the ambient task context; with neither, a new sampled-by-rate
        root. A ``detail`` span is a step inside an operation (a phase
        of a read): a span like any other, kept in the ring by the same
        rule, but it raises no slow-op line of its own — the operation
        round it does, under the same trace id. With none round it, it
        is the operation."""
        if not self.enabled:
            return NULL_SPAN
        p = parent if parent is not None else _current.get()
        if p is None:
            return self.start_trace(op, attrs)
        ctx = SpanCtx(p.trace_id, _new_span_id(), p.sampled)
        return Span(self, ctx, p.span_id, op, dict(attrs or {}), detail)

    # ---------------- record / query ----------------

    def _record(self, span: Span) -> None:
        slow = 0.0 < self.slow_s <= span.dur
        keep = span.ctx.sampled or span.status != "ok" or slow
        if self.metrics is not None:
            self.metrics.inc("trace.spans_recorded" if keep
                             else "trace.spans_dropped")
        if slow and not span.detail:
            log.warning(
                "slow-op component=%s op=%s dur_ms=%.1f status=%s "
                "trace_id=%s span_id=%x attrs=%s",
                self.component, span.op, span.dur * 1000, span.status,
                span.ctx.trace_id, span.ctx.span_id, span.attrs)
        if not keep:
            return
        self.store.append({
            "trace_id": span.ctx.trace_id, "span_id": span.ctx.span_id,
            "parent": span.parent_id, "component": self.component,
            "op": span.op, "start": span.start, "mono": span._t0,
            "dur": span.dur, "status": span.status, "attrs": span.attrs,
        })

    def spans_for(self, trace_id: str) -> list[dict]:
        return self.store.for_trace(trace_id)

    def ingest(self, spans) -> None:
        """Accept spans shipped from another process (client push)."""
        self.store.extend(spans)

    def drain(self, max_n: int = 512) -> list[dict]:
        return self.store.drain(max_n)


# ---------------- tree assembly / rendering ----------------

def assemble_tree(spans: list[dict]) -> list[dict]:
    """Nest spans by parent link; orphans (parent not collected — e.g.
    an unflushed client span) surface as extra roots instead of
    vanishing. Children sort by start time."""
    nodes = {s["span_id"]: {**s, "children": []} for s in spans
             if "span_id" in s}
    roots: list[dict] = []
    for n in nodes.values():
        parent = nodes.get(n.get("parent"))
        if parent is not None and parent is not n:
            parent["children"].append(n)
        else:
            roots.append(n)

    def _sort(node: dict) -> None:
        node["children"].sort(key=lambda c: c.get("start", 0.0))
        for c in node["children"]:
            _sort(c)

    roots.sort(key=lambda r: r.get("start", 0.0))
    for r in roots:
        _sort(r)
    return roots


def render_tree(roots: list[dict], trace_id: str = "") -> str:
    """ASCII renderer for `cv trace <id>`."""
    def count(n: dict) -> int:
        return 1 + sum(count(c) for c in n["children"])

    total = sum(count(r) for r in roots)
    comps = set()

    def walk(n: dict, prefix: str, is_last: bool, top: bool,
             out: list[str]) -> None:
        comps.add(n.get("component", "?"))
        attrs = {k: v for k, v in (n.get("attrs") or {}).items()}
        tail = f"  {attrs}" if attrs else ""
        mark = "" if top else ("└─ " if is_last else "├─ ")
        out.append(f"{prefix}{mark}{n.get('component', '?')}:"
                   f"{n.get('op', '?')} {n.get('dur', 0.0) * 1000:.2f}ms "
                   f"[{n.get('status', '?')}]{tail}")
        child_prefix = prefix if top else \
            prefix + ("   " if is_last else "│  ")
        kids = n["children"]
        for i, c in enumerate(kids):
            walk(c, child_prefix, i == len(kids) - 1, False, out)

    lines: list[str] = []
    for r in roots:
        walk(r, "", True, True, lines)
    head = (f"trace {trace_id or (roots[0]['trace_id'] if roots else '?')}"
            f" ({total} spans, {len(comps)} components)")
    return "\n".join([head] + lines)
