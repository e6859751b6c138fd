"""Observability plane: distributed tracing, span stores, step profiler.

See docs/observability.md for the header format, sampling rules and the
collection endpoints (`/metrics`, `/api/trace/<id>`, `cv trace`)."""

from curvine_tpu.obs.trace import (  # noqa: F401
    NULL_SPAN, TRACE_KEY, Span, SpanCtx, SpanStore, Timed, Tracer,
    assemble_tree, current_ctx, render_tree,
)
from curvine_tpu.obs.profiler import StepProfiler  # noqa: F401
