"""Search observatory — two-plane observability (DESIGN.md §15).

Device plane (``search_metrics``): a ``SearchMetrics`` pytree of traced
per-round counters carried through the jitted search chunks as an optional
accumulator — the search results stay bit-identical with metrics on or
off, and the host reads one small pytree per chunk.

Host plane (``trace`` / ``metrics``): one span API, ``trace.span``, on the
profiler's clock (always on; a ``jax.profiler.TraceAnnotation`` each) that
also feeds a Chrome/Perfetto trace-event recorder for scheduler events
(admission, quanta, preemption, deadline expiry, device sync, jit
compiles) when one is attached, plus a counter/gauge registry with JSON
snapshots and a Prometheus-style text exposition.

``profile`` closes the loop: it fits the measured per-round dispatch cost
and per-task burden from recorded spans and feeds them into the analytic
``core/cilkview.py`` DagModel — measured, not guessed, burden terms for
the Fig 9 overlay.
"""

from repro.obsv.search_metrics import (  # noqa: F401
    SearchMetrics,
    accumulate_iteration,
    init_search_metrics,
    init_search_metrics_forest,
    merge_metrics,
    summarize_metrics,
)
from repro.obsv.trace import TraceRecorder, validate_trace  # noqa: F401
from repro.obsv.metrics import MetricsRegistry  # noqa: F401
