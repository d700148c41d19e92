"""The harness's own spans around its calls into the program.

Each span is a ``jax.profiler.TraceAnnotation`` (so it lands in the
profiler's trace) and is also kept here with its host-clock start and end,
so that a span the profiler stopped inside, whose trace event is never
written, still names the idle gaps it holds.
"""

from __future__ import annotations

import contextlib
import time

SPANS: list[list] = []      # [name, start, end or None], perf_counter seconds


@contextlib.contextmanager
def span(name: str):
    import jax

    rec = [name, time.perf_counter(), None]
    SPANS.append(rec)
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            rec[2] = time.perf_counter()


def on_trace_clock(mark_ns: float, mark_s: float) -> list[tuple]:
    """The spans as (start_ns, duration_ns, name) on the trace's clock,
    given one instant on both clocks; an open span runs to the end."""
    out = []
    for name, a, b in SPANS:
        start = mark_ns + (a - mark_s) * 1e9
        dur = 1e18 if b is None else (b - a) * 1e9
        out.append((start, dur, name))
    return out
