"""Host-plane spans: one span API on the profiler's clock (DESIGN.md §15).

The serving and search drivers are host-side loops dispatching jitted
quanta; their time structure (rounds, quantum dispatch, device sync,
admission waits, preemption churn, compile stalls) is exactly what the
paper's profiling chapters measure.

``span(name, recorder=None, **args)`` is the one way to mark it. Every span
enters a ``jax.profiler.TraceAnnotation``: under a running profiler
(``jax.profiler.start_trace``) it is an event of the profile's host plane,
with ``args`` as its stats, on the same clock as the device ops of that
profile; with no profiler running it costs about a microsecond, so the
search's round loop keeps its spans always on. Given a ``TraceRecorder``,
the span is also recorded there.

``TraceRecorder`` writes Chrome/Perfetto trace-event JSON — open
``chrome://tracing`` or https://ui.perfetto.dev and load the file — on its
own clock: microseconds from the recorder's creation (``time.perf_counter``
based, so spans compose with the drivers' own telemetry clocks). Event
vocabulary (the ``ph`` field of the trace-event format):

- ``X`` *complete* spans with a duration — quanta, rounds, device syncs
  (``span`` with a recorder, or ``TraceRecorder.span``);
- ``i`` *instant* events — admission, preemption, retirement, deadline
  expiry, jit compiles;
- ``C`` counter tracks — queue depth, active slots;
- ``M`` metadata naming the process track.

Recording never raises into the traced code path: a ``None`` recorder is
the off switch and every driver hook guards on it.

``CompileWatch`` turns jit-cache growth into trace events: it snapshots
``fn._cache_size()`` for registered jitted callables and, on each
``poll()``, emits an instant event per callable whose cache grew — the
compile-counting context the serving engines poll once per tick.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable

import jax


PHASES = ("X", "i", "C", "M")


@contextlib.contextmanager
def span(name: str, recorder: TraceRecorder | None = None, **args):
    """A named host span: a profiler event always, a recorder event too
    when ``recorder`` is given.

    The block receives ``args`` and may update it (e.g. the rounds a
    quantum actually ran); with a recorder, both its event and the
    profiler's carry the values at exit.
    """
    with jax.profiler.TraceAnnotation(name, **args) as ann:
        if recorder is None:
            yield args
            return
        t0 = recorder.ts_us()
        try:
            yield args
        finally:
            if args:
                ann.set_metadata(**args)
            recorder.complete(name, t0, recorder.ts_us() - t0,
                              args=args or None)


class CompileWatch:
    """Cache-size probe for one jitted callable (see module docstring)."""

    def __init__(self, name: str, fn: Any):
        self.name = name
        self.fn = fn
        self.last = int(fn._cache_size())
        self.total_new = 0

    def poll(self) -> int:
        """New cache entries since the previous poll."""
        cur = int(self.fn._cache_size())
        delta = cur - self.last
        self.last = cur
        if delta > 0:
            self.total_new += delta
        return delta


class TraceRecorder:
    """Append-only trace-event buffer with span/instant/counter helpers."""

    def __init__(self, process_name: str = "repro-search",
                 clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self.events: list[dict] = []
        self._watches: list[CompileWatch] = []
        self.metadata("process_name", {"name": process_name})

    # -- clock ------------------------------------------------------------
    def ts_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    # -- raw emitters -----------------------------------------------------
    def _emit(self, ph: str, name: str, *, ts: float | None = None,
              tid: int = 0, **extra) -> dict:
        ev = {"name": name, "ph": ph, "pid": 0, "tid": tid,
              "ts": self.ts_us() if ts is None else ts}
        ev.update({k: v for k, v in extra.items() if v is not None})
        self.events.append(ev)
        return ev

    def metadata(self, name: str, args: dict, tid: int = 0):
        self._emit("M", name, ts=0.0, tid=tid, args=args)

    def instant(self, name: str, args: dict | None = None, tid: int = 0):
        self._emit("i", name, tid=tid, s="t", args=args)

    def complete(self, name: str, ts_us: float, dur_us: float,
                 args: dict | None = None, tid: int = 0):
        self._emit("X", name, ts=ts_us, tid=tid, dur=max(0.0, dur_us),
                   args=args)

    def counter(self, name: str, values: dict, tid: int = 0):
        self._emit("C", name, tid=tid, args=values)

    def span(self, name: str, args: dict | None = None):
        """``with tracer.span("quantum", {...}) as args:`` — the module's
        ``span`` with this recorder attached."""
        return span(name, self, **(args or {}))

    # -- compile counting -------------------------------------------------
    def watch_compiles(self, name: str, fn: Any) -> CompileWatch:
        """Track a jitted callable's cache; ``poll_compiles`` emits an
        instant ``jit_compile`` event whenever it grew."""
        w = CompileWatch(name, fn)
        self._watches.append(w)
        return w

    def poll_compiles(self):
        for w in self._watches:
            d = w.poll()
            if d > 0:
                self.instant("jit_compile", {"fn": w.name, "new_programs": d,
                                             "total": w.total_new})

    def compile_counts(self) -> dict[str, int]:
        self.poll_compiles()
        return {w.name: w.total_new for w in self._watches}

    # -- output -----------------------------------------------------------
    def to_dict(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


def validate_trace(obj: dict | str) -> int:
    """Structural check of a trace (dict or file path) -> event count.

    Raises ``ValueError`` on malformed traces: missing ``traceEvents``,
    events without name/ph/ts, a phase outside the recorder's vocabulary
    (``X``, ``i``, ``C``, ``M``), or ``X`` events without ``dur``. Used by
    the CI trace smoke and by tests.
    """
    if isinstance(obj, str):
        with open(obj) as f:
            obj = json.load(f)
    events = obj.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise ValueError("trace has no traceEvents list")
    for i, ev in enumerate(events):
        for field in ("name", "ph", "ts"):
            if field not in ev:
                raise ValueError(f"event {i} missing {field!r}: {ev}")
        if ev["ph"] not in PHASES:
            raise ValueError(f"event {i} has unknown phase {ev['ph']!r}: "
                             f"{ev}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"complete event {i} missing dur: {ev}")
    json.dumps(events[: min(len(events), 64)])   # must be JSON-serializable
    return len(events)
