"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler writes, with
``jax.profiler.ProfileData``, into plain lists; every other function works
on those lists, so the reduction is tested on small synthetic traces.

- device ops: the ``XLA Ops`` line of each ``/device:TPU:n`` plane, as
  (start_ns, duration_ns, name) with the name cut to the HLO op (``%name``);
- device modules: the ``XLA Modules`` line (one event per jitted program
  execution, e.g. ``jit_run_chunk(...)``);
- host spans: every event on a host thread line whose name is one of the
  harness's own annotations.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

# ops that only hold other ops (a loop, a call): their time is their body's
CONTAINERS = re.compile(r"^(while|conditional|call|fusion\.call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all)")
HARNESS_SPANS = ("init", "move", "window_start")


def op_name(full: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return full.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(name: str) -> str:
    """``uct_select.6`` -> ``uct_select``; ``fusion.12`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name)


def load(log_dir: str | Path) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}
    from the newest trace under ``log_dir``."""
    import jax

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {"devices": {}, "host": []}
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    entry["ops"] = [(e.start_ns, e.duration_ns, op_name(e.name))
                                    for e in line.events]
                elif line.name == "XLA Modules":
                    entry["modules"] = [(e.start_ns, e.duration_ns, e.name)
                                        for e in line.events]
            devices[plane.name] = entry
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HARNESS_SPANS:
                        host.append((e.start_ns, e.duration_ns, e.name))
    return {"devices": devices, "host": sorted(host)}


def clip(events, t0: float, t1: float):
    """Events cut to the window [t0, t1); those outside it dropped."""
    out = []
    for s, d, name in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b - a, name))
    return out


def busy_intervals(events) -> list[tuple[float, float]]:
    """Union of the events' [start, end) intervals, merged and sorted."""
    spans = sorted((s, s + d) for s, d, _ in events if d > 0)
    merged: list[list[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(clip(events, t0, t1)))


def leaf_ops(events):
    """The ops that do work: container ops (a loop, a call) left out, since
    their span covers the gaps between the ops of their body."""
    return [e for e in events if not CONTAINERS.match(e[2])]


def device_busy_ns(devices: dict, t0: float, t1: float) -> list[float]:
    """Each device's busy time in the window: the union of its ops'
    intervals, container ops and whole-program events left out, so a gap
    between two ops inside one program counts as idle."""
    return [busy_ns(leaf_ops(d["ops"]), t0, t1) for d in devices.values()]


def program_busy_ns(devices: dict, t0: float, t1: float) -> list[float]:
    """Each device's time in the window with a jitted program running: the
    union of its ``XLA Modules`` intervals."""
    return [busy_ns(d["modules"], t0, t1) for d in devices.values()]


def idle_pct(devices: dict, t0: float, t1: float):
    """Share of the window in which no operation ran, in percent, averaged
    over the devices; None without a device."""
    busy = device_busy_ns(devices, t0, t1)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))


def idle_gaps(events, t0: float, t1: float) -> list[tuple[float, float]]:
    """The stretches of [t0, t1) in which no event runs."""
    gaps, cur = [], t0
    for a, b in busy_intervals(clip(events, t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def host_activity(host_spans, t: float) -> str:
    """The innermost (latest-starting) harness span that holds instant t."""
    best = None
    for s, d, name in host_spans:
        if s <= t < s + d and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "outside"


def named_gaps(events, host_spans, t0: float, t1: float, top: int = 10):
    """The longest idle gaps, each named by what the host was doing at its
    middle: [["idle during <span>", seconds], ...]."""
    gaps = sorted(idle_gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:top]
    return [[f"idle during {host_activity(host_spans, (a + b) / 2)}",
             (b - a) / 1e9] for a, b in gaps]


def op_time(events, t0: float, t1: float) -> dict[str, float]:
    """Device seconds per op kind in the window, container ops left out
    (their bodies' ops are counted instead)."""
    out: dict[str, float] = {}
    for _, d, name in clip(events, t0, t1):
        kind = op_kind(name)
        if CONTAINERS.match(name):
            continue
        out[kind] = out.get(kind, 0.0) + d / 1e9
    return out


def top_ops(events, t0: float, t1: float, top: int = 10):
    ranked = sorted(op_time(events, t0, t1).items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def calls(events, kind: str, t0: float, t1: float) -> list[float]:
    """Durations (ns) of the op ``kind``'s calls that lie wholly in the
    window."""
    return [d for s, d, name in events
            if op_kind(name) == kind and s >= t0 and s + d <= t1]


def mean_call_s(devices: dict, kind: str, t0: float, t1: float):
    """Mean device seconds of the op ``kind``'s calls wholly in the window,
    over all devices; None if it never ran there."""
    durs = [d for dev in devices.values()
            for d in calls(dev["ops"], kind, t0, t1)]
    return sum(durs) / len(durs) / 1e9 if durs else None


def collective_ns(events, t0: float, t1: float) -> float:
    return sum(d for _, d, name in clip(events, t0, t1)
               if COLLECTIVE.match(name))


def module_runs(modules, prefix: str, t0: float, t1: float) -> list[float]:
    """Durations (ns) of the jitted program ``prefix``'s runs wholly in the
    window (``jit_<fn>(<hash>)``)."""
    return [d for s, d, name in modules
            if name.startswith(f"jit_{prefix}(") and s >= t0 and s + d <= t1]
