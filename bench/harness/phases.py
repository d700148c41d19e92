"""Device time of the compiled round by phase, and the round boundaries.

The program names the phases of one sync iteration with ``jax.named_scope``
(``descent``, ``expand``, ``leaf_eval``, ``backup``); the compiler keeps the
name in each op's ``op_name`` metadata, e.g.
``jit(run_chunk)/while/body/expand/sort``. The reduced trace holds op names
alone, so ``scope_map`` reads the metadata from the compiled program's HLO
text and ``run_chunk_scopes`` compiles the cell's ``run_chunk`` for it: the
program the window ran, whose op names a compile repeats exactly.

An op the compiler made (an async copy, a sliced transfer, a rewritten
scan) carries no ``op_name`` path, only a bare name or none; it takes the
phase of the nearest op that uses its result, or, failing that, of the
nearest op that produces its input. An op whose path names no phase (the
loop counter, the iteration's key folding) stays ``unscoped``.

``round_gaps`` measures, on the device, the time from the end of one
``run_chunk`` program to the start of the next within one search: two rounds
of one search are separated only by the round's own programs (its keys,
``fold_task_keys``, and the conversion of its inputs,
``convert_element_type``), while between two searches the stats readbacks
and the next tree's set-up run programs of their own.
"""

from __future__ import annotations

import bisect
import re
import sys
from functools import lru_cache

from harness import trace as tr

PHASES = ("descent", "expand", "leaf_eval", "backup")
UNSCOPED = "unscoped"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def phase_of(op_name: str | None) -> str | None:
    """The innermost phase scope on an ``op_name`` path, or None."""
    found = None
    for part in (op_name or "").split("/"):
        if part in PHASES:
            found = part
    return found


def scope_map(hlo_text: str) -> dict[str, str]:
    """{HLO op name: phase or ``unscoped``} from a compiled module's text."""
    phase: dict[str, str | None] = {}
    made: set[str] = set()          # ops the compiler made: no op_name path
    users: dict[str, list[str]] = {}
    operands: dict[str, list[str]] = {}
    comp: set[str] = set()
    for line in hlo_text.splitlines():
        if _COMPUTATION.match(line):
            comp = set()
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        phase[name] = phase_of(op.group(1) if op else None)
        if not op or "/" not in op.group(1):
            made.add(name)
        operands[name] = [o for o in _OPERAND.findall(rest) if o in comp]
        for o in operands[name]:
            users.setdefault(o, []).append(name)
        comp.add(name)
    out = {}
    for name, ph in phase.items():
        if not ph and name in made:
            ph = _nearest(name, users, phase) or _nearest(
                name, operands, phase)
        out[name] = ph or UNSCOPED
    return out


def _nearest(name: str, edges: dict, phase: dict) -> str | None:
    """Breadth-first along ``edges`` to the first op with a phase."""
    seen, frontier = {name}, [name]
    while frontier:
        nxt = []
        for n in frontier:
            for e in edges.get(n, ()):
                if phase.get(e):
                    return phase[e]
                if e not in seen:
                    seen.add(e)
                    nxt.append(e)
        frontier = nxt
    return None


@lru_cache(maxsize=4)
def _compiled_run_chunk(cfg_items: tuple) -> str:
    import jax
    import jax.numpy as jnp

    from harness.drivers.search import gscpm_config
    from repro.core import gscpm
    from repro.core.tree import init_tree

    g = gscpm_config(dict(cfg_items))
    game = g.game_obj
    spec = jax.ShapeDtypeStruct
    tree = jax.eval_shape(lambda: init_tree(g.tree_cap, game.n_actions, 1))
    keys = jax.eval_shape(lambda: gscpm.fold_task_keys(
        jax.random.key(0), jnp.arange(g.n_workers, dtype=jnp.int32)))
    lowered = gscpm.run_chunk.lower(
        tree, spec((game.n_cells,), jnp.int8), g, keys,
        spec((g.n_workers,), jnp.bool_), spec((), jnp.int32),
        spec((), jnp.float32))
    # compiled afresh, not read from the persistent cache: the op names
    # are the same either way, and a fresh executable always has its text
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def run_chunk_scopes(cfg: dict) -> dict[str, str]:
    """The phase of each op of the cell's compiled ``run_chunk``."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return scope_map(_compiled_run_chunk(items))


def program_runs(modules, prefix: str, t0: float, t1: float):
    """(start, end) of the program ``prefix``'s runs wholly in the window.

    The profiler ends the event of a program it stops inside at the stop,
    so the device's last program event may be a run cut short; it is left
    out, as is any run that no other program event follows."""
    last = max((s for s, _, _ in modules), default=None)
    return [(s, s + d) for s, d, name in modules
            if name.startswith(f"jit_{prefix}(") and s >= t0 and s + d <= t1
            and s < last]


def ops_in_runs(ops, runs):
    """The leaf ops that start inside one of ``runs``."""
    runs = sorted(runs)
    starts = [a for a, _ in runs]
    out = []
    for op in tr.leaf_ops(ops):
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < runs[i][1]:
            out.append(op)
    return out


def phase_ns(ops, scopes: dict[str, str]) -> dict[str, float]:
    """Device ns per phase (and ``unscoped``) over ``ops``; an op the map
    does not know is unscoped."""
    out = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    for _, d, name in ops:
        out[scopes.get(name, UNSCOPED)] += d
    return out


def breakdown(devices: dict, scopes: dict[str, str], m: int, t0: float,
              t1: float):
    """Per sync iteration, in microseconds, each phase's (and the
    unscoped) device time in the ``run_chunk`` programs wholly in the
    window, summed over the devices; None if none ran there."""
    total = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    n_runs = 0
    for dev in devices.values():
        runs = program_runs(dev["modules"], "run_chunk", t0, t1)
        n_runs += len(runs)
        for k, v in phase_ns(ops_in_runs(dev["ops"], runs), scopes).items():
            total[k] += v
    if not n_runs:
        return None
    return {k: v / (n_runs * m) / 1e3 for k, v in total.items()}


_READ: list = []             # [(trace, reading)] of the last traced run


def phase_us(ctx, phase: str):
    """The reading of ``phase`` for the traced run in ``ctx``; None where
    the program names no phase (or no ``run_chunk`` ran in the window).
    The first call logs the whole breakdown and each round's device time."""
    if not _READ or _READ[0][0] is not ctx["trace"]:
        _READ[:] = [(ctx["trace"], _read(ctx))]
    got = _READ[0][1]
    return None if got is None else got.get(phase)


def _read(ctx) -> dict | None:
    cfg = ctx["config"]
    m = max(1, cfg["n_playouts"] // max(1, cfg["n_tasks"]))
    scopes = run_chunk_scopes(cfg)
    if not any(v in PHASES for v in scopes.values()):
        return None
    got = breakdown(ctx["trace"]["devices"], scopes, m, ctx["t0"], ctx["t1"])
    if got is None:
        return None
    whole = sum(got.values())
    log = ", ".join(f"{k} {v:.1f}" for k, v in got.items())
    print(f"phases per sync iteration (us): {log}; sum {whole:.1f}; "
          f"unscoped {100 * got[UNSCOPED] / max(whole, 1e-9):.2f}%",
          file=sys.stderr, flush=True)
    for plane, dev in ctx["trace"]["devices"].items():
        runs = program_runs(dev["modules"], "run_chunk", ctx["t0"], ctx["t1"])
        print(f"{plane} run_chunk rounds in the window (ms): " + ", ".join(
            f"{(b - a) / 1e6:.3f}" for a, b in runs), file=sys.stderr,
            flush=True)
    return {k: v for k, v in got.items() if k in PHASES and v > 0}


ROUND_PROGRAMS = ("jit_fold_task_keys(", "jit_convert_element_type(")


def round_gaps(modules, t0: float, t1: float) -> list[float]:
    """Device ns from the end of each ``run_chunk`` run to the start of the
    next one of the same search, both wholly in the window: the two are
    consecutive runs with no program but the round's own between them."""
    runs = sorted((s, s + d, name) for s, d, name in modules
                  if s >= t0 and s + d <= t1)
    gaps, last = [], None
    for s, e, name in runs:
        if name.startswith("jit_run_chunk("):
            if last is not None:
                gaps.append(s - last)
            last = e
        elif not name.startswith(ROUND_PROGRAMS):
            last = None
    return gaps
