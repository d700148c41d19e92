"""Observability-layer suite (DESIGN.md §15).

The contracts that make the two planes trustworthy:

- **bit-identity**: a search with the device-plane ``SearchMetrics``
  accumulator threaded through its compiled chunks is bit-identical —
  every ``Tree`` leaf — to the same search with metrics off, for hex AND
  gomoku, single tree and forest;
- **two programs**: ``GSCPMConfig.metrics`` is a hashed static flag, so a
  Cp × grain × budget sweep with metrics on and off compiles exactly TWO
  quantum programs per game class (asserted via jit-cache deltas);
- **conservation**: the traced counters must agree with the tree the
  search actually built and with the schedule it actually ran;
- **trace structure**: the recorder emits valid Chrome trace-event JSON
  (``validate_trace`` accepts it and rejects malformed variants), serving
  traces carry the admission/quantum/preempt/retire/deadline vocabulary,
  and ``obsv.profile`` recovers known burden terms from synthetic spans;
- **QueueStats**: progress telemetry (preemptions, quanta, tokens) is
  reported even when NO request has finished (the regression this PR
  fixes).
"""

from __future__ import annotations

import dataclasses
import json
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.core.gscpm import GSCPMConfig, gscpm_search, run_chunk
from repro.core.root_parallel import gscpm_search_batch, run_chunk_forest
from repro.core.tree import init_tree, node_depths
from repro.obsv import (
    MetricsRegistry,
    TraceRecorder,
    init_search_metrics,
    init_search_metrics_forest,
    merge_metrics,
    summarize_metrics,
    validate_trace,
)
from repro.obsv.trace import span
from repro.serve.games import GameRequest, TPFIFOGameEngine
from repro.serve.tpfifo import QueueStats, Ticket

SIZE = 5


def cfg_for(game, metrics=False, **kw):
    kw.setdefault("board_size", SIZE)
    kw.setdefault("n_workers", 4)
    kw.setdefault("tree_cap", 512)
    kw.setdefault("n_playouts", 64)
    kw.setdefault("n_tasks", 8)
    return GSCPMConfig(game=game, metrics=metrics, **kw)


def trees_equal(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------------------ bit-identity ----
@pytest.mark.parametrize("game", ["hex", "gomoku"])
def test_metrics_whole_search_bit_identity(game):
    """Same key, same schedule: metrics on vs off must agree on EVERY tree
    leaf (visits, wins, structure, allocation counters)."""
    cfg = cfg_for(game)
    board = cfg.game_obj.init_board()
    key = jax.random.key(7)
    t_off, s_off = gscpm_search(board, 1, cfg, key)
    t_on, s_on = gscpm_search(board, 1,
                              dataclasses.replace(cfg, metrics=True), key)
    assert trees_equal(t_off, t_on)
    assert s_off["best_move"] == s_on["best_move"]
    assert "metrics" in s_on and "metrics" not in s_off


@pytest.mark.parametrize("game", ["hex", "gomoku"])
def test_metrics_forest_bit_identity(game):
    cfg = cfg_for(game, n_playouts=32, n_tasks=8)
    board = cfg.game_obj.init_board()
    key = jax.random.key(3)
    f_off, s_off = gscpm_search_batch(board, 1, cfg, key, n_trees=3)
    f_on, s_on = gscpm_search_batch(
        board, 1, dataclasses.replace(cfg, metrics=True), key, n_trees=3)
    assert trees_equal(f_off, f_on)
    assert s_on["metrics"]["lane_playouts"] == s_off["playouts"]


# ------------------------------------------------------------ two programs ----
def test_exactly_two_programs_per_game_class():
    """Cp × grain × budget sweeps with metrics on AND off compile exactly
    two quantum programs per game class — the metrics arm is one extra
    cache entry, budget knobs stay compare=False. The (n_workers, tree_cap,
    board_size) combination is unique to this test so the cache delta is
    exact even with other test modules warm in the same process."""
    for game in ("hex", "gomoku"):
        before = run_chunk._cache_size()
        board = None
        key = jax.random.key(0)
        for metrics in (False, True):
            for cp, (n_po, n_t) in [(0.5, (16, 4)), (1.7, (32, 8)),
                                    (0.9, (24, 12))]:
                cfg = GSCPMConfig(game=game, board_size=4, n_workers=6,
                                  tree_cap=384, n_playouts=n_po,
                                  n_tasks=n_t, cp=cp, metrics=metrics)
                board = cfg.game_obj.init_board()
                gscpm_search(board, 1, cfg, key)
        assert run_chunk._cache_size() == before + 2, game


def test_run_chunk_rejects_flag_accumulator_mismatch():
    cfg = cfg_for("hex")
    board = cfg.game_obj.init_board()
    tree = init_tree(cfg.tree_cap, cfg.game_obj.n_actions, 1)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.key(0), jnp.arange(cfg.n_workers))
    active = jnp.ones((cfg.n_workers,), bool)
    with pytest.raises(ValueError, match="metrics"):
        run_chunk(tree, board, cfg, keys, active, jnp.int32(1),
                  jnp.float32(1.0), init_search_metrics())


# ------------------------------------------------------------ conservation ----
@pytest.mark.parametrize("game", ["hex", "gomoku"])
def test_counter_conservation(game):
    """The device counters must agree with the tree and the schedule:
    every playout is a scheduled lane iteration, every expansion is a tree
    node, every proposal either allocates or collides, and no descent is
    deeper than the tree it walked."""
    cfg = cfg_for(game, metrics=True, n_playouts=72, n_tasks=12)
    board = cfg.game_obj.init_board()
    tree, st = gscpm_search(board, 1, cfg, jax.random.key(11))
    m = st["metrics"]
    sch = scheduler.make_schedule(cfg.n_playouts, cfg.n_tasks,
                                  cfg.n_workers, cfg.scheduler)
    sstats = scheduler.schedule_stats(sch)

    assert m["lane_playouts"] == st["playouts"] \
        == sstats["lane_iterations"]
    assert m["masked_lane_iterations"] == sum(
        int((~np.asarray(r.active)).sum()) * r.m for r in sch)
    assert m["sync_iterations"] == sum(r.m for r in sch)
    # every playout backs up through the root exactly once
    assert int(np.asarray(tree.visits)[0]) == m["lane_playouts"]

    depths = node_depths(tree)
    n_nodes = int(tree.n_nodes)
    assert m["expansions"] == n_nodes - 1          # root precedes the search
    assert m["tree_nodes_peak"] == n_nodes         # nodes are never freed
    assert m["expand_proposals"] == m["expansions"] + m["expand_collisions"]
    assert 0 <= m["depth_max"] <= depths[:n_nodes].max()
    assert 0 <= m["depth_sum"] <= m["depth_max"] * m["lane_playouts"]
    assert m["leaf_collisions"] <= m["lane_playouts"]
    n_cells = cfg.game_obj.n_cells
    assert 0 < m["playout_len_max"] <= n_cells
    assert m["playout_moves"] <= n_cells * m["lane_playouts"]
    assert m["held_levels"] >= 0


def test_forest_summary_merges_members():
    fm = init_search_metrics_forest(3)
    fm = fm._replace(
        lane_playouts=jnp.asarray([4, 5, 6], jnp.int32),
        depth_max=jnp.asarray([2, 7, 3], jnp.int32),
        depth_sum=jnp.asarray([1, 2, 3], jnp.int32))
    s = summarize_metrics(fm)
    assert s["lane_playouts"] == 15
    assert s["depth_max"] == 7                      # max-merged gauge
    assert s["depth_sum"] == 6                      # summed counter


def test_merge_metrics_sum_vs_max_fields():
    a = init_search_metrics()._replace(
        expansions=jnp.int32(3), tree_nodes_peak=jnp.int32(10),
        tile_gathers=jnp.int32(5), backup_levels=jnp.int32(9),
        sync_iterations=jnp.int32(2))
    b = init_search_metrics()._replace(
        expansions=jnp.int32(4), tree_nodes_peak=jnp.int32(8),
        tile_gathers=jnp.int32(2), backup_levels=jnp.int32(8),
        sync_iterations=jnp.int32(2))
    c = merge_metrics(a, b)
    assert int(c.expansions) == 7
    assert int(c.tree_nodes_peak) == 10
    assert int(c.tile_gathers) == 7                 # summed counter
    assert int(c.backup_levels) == 17               # summed counter
    s = summarize_metrics(c)
    assert s["tile_gathers_per_iter"] == 7 / 4
    assert s["backup_levels_per_iter"] == 17 / 4


@pytest.mark.parametrize("vl_rounds", [1, 2])
def test_tile_gathers_counts_levels_stepped_from(monkeypatch, vl_rounds):
    """``tile_gathers`` is the (W, C) tiles the descent's level loop really
    gathered (counted here at each gather the compiled loop runs): one per
    level below the root from which some lane stepped — none at the root,
    whose stats are one shared row, and none at the level where every lane
    stops."""
    from repro.core import gscpm
    from repro.core.gscpm import select_one

    gathered = []
    real = gscpm.child_stat_tile

    def counting(tree, nodes, **kw):
        if nodes.shape[0] > 1:                      # a tile, not the root row
            jax.debug.callback(lambda n: gathered.append(1), nodes[0])
        return real(tree, nodes, **kw)

    monkeypatch.setattr(gscpm, "child_stat_tile", counting)
    W = 8
    cfg = cfg_for("hex", metrics=True, n_workers=W, vl_rounds=vl_rounds,
                  board_size=3, tree_cap=2048)
    game = cfg.game_obj
    board = game.init_board()
    one = jax.jit(lambda t, k, mx: gscpm.sync_iteration(
        t, board, cfg, jnp.float32(1.0), k, jnp.ones((W,), bool), mx))
    # the oracle: each lane's own scalar descent, on the same noise keys
    depths_of = jax.jit(lambda t, keys: jax.vmap(lambda k: select_one(
        t, board, game, jnp.float32(1.0), jax.random.split(k, 3)[0],
        cfg.select_noise)[1])(keys))
    tree = init_tree(cfg.tree_cap, game.n_actions, 1)
    mx = init_search_metrics()
    stepped = []
    for i in range(100):
        keys = jax.random.split(jax.random.key(i), W)
        if vl_rounds == 1:
            stepped.append(max(int(depths_of(tree, keys).max()) - 1, 0))
        tree, mx = one(tree, keys, mx)
    jax.effects_barrier()
    m = summarize_metrics(mx)
    assert m["depth_max"] >= 3                      # the loop did run
    assert m["tile_gathers"] == len(gathered) > 0
    if vl_rounds == 1:
        assert stepped[0] == 0                      # root not yet expanded
        assert m["tile_gathers"] == sum(stepped)


def test_backup_levels_counts_the_levels_backed_up(monkeypatch):
    """``backup_levels`` is the path levels the backup scattered: the
    leading columns of the paths it was handed that hold a node (counted
    here from those paths on the host), which is the deepest descent + 1,
    + 2 where a lane at that depth expanded."""
    from repro.core import gscpm

    touched = []
    real = gscpm.backup_paths

    def counting(tree, paths, values, weights):
        def record(p):
            touched.append(int(np.flatnonzero((p != tree.cap).any(0))[-1]) + 1)
        jax.debug.callback(record, paths)
        return real(tree, paths, values, weights)

    monkeypatch.setattr(gscpm, "backup_paths", counting)
    W = 8
    cfg = cfg_for("hex", metrics=True, n_workers=W, board_size=3,
                  tree_cap=2048)
    board = cfg.game_obj.init_board()
    one = jax.jit(lambda t, k, mx: gscpm.sync_iteration(
        t, board, cfg, jnp.float32(1.0), k, jnp.ones((W,), bool), mx))
    tree = init_tree(cfg.tree_cap, cfg.game_obj.n_actions, 1)
    total = init_search_metrics()
    levels, deepest = [], []
    for i in range(60):
        keys = jax.random.split(jax.random.key(i), W)
        _, mx = one(tree, keys, init_search_metrics())
        tree, total = one(tree, keys, total)
        m = summarize_metrics(mx)
        levels.append(m["backup_levels"])
        deepest.append(m["depth_max"])
    jax.effects_barrier()
    assert levels == touched[::2] == touched[1::2]
    assert summarize_metrics(total)["backup_levels"] == sum(levels)
    assert all(d + 1 <= n <= d + 2 for n, d in zip(levels, deepest))
    assert levels[0] == 2                            # the root expands
    assert max(deepest) >= 2 and \
        sum(n == d + 2 for n, d in zip(levels, deepest)) > 0


# ------------------------------------------------------------------ tracer ----
def test_trace_recorder_structure_and_validation(tmp_path):
    tr = TraceRecorder(process_name="t")
    tr.instant("evt", {"k": 1})
    with tr.span("quantum", {"rounds": 0}) as args:
        args["rounds"] += 2             # the event carries the exit value
    tr.counter("queue", {"depth": 3})
    d = tr.to_dict()
    assert d["displayTimeUnit"] == "ms"
    assert [e["ph"] for e in d["traceEvents"]] == ["M", "i", "X", "C"]
    assert d["traceEvents"][2]["args"] == {"rounds": 2}
    n = validate_trace(d)
    assert n == len(d["traceEvents"]) == 4
    path = tr.save(str(tmp_path / "t.json"))
    assert validate_trace(path) == n
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="missing"):
        validate_trace({"traceEvents": [{"ph": "i", "ts": 0}]})
    with pytest.raises(ValueError, match="dur"):
        validate_trace({"traceEvents": [{"name": "x", "ph": "X", "ts": 0}]})
    # begin/end pairs are not the recorder's vocabulary: spans are X events
    with pytest.raises(ValueError, match="unknown phase"):
        validate_trace({"traceEvents": [{"name": "b", "ph": "B", "ts": 0}]})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_trace({"traceEvents": [{"name": "e", "ph": "E", "ts": 0}]})


def test_span_without_recorder_records_nothing():
    tr = TraceRecorder()
    before = list(tr.events)
    with span("gscpm_round", round=0, m=4) as args:
        assert args == {"round": 0, "m": 4}
    with span("search_wait"):
        pass
    assert tr.events == before


def test_span_with_recorder_records_one_complete_event():
    tr = TraceRecorder()
    n = len(tr.events)
    with span("gscpm_round", tr, round=3, m=256, tasks=244):
        pass
    (ev,) = tr.events[n:]
    assert ev["name"] == "gscpm_round" and ev["ph"] == "X"
    assert ev["dur"] >= 0.0
    assert ev["args"] == {"round": 3, "m": 256, "tasks": 244}


def test_compile_watch_counts_jit_cache_growth():
    @jax.jit
    def f(x):
        return x + 1

    tr = TraceRecorder()
    tr.watch_compiles("f", f)
    f(jnp.zeros((2,)))                 # compile 1
    f(jnp.zeros((3,)))                 # compile 2 (new shape)
    f(jnp.zeros((3,)))                 # cache hit
    tr.poll_compiles()
    assert tr.compile_counts() == {"f": 2}
    evs = [e for e in tr.events if e["name"] == "jit_compile"]
    assert len(evs) == 1 and evs[0]["args"]["new_programs"] == 2


# ---------------------------------------------------------------- registry ----
def test_metrics_registry_counters_gauges_exposition(tmp_path):
    reg = MetricsRegistry()
    reg.counter("requests_total", "all requests").inc()
    reg.counter("requests_total").inc(2)
    reg.gauge("depth").set(7)
    with pytest.raises(ValueError, match="registered"):
        reg.gauge("requests_total")
    snap = reg.snapshot()
    assert snap["metrics"]["requests_total"]["value"] == 3
    assert snap["metrics"]["depth"]["type"] == "gauge"
    text = reg.exposition()
    assert "# TYPE requests_total counter" in text
    assert "requests_total 3" in text
    assert "depth 7" in text
    path = reg.save(str(tmp_path / "m.json"))
    with open(path) as f:
        assert json.load(f)["metrics"]["depth"]["value"] == 7


# ------------------------------------------------------------ serving trace ----
def test_served_trace_carries_scheduling_vocabulary(tmp_path):
    """A preempting, deadline-bearing serve run records the full event
    vocabulary and the device-plane metrics land in every result —
    without perturbing the served answers (same engine config minus
    observers must produce identical root stats)."""
    def build(tracer=None, registry=None, metrics=False):
        eng = TPFIFOGameEngine(n_slots=1, grain=1, preempt_quanta=1,
                               n_workers=4, tree_cap=512, metrics=metrics,
                               tracer=tracer, registry=registry)
        for i, (g, n) in enumerate([("hex", 64), ("gomoku", 32),
                                    ("hex", 32)]):
            eng.submit(GameRequest(rid=i, game=g, board_size=SIZE,
                                   n_playouts=n, n_tasks=8, seed=i))
        eng.submit(GameRequest(rid=99, game="hex", board_size=SIZE,
                               n_playouts=64, n_tasks=8, seed=9,
                               deadline_s=0.0))      # expires immediately
        eng.run()
        return eng

    tr, reg = TraceRecorder(), MetricsRegistry()
    eng = build(tracer=tr, registry=reg, metrics=True)
    plain = build()

    names = {e["name"] for e in tr.events}
    assert {"admission", "quantum", "preempt", "retire", "deadline_expiry",
            "device_sync", "tick", "queue"} <= names
    assert validate_trace(tr.to_dict()) == len(tr.events)
    path = tr.save(str(tmp_path / "serve.json"))
    assert validate_trace(path) > 0

    for r_obs, r_plain in zip(eng.finished, plain.finished):
        assert r_obs.rid == r_plain.rid
        if not r_obs.result["deadline_expired"]:
            assert "metrics" in r_obs.result
            assert (r_obs.result["root_visits"]
                    == r_plain.result["root_visits"]).all()
        assert r_obs.result["best_move"] == r_plain.result["best_move"]
    m = reg.snapshot()["metrics"]
    assert m["serve_requests_finished_total"]["value"] == 4
    assert m["serve_preemptions_total"]["value"] == eng.stats().n_preemptions
    assert m["serve_deadline_expiries_total"]["value"] >= 1

    # every quantum span carries the work annotation profile.py consumes
    quanta = [e for e in tr.events if e["name"] == "quantum"]
    assert quanta and all(
        e["ph"] == "X" and "dur" in e and "rounds" in e["args"]
        and "iterations" in e["args"] for e in quanta)
    assert sum(e["args"]["rounds"] for e in quanta) == eng.stats().tokens


# -------------------------------------------------------- QueueStats fixes ----
def _ticket(out_len=0, preemptions=0, quanta=0, done_at=None):
    @dataclasses.dataclass
    class R:
        rid: int = 0
        out: list = dataclasses.field(default_factory=list)
        done: bool = False

    t = Ticket(req=R(out=list(range(out_len))), t_submit=0.0)
    t.preemptions = preemptions
    t.quanta = quanta
    if done_at is not None:
        t.t_admit = 0.1
        t.t_done = done_at
    return t


def test_queue_stats_reported_with_no_finished_requests():
    """Regression: a run that preempted requests but finished none used to
    report all-zero telemetry."""
    st = QueueStats.from_tickets([
        _ticket(out_len=3, preemptions=2, quanta=5),
        _ticket(out_len=1, preemptions=1, quanta=2)])
    assert st.n_finished == 0
    assert st.n_preemptions == 3
    assert st.quanta == 7
    assert st.tokens == 4
    assert st.wall_s == 0.0 and st.latency_p95 == 0.0


def test_queue_stats_mixed_finished_and_unfinished():
    st = QueueStats.from_tickets([
        _ticket(out_len=4, preemptions=1, quanta=3, done_at=1.0),
        _ticket(out_len=2, preemptions=2, quanta=2)])      # still queued
    assert st.n_finished == 1
    assert st.n_preemptions == 3                # unfinished work counted
    assert st.quanta == 5
    assert st.tokens == 6
    # percentiles/throughput stay defined over the finished set only
    assert st.latency_p50 == pytest.approx(1.0)
    assert st.throughput_tok_s == pytest.approx(4 / 1.0)


def test_engine_stats_cover_active_and_queued_tickets():
    """Mid-run stats() sees preemptions/quanta of requests that have not
    finished (search dispatch stubbed out for speed)."""
    with mock.patch("repro.serve.games.run_schedule_round",
                    lambda tree, board, cfg, key, rnd, cp: tree):
        eng = TPFIFOGameEngine(n_slots=1, grain=1, preempt_quanta=1,
                               n_workers=4, tree_cap=64)
        for i in range(3):
            eng.submit(GameRequest(rid=i, game="hex", board_size=SIZE,
                                   n_playouts=512, n_tasks=64, seed=i))
        eng.run(max_ticks=3, on_exhaust="ignore")   # deliberate early stop
    st = eng.stats()
    assert st.n_finished == 0
    assert st.n_unfinished == 3                # PR 9: leftovers are visible
    assert st.quanta > 0                       # progress before any finish
    assert st.tokens > 0
    assert st.n_preemptions > 0


# ----------------------------------------------------------------- profile ----
def _synthetic_trace(points, t_round_us, t_iter_us, workers=8):
    """X spans with dur = rounds*t_round + rounds*m*workers*t_iter."""
    evs, ts = [], 0.0
    for rounds, m in points:
        iters = rounds * m
        dur = rounds * t_round_us + iters * workers * t_iter_us
        evs.append({"name": "gscpm_round", "ph": "X", "pid": 0, "tid": 0,
                    "ts": ts, "dur": dur,
                    "args": {"rounds": rounds, "iterations": iters,
                             "workers": workers}})
        ts += dur + 10.0
    return {"traceEvents": evs}


def test_profile_fit_recovers_known_burden():
    from repro.obsv.profile import fit_dispatch_profile, measured_dag_model

    trace = _synthetic_trace(
        [(4, 2), (2, 16), (8, 1), (1, 64), (16, 4)],
        t_round_us=500.0, t_iter_us=2.0, workers=8)
    prof = fit_dispatch_profile(trace)
    assert prof["identifiable"]
    assert prof["n_workers"] == 8
    assert prof["t_round_s"] == pytest.approx(500e-6, rel=1e-6)
    assert prof["t_iter_s"] == pytest.approx(2e-6, rel=1e-6)
    # burden terms in t_iter units: t_round/t_iter, split over W lanes
    assert prof["t_round_units"] == pytest.approx(250.0, rel=1e-5)
    assert prof["t_spawn_units"] == pytest.approx(250.0 / 8, rel=1e-5)
    assert prof["fit_rms_rel"] < 1e-6
    model = measured_dag_model(prof)
    assert model.t_iter == 1.0
    assert model.t_round == pytest.approx(250.0, rel=1e-5)


def test_profile_fit_rank_deficient_fallback():
    from repro.obsv.profile import fit_dispatch_profile

    # all spans share one rounds:iterations ratio -> terms inseparable
    trace = _synthetic_trace([(2, 8), (4, 8), (8, 8)],
                             t_round_us=100.0, t_iter_us=1.0)
    prof = fit_dispatch_profile(trace)
    assert not prof["identifiable"]
    assert prof["t_iter_s"] > 0.0              # never a degenerate model


def test_profile_fit_excludes_compile_tainted_spans():
    from repro.obsv.profile import fit_dispatch_profile

    trace = _synthetic_trace(
        [(4, 2), (2, 16), (8, 1), (1, 64), (16, 4)],
        t_round_us=500.0, t_iter_us=2.0, workers=8)
    first = trace["traceEvents"][0]
    first["dur"] += 3_000_000.0                # a 3 s compile stall
    trace["traceEvents"].append(
        {"name": "jit_compile", "ph": "i", "s": "t", "pid": 0, "tid": 0,
         "ts": first["ts"] + 1.0, "args": {"fn": "run_chunk"}})
    prof = fit_dispatch_profile(trace)
    assert prof["n_excluded_compile"] == 1
    assert prof["t_round_s"] == pytest.approx(500e-6, rel=1e-4)


def test_profile_requires_dispatch_spans():
    from repro.obsv.profile import fit_dispatch_profile

    with pytest.raises(ValueError, match="dispatch spans"):
        fit_dispatch_profile({"traceEvents": [
            {"name": "tick", "ph": "X", "ts": 0, "dur": 1}]})


def test_measured_vs_analytic_table_renders():
    from repro.obsv.profile import (fit_dispatch_profile, format_table,
                                    measured_vs_analytic)

    trace = _synthetic_trace([(4, 2), (1, 64)], 500.0, 2.0)
    rows = measured_vs_analytic(fit_dispatch_profile(trace),
                                n_playouts=256, task_counts=(8, 64),
                                n_cores=61)
    assert [r["n_tasks"] for r in rows] == [8, 64]
    for r in rows:
        assert r["parallelism_measured"] <= r["parallelism_analytic"] * 1.01
        assert r["burdened_parallelism_measured"] > 0
    table = format_table(rows)
    assert "par(measured)" in table and len(table.splitlines()) == 4


# ------------------------------------------------------- traced search CLI ----
def test_gscpm_search_tracer_records_fittable_rounds():
    from repro.obsv.profile import fit_dispatch_profile

    tr = TraceRecorder()
    cfg = cfg_for("hex", n_playouts=32, n_tasks=8)
    board = cfg.game_obj.init_board()
    gscpm_search(board, 1, cfg, jax.random.key(0))          # warm
    for n_t in (4, 8, 16):
        c = dataclasses.replace(cfg, n_playouts=32, n_tasks=n_t)
        gscpm_search(board, 1, c, jax.random.key(0), tracer=tr)
    spans = [e for e in tr.events if e["name"] == "gscpm_round"]
    assert spans and all(e["args"]["rounds"] == 1 for e in spans)
    expect = sum(
        r.m
        for n_t in (4, 8, 16)
        for r in scheduler.make_schedule(32, n_t, cfg.n_workers,
                                         cfg.scheduler))
    assert sum(e["args"]["iterations"] for e in spans) == expect
    prof = fit_dispatch_profile(tr, n_workers=cfg.n_workers)
    assert prof["t_iter_s"] >= 0.0
    assert validate_trace(tr.to_dict()) == len(tr.events)


# ----------------------------------------- phase scopes and program spans ----
PHASE_SCOPES = ("descent", "expand", "leaf_eval", "backup")
SEARCH_SPANS = ("gscpm_search", "search_init", "gscpm_round", "search_wait",
                "search_stats")


def _op_name_paths(hlo_text: str) -> list[list[str]]:
    return [p.split("/") for p in re.findall(r'op_name="([^"]*)"', hlo_text)]


def _compiled_text(program: str) -> str:
    from repro.core.gscpm import fold_task_keys
    from repro.core.root_parallel import (fold_member_task_keys,
                                          init_sync_state, sync_root_stats)
    from repro.core.tree import init_forest

    cfg = cfg_for("hex", vl_rounds=2 if program == "run_chunk_vl2" else 1)
    game, W, E = cfg.game_obj, cfg.n_workers, 2
    task_ids = jnp.arange(W, dtype=jnp.int32)
    m, cp = jnp.int32(2), jnp.float32(1.0)
    if program == "sync_root_stats":
        forest = init_forest(E, cfg.tree_cap, game.n_actions, 1)
        return sync_root_stats.lower(
            forest, init_sync_state(E, game.n_actions),
            game.n_actions).compile().as_text()
    if program == "run_chunk_forest":
        forest = init_forest(E, cfg.tree_cap, game.n_actions, 1)
        keys = fold_member_task_keys(
            fold_task_keys(jax.random.key(0), jnp.arange(E)), task_ids)
        boards = jnp.tile(game.init_board()[None, :], (E, 1))
        return run_chunk_forest.lower(
            forest, boards, cfg, keys, jnp.ones((E, W), bool), m,
            cp).compile().as_text()
    tree = init_tree(cfg.tree_cap, game.n_actions, 1)
    keys = fold_task_keys(jax.random.key(0), task_ids)
    return run_chunk.lower(tree, game.init_board(), cfg, keys,
                           jnp.ones((W,), bool), m, cp).compile().as_text()


@pytest.mark.parametrize("program", ["run_chunk", "run_chunk_vl2",
                                     "run_chunk_forest", "sync_root_stats"])
def test_phase_scopes_in_compiled_programs(program):
    """The named phases reach the compiled program's op_name metadata,
    where a device trace can attribute each op; the forest's merge too."""
    paths = _op_name_paths(_compiled_text(program))
    parts = {p for path in paths for p in path}
    want = {"merge"} if program == "sync_root_stats" else set(PHASE_SCOPES)
    assert want <= parts
    if program == "run_chunk_vl2":
        # the virtual-loss add between selection rounds is backup work
        assert any("descent" in p and "backup" in p[p.index("descent"):]
                   for p in paths)


def _profile(tmp_path, fn, names=SEARCH_SPANS):
    """Run ``fn`` under the JAX profiler; return its host-plane events
    named in ``names`` as (start_ns, name, stats dict)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    events = sorted(
        (e.start_ns, e.name, dict(e.stats))
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name in names)
    return out, events


@pytest.mark.parametrize("driver", ["gscpm_search", "gscpm_search_batch"])
def test_profiler_trace_holds_the_search_spans(tmp_path, driver):
    cfg = cfg_for("hex", n_playouts=48, n_tasks=12)
    board = cfg.game_obj.init_board()
    schedule = scheduler.make_schedule(cfg.n_playouts, cfg.n_tasks,
                                       cfg.n_workers, cfg.scheduler)
    if driver == "gscpm_search":
        run = lambda: gscpm_search(board, 1, cfg, jax.random.key(3))  # noqa
        trees = 1
    else:
        run = lambda: gscpm_search_batch(  # noqa: E731
            board, 1, cfg, jax.random.key(3), n_trees=2, shard="off")
        trees = 2
    run()                                  # compile outside the trace
    _, events = _profile(tmp_path, run)
    names = [n for _, n, _ in events]
    for name in ("gscpm_search", "search_init", "search_wait",
                 "search_stats"):
        assert names.count(name) == 1, name
    rounds = [st for _, n, st in events if n == "gscpm_round"]
    assert len(rounds) == len(schedule) == 3
    for r, (st, rnd) in enumerate(zip(rounds, schedule)):
        assert st["round"] == r and st["m"] == rnd.m
        assert st["tasks"] == trees * int(rnd.active.sum())
    # the round spans lie inside the search span, after its init
    start = {n: s for s, n, _ in events}
    assert start["gscpm_search"] <= start["search_init"] < min(
        s for s, n, _ in events if n == "gscpm_round")


def test_profiler_trace_holds_the_forest_spans(tmp_path):
    """The forest's own spans: the placement once, one ``forest_merge`` per
    merge with its round and index, and the search span's placement and
    merge counts."""
    cfg = cfg_for("hex", n_playouts=48, n_tasks=12)
    board = cfg.game_obj.init_board()
    run = lambda: gscpm_search_batch(  # noqa: E731
        board, 1, cfg, jax.random.key(3), n_trees=2, merge_every=2,
        shard="off")
    run()                                  # compile outside the trace
    (_, st), events = _profile(tmp_path, run, names=(
        "gscpm_search", "forest_place", "forest_merge", "gscpm_round"))
    names = [n for _, n, _ in events]
    assert names.count("forest_place") == 1
    merges = [a for _, n, a in events if n == "forest_merge"]
    # 3 rounds, merged after round 1 and, as always, after the last
    assert [(a["round"], a["sync"]) for a in merges] == [(1, 0), (2, 1)]
    (search,) = [a for _, n, a in events if n == "gscpm_search"]
    assert (search["n_devices"], search["padded_members"],
            search["n_syncs"]) == (1, 0, st["n_syncs"]) == (1, 0, 2)
    start = {n: s for s, n, _ in events}
    assert start["gscpm_search"] <= start["forest_place"] < min(
        s for s, n, _ in events if n == "gscpm_round")


def test_search_bit_identical_with_the_profiler_running(tmp_path):
    cfg = cfg_for("hex")
    board = cfg.game_obj.init_board()
    plain, s_plain = gscpm_search(board, 1, cfg, jax.random.key(5))
    (traced, s_traced), _ = _profile(
        tmp_path, lambda: gscpm_search(board, 1, cfg, jax.random.key(5)))
    assert trees_equal(plain, traced)
    assert s_plain["best_move"] == s_traced["best_move"]
