"""Chaos suite for the TPFIFO serving stack (DESIGN.md §17).

The center-of-gravity pin: under a seeded ``FaultPlan`` (dispatch
failures, NaN poisoning, clock stalls, duplicate submissions) the engine
completes every non-shed request with results **bit-identical** to a
fault-free run of the same seeds, never crashes the driver loop,
quarantines failing slots while serving on the survivors, and does all
of it with ZERO new jit compilations.

Class-key discipline: jit caches are shared across the pytest process,
so this file owns the (board_size=5, tree_cap=256) game classes —
test_serve_games owns 512@5/6, test_obsv owns 384@4, test_reroot owns
1024@5. Compile-count assertions here stay meaningful as long as no
other file serves these classes.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import scheduler
from repro.core.gscpm import gscpm_search, run_chunk
from repro.core.tree import init_tree, root_summary
from repro.serve import resilience as rz
from repro.serve.games import GameRequest, TPFIFOGameEngine

SIZE = 5
CAP = 256
WORKERS = 4


def engine(**kw):
    kw.setdefault("n_slots", 1)
    kw.setdefault("grain", 1)
    kw.setdefault("n_workers", WORKERS)
    kw.setdefault("tree_cap", CAP)
    return TPFIFOGameEngine(**kw)


def req(rid, game="hex", **kw):
    kw.setdefault("board_size", SIZE)
    kw.setdefault("n_playouts", 64)
    kw.setdefault("n_tasks", 16)     # 4 schedule rounds at W=4
    kw.setdefault("seed", rid if isinstance(rid, int) else 0)
    return GameRequest(rid=rid, game=game, **kw)


def reference(eng, r):
    """The uninterrupted search a recovered request must match bit-for-bit."""
    cfg = eng.request_cfg(r)
    board = (cfg.game_obj.init_board() if r.board is None
             else jnp.asarray(r.board, jnp.int8))
    tree, _ = gscpm_search(board, r.to_move, cfg, jax.random.key(r.seed))
    return root_summary(tree, cfg.game_obj.n_actions)


def assert_same_search(r, ref):
    np.testing.assert_array_equal(r.result["root_visits"],
                                  ref["root_visits"])
    np.testing.assert_array_equal(r.result["root_wins"], ref["root_wins"])
    assert r.result["best_move"] == ref["best_move"]
    assert r.result["root_value"] == ref["root_value"]


@pytest.fixture(scope="module")
def warm():
    """Compile both game classes once so compile-count deltas isolate
    chaos churn from first-touch compilation."""
    eng = engine(n_slots=1)
    eng.submit(req("warm-hex", "hex", seed=0))
    eng.submit(req("warm-gom", "gomoku", seed=0))
    eng.run()
    return run_chunk._cache_size()


# -------------------------------------------------------------- fault plan ----
def test_fault_plan_deterministic_and_seeded():
    a = rz.FaultPlan.generate(seed=9, n_ticks=50, n_slots=4, rate=0.2)
    b = rz.FaultPlan.generate(seed=9, n_ticks=50, n_slots=4, rate=0.2)
    c = rz.FaultPlan.generate(seed=10, n_ticks=50, n_slots=4, rate=0.2)
    assert a.events == b.events
    assert a.events != c.events
    assert all(ev.kind in rz.FAULT_KINDS for ev in a.events)
    assert all(0 <= ev.tick < 50 and 0 <= ev.slot < 4 for ev in a.events)
    # rate sanity on the Bernoulli grid: 200 cells at p=.2 -> ~40
    assert 10 <= len(a.events) <= 80


def test_fault_plan_validates_inputs():
    with pytest.raises(ValueError):
        rz.FaultPlan.generate(seed=0, n_ticks=5, n_slots=1, rate=1.5)
    with pytest.raises(ValueError):
        rz.FaultPlan.generate(seed=0, n_ticks=5, n_slots=1, rate=0.1,
                              kinds=("segfault",))


def test_injector_arms_per_tick_and_counts_fired():
    plan = rz.FaultPlan(events=(
        rz.FaultEvent(tick=0, slot=0, kind="dispatch_error"),
        rz.FaultEvent(tick=0, slot=1, kind="poison_nan"),
        rz.FaultEvent(tick=1, slot=0, kind="clock_stall", stall_s=1.0),
    ))
    inj = rz.FaultInjector(plan)
    driver_evs = inj.begin_tick(0)
    assert driver_evs == []                       # both tick-0 kinds are slot-level
    assert inj.dispatch_fault(1) is None          # wrong slot
    assert inj.dispatch_fault(0).kind == "dispatch_error"
    assert inj.dispatch_fault(0) is None          # consumed
    assert inj.poison(1).kind == "poison_nan"
    driver_evs = inj.begin_tick(1)
    assert [ev.kind for ev in driver_evs] == ["clock_stall"]
    assert inj.dispatch_fault(0) is None          # tick 0 events disarmed
    inj.record_fired(plan.events[0])
    assert inj.summary()["fired"] == {"dispatch_error": 1}


# ------------------------------------------------------------ result guard ----
def _good_res(n=4, total=8.0):
    v = np.full(n, total / n)
    return {"root_visits": v, "root_wins": v * 0.5, "best_move": 0,
            "root_value": 0.5, "tree_nodes": n + 1}


def test_validate_result_accepts_clean_and_flags_each_violation():
    assert rz.validate_result(_good_res(), 8) == []
    assert rz.validate_result(_good_res(), None) == []   # warm: no conservation
    bad = _good_res()
    bad["root_wins"] = bad["root_wins"] + np.nan
    assert any("wins not finite" in v for v in rz.validate_result(bad, 8))
    bad = _good_res()
    bad["root_visits"][0] = -1.0
    out = rz.validate_result(bad, 8)
    assert any("non-negative" in v for v in out)
    bad = _good_res()
    bad["root_wins"][0] = bad["root_visits"][0] + 1     # wins > visits
    assert any("outside [0, visits]" in v for v in rz.validate_result(bad, 8))
    assert any("conservation" in v for v in rz.validate_result(_good_res(), 9))
    bad = _good_res()
    bad["root_value"] = float("nan")
    assert any("root value" in v for v in rz.validate_result(bad, 8))
    bad = _good_res()
    bad["best_move"] = 99
    assert any("best_move" in v for v in rz.validate_result(bad, 8))


# --------------------------------------------------------------- snapshots ----
def test_snapshot_restore_roundtrip_and_poison_detection():
    tree = init_tree(64, 8, 1)
    tree = tree._replace(visits=tree.visits.at[0].set(4.0),
                         wins=tree.wins.at[0].set(2.0))
    snap = rz.snapshot_search(tree, None, round_idx=2, playouts=16, out_len=2)
    assert rz.snapshot_is_clean(snap)
    back, metrics = rz.restore_search(snap)
    assert metrics is None
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype
    dirty = rz.snapshot_search(rz.poison_root_stats(tree), None, 2, 16, 2)
    assert not rz.snapshot_is_clean(dirty)


# ---------------------------------------------------- recovery bit-identity ----
def test_dispatch_fault_retries_bit_identical(warm):
    plan = rz.FaultPlan(events=(
        rz.FaultEvent(tick=1, slot=0, kind="dispatch_error"),
        rz.FaultEvent(tick=2, slot=0, kind="dispatch_error"),
    ))
    inj = rz.FaultInjector(plan)
    eng = engine(injector=inj, retry_backoff=(1, 2))
    r = req("df", seed=3)
    assert eng.submit(r)
    eng.run(max_ticks=500)
    assert inj.fired["dispatch_error"] >= 1
    assert r.result["status"] == "answered"
    assert r.result["retries"] >= 1
    assert eng.stats().n_retries >= 1
    assert_same_search(r, reference(eng, r))
    assert run_chunk._cache_size() == warm      # zero recompiles


def test_poison_guard_rejects_and_recovers_bit_identical(warm):
    plan = rz.FaultPlan(events=(
        rz.FaultEvent(tick=2, slot=0, kind="poison_nan"),))
    inj = rz.FaultInjector(plan)
    eng = engine(injector=inj)
    r = req("poison", seed=7)
    eng.submit(r)
    eng.run(max_ticks=500)
    assert inj.fired["poison_nan"] == 1
    # the corrupted answer never shipped: it became a retry that recovered
    assert r.result["status"] == "answered"
    assert r.result["retries"] >= 1
    assert np.isfinite(r.result["root_wins"]).all()
    assert_same_search(r, reference(eng, r))
    assert run_chunk._cache_size() == warm


def test_mixed_chaos_generated_plan_all_complete_bit_identical(warm):
    plan = rz.FaultPlan.generate(seed=13, n_ticks=60, n_slots=4, rate=0.3)
    inj = rz.FaultInjector(plan)
    eng = engine(n_slots=2, grain=2, injector=inj, quarantine_after=3,
                 retry_backoff=(1, 4))
    reqs = [req(i, ("hex", "gomoku")[i % 2], seed=i) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    for r in reqs:
        assert r.result["status"] == "answered"
        assert_same_search(r, reference(eng, r))
    assert run_chunk._cache_size() == warm


# ---------------------------------------------------------------- quarantine ----
def test_slot_quarantined_after_consecutive_failures_serves_on_survivor(warm):
    # slot 0 fails its dispatch EVERY tick; slot 1 is healthy
    evs = tuple(rz.FaultEvent(tick=t, slot=0, kind="dispatch_error")
                for t in range(100))
    eng = engine(n_slots=2, injector=rz.FaultInjector(rz.FaultPlan(evs)),
                 quarantine_after=2)
    reqs = [req(i, seed=i) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    st = eng.stats()
    assert st.n_quarantined == 1
    assert st.n_retries >= 2                    # the strikes that led there
    for r in reqs:
        assert r.result["status"] == "answered"
        assert_same_search(r, reference(eng, r))
    assert run_chunk._cache_size() == warm


def test_last_healthy_slot_never_quarantined():
    # every slot faulted every tick: at most n_slots-1 quarantines, and the
    # engine still drains on the last healthy slot once the plan runs dry
    evs = tuple(rz.FaultEvent(tick=t, slot=s, kind="dispatch_error")
                for t in range(8) for s in range(2))
    eng = engine(n_slots=2, injector=rz.FaultInjector(rz.FaultPlan(evs)),
                 quarantine_after=2, retry_backoff=(1, 2))
    reqs = [req(f"lh{i}", seed=i) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    assert eng.stats().n_quarantined <= 1
    assert all(r.result["status"] == "answered" for r in reqs)


# ------------------------------------------------------- shedding / dedup ----
def test_bounded_admission_sheds_with_status(warm):
    eng = engine(max_queue=2)
    rs = [req(f"s{i}", seed=i) for i in range(4)]
    assert eng.submit(rs[0]) and eng.submit(rs[1])
    assert not eng.submit(rs[2])               # class queue full -> shed
    assert rs[2].done and rs[2].result["status"] == "shed"
    assert rs[2].result["reason"] == "queue_full"
    # shedding is PER CLASS: a gomoku request still gets in
    g = req("g0", "gomoku", seed=1)
    assert eng.submit(g)
    eng.run(max_ticks=2000)
    st = eng.stats()
    assert st.n_shed == 1
    assert {r.rid for r in eng.finished} == {"s0", "s1", "g0"}
    for r in (rs[0], rs[1], g):
        assert_same_search(r, reference(eng, r))


def test_duplicate_submission_dropped_not_double_served():
    eng = engine()
    r = req("dup", seed=2)
    assert eng.submit(r)
    assert not eng.submit(r)                   # same rid still pending
    eng.run(max_ticks=1000)
    assert len(eng.finished) == 1
    assert r.result["status"] == "answered"


def test_injected_duplicate_submit_is_deduped(warm):
    plan = rz.FaultPlan(events=(
        rz.FaultEvent(tick=1, slot=0, kind="duplicate_submit"),))
    inj = rz.FaultInjector(plan)
    eng = engine(injector=inj)
    rs = [req(f"q{i}", seed=i) for i in range(2)]
    for r in rs:
        eng.submit(r)
    eng.run(max_ticks=1000)
    assert inj.fired.get("duplicate_submit", 0) == 1
    assert len(eng.finished) == 2              # each original served once
    assert all(r.result["status"] == "answered" for r in rs)


# ------------------------------------------------------------- clock stall ----
def test_clock_stall_expires_deadline_cleanly(warm):
    plan = rz.FaultPlan(events=(
        rz.FaultEvent(tick=1, slot=0, kind="clock_stall", stall_s=60.0),))
    inj = rz.FaultInjector(plan)
    eng = engine(injector=inj)
    r = req("cs", seed=4, deadline_s=30.0)
    eng.submit(r)
    eng.run(max_ticks=500)
    assert inj.fired["clock_stall"] == 1
    assert r.result["status"] == "deadline_expired"
    assert r.result["deadline_expired"]
    assert 0 < r.result["rounds"] < r.result["rounds_total"]
    assert np.isfinite(r.result["root_wins"]).all()   # partial stats, clean
    assert run_chunk._cache_size() == warm


# --------------------------------------------------------- exhaust detection ----
def test_run_exhaust_raises_with_unfinished_rids():
    with mock.patch("repro.serve.games.run_schedule_round",
                    lambda tree, board, cfg, key, rnd, cp: tree):
        eng = engine(preempt_quanta=1, tree_cap=64, guard=False)
        for i in range(3):
            eng.submit(req(i, seed=i))
        with pytest.raises(RuntimeError, match="max_ticks=2 exhausted"):
            eng.run(max_ticks=2)
        with pytest.warns(RuntimeWarning, match="unfinished"):
            eng.run(max_ticks=1, on_exhaust="warn")
        assert eng.stats().n_unfinished == 3
        eng.run(on_exhaust="ignore", max_ticks=1)     # deliberate early stop


# --------------------------------------------------------- submit validation ----
def test_submit_validation_typed_errors():
    eng = engine()
    with pytest.raises(ValueError, match="n_playouts"):
        eng.submit(req("v0", n_playouts=0))
    with pytest.raises(ValueError, match="n_playouts"):
        eng.submit(req("v1", n_playouts=2.5))
    with pytest.raises(ValueError, match="n_tasks"):
        eng.submit(req("v2", n_tasks=-1))
    with pytest.raises(ValueError, match="to_move"):
        eng.submit(req("v3", to_move=3))
    with pytest.raises(ValueError, match="cp"):
        eng.submit(req("v4", cp=float("nan")))
    with pytest.raises(ValueError, match="cp"):
        eng.submit(req("v5", cp=-0.5))
    with pytest.raises(TypeError, match="cp"):
        eng.submit(req("v6", cp="high"))
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(req("v7", deadline_s=-1.0))
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(req("v8", deadline_s=float("inf")))
    with pytest.raises(ValueError, match="board shape"):
        eng.submit(req("v9", board=np.zeros(7, np.int8)))
    with pytest.raises(TypeError, match="board dtype"):
        eng.submit(req("v10", board=np.zeros(SIZE * SIZE, np.float32)))
    with pytest.raises(ValueError, match="board cells"):
        eng.submit(req("v11", board=np.full(SIZE * SIZE, 7, np.int8)))
    with pytest.raises(ValueError):
        eng.submit(req("v12", game="chess"))          # unregistered game
    assert not eng.has_work()                         # nothing leaked in


# --------------------------------------------------------- chaos drain (PBT) ----
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_chaos_trace_always_drains(seed, warm):
    """Mixed hex+gomoku Poisson trace + random fault plan: the engine
    always drains; every request ends in exactly one of
    answered | shed | deadline_expired; answered results pass the guard;
    fully-run answered searches are bit-identical to fault-free refs."""
    rng = np.random.default_rng(seed)
    plan = rz.FaultPlan.generate(
        seed=seed, n_ticks=80, n_slots=4, rate=float(rng.uniform(0.05, 0.4)))
    eng = engine(n_slots=2, grain=int(rng.integers(1, 3)),
                 injector=rz.FaultInjector(plan), quarantine_after=3,
                 max_queue=8, retry_backoff=(1, 4))
    n = int(rng.integers(4, 9))
    reqs = [req(i, ("hex", "gomoku")[int(rng.integers(2))], seed=i,
                deadline_s=(None if rng.random() < 0.7
                            else float(rng.uniform(0.5, 2.0))))
            for i in range(n)]
    arrivals = np.cumsum(rng.exponential(0.01, n))
    eng.run_trace(list(zip(arrivals, reqs)), max_ticks=20_000)
    statuses = {r.rid: r.result["status"] for r in reqs}
    assert all(s in ("answered", "shed", "deadline_expired")
               for s in statuses.values())
    assert all(r.done for r in reqs)
    for r in reqs:
        if r.result["status"] != "answered":
            continue
        expected = (None if r.result.get("reused_visits")
                    else r.result["playouts"])
        assert rz.validate_result(r.result, expected) == []
        if r.result["rounds"] == r.result["rounds_total"]:
            assert_same_search(r, reference(eng, r))
    assert run_chunk._cache_size() == warm
