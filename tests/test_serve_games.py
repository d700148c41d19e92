"""Serving-equivalence suite for TPFIFO game-search serving (DESIGN.md §14).

The correctness anchors of `repro.serve.games`:

- **bit-identity**: a search served in m-round quanta with forced
  tail-requeue preemption produces bit-identical root move statistics to
  the same search run uninterrupted (`gscpm_search`, same RNG schedule) —
  for hex AND gomoku, from empty and midgame positions;
- **FIFO admission** is preserved under mixed game classes and mixed
  playout budgets, and a saturated class never head-of-line-blocks
  another class's traffic;
- **one compiled quantum per game class**: per-request budget/Cp/grain/
  deadline sweeps across admissions trigger ZERO recompiles, and mixed
  hex+gomoku traffic compiles exactly one `run_chunk` program per class;
- **deadline expiry** retires a request with whatever stats it has —
  never a crash, never a poisoned slot.
"""

from __future__ import annotations

import collections
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import scheduler
from repro.core.gscpm import gscpm_search, run_chunk
from repro.core.tree import root_summary
from repro.serve.games import GameRequest, TPFIFOGameEngine
from repro.serve.tpfifo import QueueStats

SIZE = 5


def engine(**kw):
    kw.setdefault("n_slots", 1)
    kw.setdefault("grain", 1)
    kw.setdefault("n_workers", 4)
    kw.setdefault("tree_cap", 512)
    return TPFIFOGameEngine(**kw)


def req(rid, game="hex", **kw):
    kw.setdefault("board_size", SIZE)
    kw.setdefault("n_playouts", 64)
    kw.setdefault("n_tasks", 8)
    kw.setdefault("seed", rid)
    return GameRequest(rid=rid, game=game, **kw)


def reference(eng, r):
    """The uninterrupted search the served request must match bit-for-bit."""
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
    assert r.result["tree_nodes"] == ref["tree_nodes"]


def midgame_board(game, k=4, seed=0):
    rng = np.random.default_rng(seed)
    b = np.zeros(SIZE * SIZE, dtype=np.int8)
    for t, i in enumerate(rng.permutation(SIZE * SIZE)[:k]):
        b[i] = 1 if t % 2 == 0 else 2
    return b


# ------------------------------------------------------------ bit-identity ----
@pytest.mark.parametrize("game", ["hex", "gomoku"])
def test_preempted_quanta_bit_identical_to_uninterrupted(game):
    """Two same-class requests on ONE slot with preempt_quanta=1 force
    tail-requeue preemption every quantum; each interleaved, repeatedly
    preempted search must equal its uninterrupted twin bit-for-bit —
    including a midgame-position request with to_move=2."""
    eng = engine(preempt_quanta=1)
    reqs = [req(0, game),
            req(1, game, n_playouts=32, n_tasks=4,
                board=midgame_board(game), to_move=2)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 2
    assert eng.stats().n_preemptions > 0       # the forcing actually forced
    for r in reqs:
        assert not r.result["deadline_expired"]
        assert r.result["rounds"] == r.result["rounds_total"]
        assert_same_search(r, reference(eng, r))


def test_mixed_class_traffic_does_not_perturb_searches():
    """Hex and gomoku interleaved through one engine with preemption: every
    request still matches its uninterrupted single-tenant search."""
    eng = engine(n_slots=1, grain=2, preempt_quanta=1)
    reqs = [req(0, "hex"), req(1, "gomoku", n_playouts=48, n_tasks=12),
            req(2, "hex", n_playouts=32, n_tasks=4, cp=1.7),
            req(3, "gomoku", n_playouts=64, n_tasks=16, cp=0.4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert_same_search(r, reference(eng, r))


# ------------------------------------------------------------- admission ----
def test_fifo_admission_order_mixed_classes_and_budgets():
    """With free slots for everyone, global admission order == submission
    order regardless of game class or playout budget."""
    eng = engine(n_slots=3, grain=2)
    mix = [("hex", 32), ("gomoku", 64), ("hex", 16), ("gomoku", 32),
           ("hex", 48)]
    reqs = [req(i, g, n_playouts=n, n_tasks=4) for i, (g, n) in enumerate(mix)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert eng.admission_order == [0, 1, 2, 3, 4]
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]


def test_saturated_class_never_blocks_other_class():
    """hex0, hex1, gomoku0 on 1-slot pools: hex1 must wait for hex0's slot,
    but gomoku0 passes it (per-class pools kill cross-game HOL blocking);
    per-class admission order still follows submission order."""
    eng = engine(n_slots=1, grain=2)
    reqs = [req(0, "hex"), req(1, "hex", n_playouts=32, n_tasks=4),
            req(2, "gomoku", n_playouts=32, n_tasks=4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.admission_order[:2] == [0, 2]   # gomoku passed the full pool
    assert eng.admission_order == [0, 2, 1]
    games = {r.rid: r.game for r in reqs}
    hex_order = [rid for rid in eng.admission_order if games[rid] == "hex"]
    assert hex_order == [0, 1]


# ------------------------------------------------------------ compilation ----
def test_zero_recompiles_across_budget_cp_grain_deadline_sweeps():
    """Once the game classes are warm, per-request n_playouts/n_tasks/Cp/
    deadline sweeps and engine grain/policy/preemption changes never grow
    run_chunk's jit cache."""
    warm = engine()
    for i, g in enumerate(["hex", "gomoku"]):
        warm.submit(req(i, g, n_playouts=16, n_tasks=4))
    warm.run()
    before = run_chunk._cache_size()

    eng = engine(n_slots=2, grain=3, policy="rebalance", preempt_quanta=2)
    sweeps = [("hex", 16, 2, 0.4, None), ("gomoku", 48, 6, 1.7, 30.0),
              ("hex", 96, 12, 2.5, 30.0), ("gomoku", 24, 24, 0.9, None),
              ("hex", 40, 5, 1.0, 30.0)]
    reqs = [req(i, g, n_playouts=n, n_tasks=t, cp=cp, deadline_s=dl)
            for i, (g, n, t, cp, dl) in enumerate(sweeps)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(sweeps)
    assert run_chunk._cache_size() == before


def test_one_compiled_quantum_per_game_class():
    """Mixed hex+gomoku traffic at a fresh board size compiles EXACTLY one
    quantum program per game class, admissions/preemptions included."""
    size = 6                       # unused by any other test in this module
    before = run_chunk._cache_size()
    eng = engine(n_slots=1, grain=1, preempt_quanta=1)
    for i, (g, n) in enumerate([("hex", 32), ("gomoku", 16), ("hex", 16),
                                ("gomoku", 32)]):
        eng.submit(req(i, g, board_size=size, n_playouts=n, n_tasks=4,
                       cp=0.5 + 0.3 * i))
    done = eng.run()
    assert len(done) == 4
    assert run_chunk._cache_size() == before + 2


# -------------------------------------------------------------- deadlines ----
def test_deadline_expiry_retires_without_poisoning_slot():
    """An already-expired deadline retires the request with empty stats
    (best_move -1, zero visits) and the slot serves the next request to its
    FULL budget — no crash, no poisoned slot."""
    eng = engine()
    dead = req(0, deadline_s=0.0)
    follow = req(1, n_playouts=32, n_tasks=4)
    eng.submit(dead)
    eng.submit(follow)
    done = eng.run()
    assert len(done) == 2
    assert dead.done and dead.result["deadline_expired"]
    assert dead.result["rounds"] == 0 and dead.result["playouts"] == 0
    assert dead.result["best_move"] == -1
    assert (dead.result["root_visits"] == 0).all()
    assert not follow.result["deadline_expired"]
    assert follow.result["rounds"] == follow.result["rounds_total"]
    assert follow.result["playouts"] == 32
    assert_same_search(follow, reference(eng, follow))
    assert eng.stats().n_finished == 2


def test_mid_search_deadline_ships_partial_stats():
    """A deadline expiring mid-search retires the request with whatever the
    tree holds: a consistent partial root summary (visits account exactly
    for the rounds that ran)."""
    eng = engine(grain=1)
    r = req(0, n_playouts=8192, n_tasks=2048, deadline_s=0.2)  # 512 rounds
    eng.submit(r)
    eng.run()
    assert r.done and r.result["deadline_expired"]
    assert 0 < r.result["rounds"] < r.result["rounds_total"]
    assert r.result["root_visits"].sum() == r.result["playouts"] > 0
    assert r.result["best_move"] >= 0


# ------------------------------------------------- budgets and telemetry ----
def test_playout_budget_conserved_and_queue_stats():
    """Every finished request's dense root visits sum to exactly its
    scheduled playout budget, preemptions notwithstanding; QueueStats
    aggregates per-request telemetry (tokens == committed rounds)."""
    eng = engine(n_slots=2, grain=2, preempt_quanta=1)
    mix = [("hex", 64, 8), ("gomoku", 32, 8), ("hex", 32, 4),
           ("gomoku", 64, 16)]
    reqs = [req(i, g, n_playouts=n, n_tasks=t)
            for i, (g, n, t) in enumerate(mix)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    rounds_total = 0
    for r in reqs:
        cfg = eng.request_cfg(r)
        sch = scheduler.make_schedule(cfg.n_playouts, cfg.n_tasks,
                                      cfg.n_workers, cfg.scheduler)
        assert r.result["playouts"] == \
            scheduler.schedule_stats(sch)["lane_iterations"]
        assert r.result["root_visits"].sum() == r.result["playouts"]
        assert r.result["queue_wait_s"] >= 0
        assert r.result["latency_s"] >= r.result["queue_wait_s"]
        rounds_total += r.result["rounds"]
    st = eng.stats()
    assert isinstance(st, QueueStats)
    assert st.n_finished == 4
    assert st.tokens == rounds_total
    assert st.quanta >= 4
    assert 0 <= st.latency_p50 <= st.latency_p95


def test_submit_rejects_bad_requests():
    eng = engine()
    with pytest.raises(ValueError):
        eng.submit(req(0, game="chess"))             # unregistered game
    with pytest.raises(ValueError):
        eng.submit(req(1, board=np.zeros(7, np.int8)))  # wrong cell count
    with pytest.raises(ValueError):
        eng.submit(req(2, n_playouts=0))


# ----------------------------------------------------- scheduling property ----
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       slots=st.sampled_from([1, 2]),
       grain=st.sampled_from([1, 2, 4]),
       preempt=st.sampled_from([1, 2]))
def test_property_mixed_traffic_never_starves(seed, slots, grain, preempt):
    """Host-side scheduling property (search dispatch stubbed out): any mix
    of game classes, budgets, and grains drains completely — every request
    finishes with its exact round budget, each admission segment commits
    >=1 round (the PR 2 livelock guard), and per-class admission order
    follows submission order."""
    rng = np.random.default_rng(seed)
    with mock.patch("repro.serve.games.run_schedule_round",
                    lambda tree, board, cfg, key, rnd, cp: tree):
        # guard off: the stubbed dispatch never commits visits, so the
        # PR 9 result guard would (correctly) reject every retirement
        eng = engine(n_slots=slots, grain=grain, preempt_quanta=preempt,
                     tree_cap=64, guard=False)
        games = ("hex", "gomoku")
        reqs = [req(i, games[int(rng.integers(2))],
                    n_playouts=int(rng.integers(8, 129)),
                    n_tasks=int(2 ** rng.integers(0, 5)))
                for i in range(int(rng.integers(3, 8)))]
        for r in reqs:
            eng.submit(r)
        done = eng.run()
    assert len(done) == len(reqs)
    for r in reqs:
        cfg = eng.request_cfg(r)
        sch = scheduler.make_schedule(cfg.n_playouts, cfg.n_tasks,
                                      cfg.n_workers, cfg.scheduler)
        assert r.result["rounds"] == len(sch)
        assert r.result["playouts"] == \
            scheduler.schedule_stats(sch)["lane_iterations"]
    for t in eng.finished_tickets:
        # progress guard: preemption only after >=1 committed round, so
        # segments (preemptions + 1) never exceed committed rounds
        assert t.preemptions + 1 <= len(t.req.out)
    by_game = {r.rid: r.game for r in reqs}
    first_admissions = list(dict.fromkeys(eng.admission_order))
    for g in ("hex", "gomoku"):
        submitted = [r.rid for r in reqs if r.game == g]
        admitted = [rid for rid in first_admissions if by_game[rid] == g]
        assert admitted == submitted


def test_quantum_failing_the_same_way_every_attempt_ends_run():
    """A deterministic quantum failure (a compile error, a bad shape) is
    raised out of ``run()`` on its second identical attempt instead of
    being requeued forever."""
    calls = []

    def broken(tree, board, cfg, key, rnd, cp):
        calls.append(rnd)
        raise ValueError("Mosaic failed to compile TPU kernel")

    with mock.patch("repro.serve.games.run_schedule_round", broken):
        eng = engine(tree_cap=64, guard=False, retry_backoff=(1, 1))
        eng.submit(req("broken", seed=0))
        with pytest.raises(ValueError, match="Mosaic failed"):
            eng.run(max_ticks=100)
    assert len(calls) == 2
    assert eng.stats().n_retries == 1


def test_quantum_failing_once_is_retried_and_answered():
    """A failure that does not repeat is contained: the request is retried
    from a cold rebuild and still answered."""
    fails = ["transient device error"]

    def flaky(tree, board, cfg, key, rnd, cp):
        if fails:
            raise RuntimeError(fails.pop())
        return tree

    with mock.patch("repro.serve.games.run_schedule_round", flaky):
        eng = engine(tree_cap=64, guard=False, retry_backoff=(1, 1))
        r = req("flaky", seed=0)
        eng.submit(r)
        eng.run(max_ticks=100)
    assert r.result["status"] == "answered"
    assert r.result["retries"] == 1
