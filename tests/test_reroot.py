"""Cross-move tree reuse suite (DESIGN.md §16).

Pins the re-root retention contract — every retained node's statistics,
topology, and depth survive ``reroot_tree``/``reroot_forest`` bit-for-bit
(``check_reroot_retention``), an unexpanded move compacts to a tree
bit-identical to a fresh ``init_tree`` with the side to move flipped, and
shrinking capacities fail loudly at trace time. Warm starts are pinned as
a DATA change, never a program change: warm searches are deterministic,
``warm_tree_check`` rejects mismatched trees eagerly, a session-served
warm search equals the direct warm reference bit-for-bit, gomoku's 0.5
draw credits ride through a re-root unchanged, and a whole session game
(re-roots, warm budgets and all) adds ZERO entries to the ``run_chunk``
jit cache beyond the per-class warm-up.

NOTE: engines/configs here use tree_cap=1024 so their class keys never
collide with the exact-compile-count suites (test_serve_games pins
tree_cap=512 at sizes 5/6, test_obsv size 4).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_game_protocol import drawn_gomoku_position

from repro.core.gscpm import (GSCPMConfig, gscpm_search, run_chunk,
                              warm_tree_check)
from repro.core.root_parallel import gscpm_search_batch
from repro.core.tree import (check_invariants, check_reroot_retention,
                             forest_member, forest_size, init_tree,
                             node_depths, reroot_forest, reroot_tree,
                             root_summary)
from repro.serve.games import (GameRequest, GameSession, TPFIFOGameEngine,
                               warm_budget)

SIZE = 5
CAP = 1024   # reserved for this suite (see module docstring)


def cfg(**kw):
    kw.setdefault("game", "hex")
    kw.setdefault("board_size", SIZE)
    kw.setdefault("n_playouts", 64)
    kw.setdefault("n_tasks", 8)
    kw.setdefault("n_workers", 4)
    kw.setdefault("tree_cap", CAP)
    return GSCPMConfig(**kw)


def engine(**kw):
    kw.setdefault("n_slots", 1)
    kw.setdefault("grain", 2)
    kw.setdefault("n_workers", 4)
    kw.setdefault("tree_cap", CAP)
    return TPFIFOGameEngine(**kw)


def searched_tree(game="hex", seed=0, **kw):
    c = cfg(game=game, **kw)
    tree, stats = gscpm_search(c.game_obj.init_board(), 1, c,
                               jax.random.key(seed))
    return tree, stats, c


def expanded_root_move(tree) -> int:
    kids = np.asarray(tree.children[0][: int(tree.n_children[0])])
    return int(np.asarray(tree.move)[kids[0]])


# ------------------------------------------------------- retention contract ----
@pytest.mark.parametrize("game", ["hex", "gomoku"])
def test_reroot_retention_bit_identical(game):
    """The played child's whole subtree survives the compaction node-for-
    node: stats bit-identical, topology remapped, depths shifted by one,
    and the result passes every tree invariant."""
    tree, stats, c = searched_tree(game)
    mv = stats["best_move"]
    dst = reroot_tree(tree, mv)
    n_sub = check_reroot_retention(tree, dst, mv)
    assert n_sub == int(dst.n_nodes) > 0
    check_invariants(dst)
    dep = node_depths(dst)
    assert dep[0] == 0
    assert (dep[1: int(dst.n_nodes)] > 0).all()
    # the new root IS the played child: same stats, flipped ownership
    kids = np.asarray(tree.children[0][: int(tree.n_children[0])])
    child = int(kids[list(np.asarray(tree.move)[kids]).index(mv)])
    assert float(dst.visits[0]) == float(tree.visits[child]) > 0
    assert float(dst.wins[0]) == float(tree.wins[child])
    assert int(dst.to_move[0]) == 3 - int(tree.to_move[0])


def test_reroot_forest_retention_per_member():
    """Every ensemble member keeps ITS OWN subtree under one vmapped
    re-root; members that never expanded the move come back as 1-node
    trees (checked per member by the same host-side contract walk)."""
    c = cfg(n_playouts=32, n_tasks=4)
    forest, _ = gscpm_search_batch(c.game_obj.init_board(), 1, c,
                                   jax.random.key(3), n_trees=3)
    mv = expanded_root_move(forest_member(forest, 0))
    dst = reroot_forest(forest, mv)
    assert forest_size(dst) == 3
    retained = 0
    for e in range(3):
        src_e, dst_e = forest_member(forest, e), forest_member(dst, e)
        retained += check_reroot_retention(src_e, dst_e, mv)
        check_invariants(dst_e)
    assert retained > 0


def test_reroot_unexpanded_move_is_fresh_init_tree():
    """Re-rooting onto a move the root never expanded must yield a tree
    BIT-IDENTICAL to ``init_tree`` with the side to move flipped — the
    'cold start in warm clothing' that makes ``play(any legal move)``
    unconditionally safe."""
    # a tiny budget cannot expand all 25 root moves
    tree, _, c = searched_tree(n_playouts=8, n_tasks=2, n_workers=2)
    kids = np.asarray(tree.children[0][: int(tree.n_children[0])])
    seen = set(np.asarray(tree.move)[kids].tolist())
    missing = next(m for m in range(c.game_obj.n_actions) if m not in seen)
    dst = reroot_tree(tree, missing)
    assert check_reroot_retention(tree, dst, missing) == 0
    fresh = init_tree(CAP, c.game_obj.n_actions, 2)   # to_move flipped
    for f, a, b in zip(tree._fields, dst, fresh):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)


def test_reroot_capacity_shrink_raises_at_trace_time():
    """new_cap < cap cannot be proven to fit from traced shapes alone —
    it must refuse eagerly, never silently truncate retained statistics."""
    tree, stats, c = searched_tree(n_playouts=16, n_tasks=2)
    with pytest.raises(ValueError, match="capacity overflow"):
        reroot_tree(tree, stats["best_move"], new_cap=CAP // 2)
    forest, _ = gscpm_search_batch(c.game_obj.init_board(), 1, c,
                                   jax.random.key(0), n_trees=2)
    with pytest.raises(ValueError, match="capacity overflow"):
        reroot_forest(forest, 0, new_cap=CAP - 1)
    # growing is fine and keeps the whole contract
    mv = stats["best_move"]
    big = reroot_tree(tree, mv, new_cap=2 * CAP)
    assert big.cap == 2 * CAP
    check_reroot_retention(tree, big, mv)
    check_invariants(big)


# ------------------------------------------------------------- warm starts ----
def test_warm_search_deterministic_bit_identical():
    """Search -> re-root -> warm search is a pure function: running the
    pipeline twice from the same seeds yields bit-identical trees and
    stats (the foundation of replayable self-play games)."""
    outs = []
    for _ in range(2):
        tree, stats, c = searched_tree(seed=7)
        mv = stats["best_move"]
        warm = reroot_tree(tree, mv)
        board = c.game_obj.place(c.game_obj.init_board(), jnp.int32(mv),
                                 jnp.int8(1))
        t2, s2 = gscpm_search(board, 2, c, jax.random.key(8), tree=warm)
        outs.append((jax.tree.map(np.asarray, t2), s2))
    (ta, sa), (tb, sb) = outs
    for f, a, b in zip(ta._fields, ta, tb):
        np.testing.assert_array_equal(a, b, err_msg=f)
    assert sa["reused_nodes"] == sb["reused_nodes"] > 0
    assert sa["reused_visits"] == sb["reused_visits"] > 0
    assert sa["best_move"] == sb["best_move"]
    check_invariants(ta)


def test_warm_tree_check_rejects_mismatched_trees():
    tree, _, c = searched_tree(n_playouts=16, n_tasks=2)
    warm_tree_check(tree, 1, c)                      # the matching case
    with pytest.raises(ValueError, match="cap"):
        warm_tree_check(init_tree(CAP // 2, 25, 1), 1, c)
    with pytest.raises(ValueError, match="different game"):
        warm_tree_check(tree, 1, cfg(game="gomoku", board_size=7))
    with pytest.raises(ValueError, match="to_move"):
        warm_tree_check(tree, 2, c)


def test_warm_budget_preserves_grain():
    """n_playouts is TOTAL evidence: the fresh remainder shrinks with the
    retained visits while the grain m (playouts per task) is preserved —
    same quantum program, fewer rounds."""
    po, tasks = warm_budget(512, 16, 8, 100.0)
    assert (po, tasks) == (412, 12)
    assert tasks == max(1, po // (512 // 16))         # m=32 sets the tasks
    # a fully warm position still refreshes one worker batch
    assert warm_budget(512, 16, 8, 512.0) == (8, 1)
    assert warm_budget(512, 16, 8, 10_000.0) == (8, 1)
    # a cold tree changes nothing
    assert warm_budget(512, 16, 8, 0.0) == (512, 16)


def test_gomoku_draw_credits_survive_reroot():
    """From the forced-draw position every node holds wins == visits/2;
    the re-rooted tree must retain the half-credits exactly and a warm
    continuation must keep root_value at exactly 0.5."""
    b = drawn_gomoku_position()
    c = cfg(game="gomoku", n_playouts=64, n_tasks=8)
    tree, stats = gscpm_search(b, 1, c, jax.random.key(5))
    assert stats["root_value"] == 0.5
    mv = stats["best_move"]
    dst = reroot_tree(tree, mv)
    check_reroot_retention(tree, dst, mv)
    nn = int(dst.n_nodes)
    np.testing.assert_allclose(np.asarray(dst.wins[:nn]),
                               np.asarray(dst.visits[:nn]) / 2.0)
    b2 = c.game_obj.place(b, jnp.int32(mv), jnp.int8(1))
    t2, s2 = gscpm_search(b2, 2, c, jax.random.key(6), tree=dst)
    check_invariants(t2)
    assert s2["root_value"] == 0.5
    nn = int(t2.n_nodes)
    np.testing.assert_allclose(np.asarray(t2.wins[:nn]),
                               np.asarray(t2.visits[:nn]) / 2.0)


def test_root_summary_reports_reused_visits():
    tree, _, c = searched_tree(n_playouts=16, n_tasks=2)
    cold = root_summary(tree, c.game_obj.n_actions)
    assert "reused_visits" not in cold    # cold snapshots stay comparable
    warm = root_summary(tree, c.game_obj.n_actions, reused_visits=5)
    assert warm["reused_visits"] == 5


# ---------------------------------------------------------------- sessions ----
def serve(eng, req):
    eng.submit(req)
    eng.run()
    return req.result


def test_session_served_warm_matches_direct_reference():
    """The full serving loop — session request, tree checkout, warm-budget
    replacement, quantum-served search, re-root — must equal the direct
    two-move reference (cold search, ``reroot_tree``, ``warm_budget``,
    warm ``gscpm_search``) bit-for-bit."""
    eng = engine()
    sess = GameSession(eng, "hex", SIZE, base_seed=11)
    r0 = serve(eng, sess.make_request(n_playouts=64, n_tasks=8))
    mv = r0["best_move"]
    sess.play(mv)
    r1 = serve(eng, sess.make_request(n_playouts=64, n_tasks=8))

    # the stateless twin pins the class cfg; the reference replays the
    # same two seeds through the library entry points
    c = eng.request_cfg(GameRequest(rid="ref", game="hex", board_size=SIZE,
                                    n_playouts=64, n_tasks=8, seed=11))
    t0, _ = gscpm_search(c.game_obj.init_board(), 1, c, jax.random.key(11))
    warm = reroot_tree(t0, mv)
    reused = float(warm.visits[0])
    # read before the warm search: gscpm_search donates the tree's buffers
    warm_nodes = int(warm.n_nodes)
    eff_po, eff_tasks = warm_budget(64, 8, c.n_workers, reused)
    c1 = dataclasses.replace(c, n_playouts=eff_po, n_tasks=eff_tasks)
    board1 = c.game_obj.place(c.game_obj.init_board(), jnp.int32(mv),
                              jnp.int8(1))
    t1, s1 = gscpm_search(board1, 2, c1, jax.random.key(12), tree=warm)
    ref = root_summary(t1, c.game_obj.n_actions)

    np.testing.assert_array_equal(r1["root_visits"], ref["root_visits"])
    np.testing.assert_array_equal(r1["root_wins"], ref["root_wins"])
    assert r1["best_move"] == ref["best_move"]
    assert r1["tree_nodes"] == ref["tree_nodes"]
    assert r1["reused_visits"] == int(reused) > 0
    assert r1["reused_nodes"] == warm_nodes - 1 > 0
    # equal-evidence accounting: the served search committed exactly the
    # reference's fresh-playout schedule (make_schedule may round eff_po)
    assert r1["playouts"] == s1["playouts"] < 64


def test_session_custody_and_legality_guards():
    """One request in flight per session (the tree has ONE owner), and
    ``play`` validates moves against the live board."""
    eng = engine()
    sess = GameSession(eng, "hex", SIZE)
    req = sess.make_request(n_playouts=16, n_tasks=2)
    with pytest.raises(RuntimeError, match="already in flight"):
        sess.make_request()
    with pytest.raises(RuntimeError, match="in flight"):
        sess.play(0)
    serve(eng, req)
    mv = req.result["best_move"]
    sess.play(mv)
    with pytest.raises(ValueError, match="illegal move"):
        sess.play(mv)                              # cell is now occupied
    assert sess.retained_visits > 0
    assert 0.0 < sess.retained_fraction <= 1.0


def test_cold_session_ablation_never_reuses():
    """reuse_tree=False keeps the session bookkeeping but drops the tree at
    every play — the benchmark's cold arm: same positions, zero reuse."""
    eng = engine()
    warm_s = GameSession(eng, "hex", SIZE, base_seed=3)
    cold_s = GameSession(eng, "hex", SIZE, base_seed=3, reuse_tree=False)
    for sess, want_reuse in ((warm_s, True), (cold_s, False)):
        r0 = serve(eng, sess.make_request(n_playouts=32, n_tasks=4))
        sess.play(r0["best_move"])
        assert (sess.tree is not None) == want_reuse
        r1 = serve(eng, sess.make_request(n_playouts=32, n_tasks=4))
        assert (r1["reused_visits"] > 0) == want_reuse
        if not want_reuse:   # a shallow warm tree may retain 0 descendants
            assert r1["reused_nodes"] == 0
    # both arms decided from the same total evidence
    assert cold_s.last_result["playouts"] == 32
    assert warm_s.last_result["playouts"] < 32


def test_whole_game_adds_zero_recompiles():
    """A whole session game — warm budgets, re-roots, every position —
    must add NOTHING to the run_chunk jit cache beyond the per-class
    warm-up: reuse is a data change, never a program change."""
    eng = engine()
    serve(eng, GameRequest(rid="warm", game="hex", board_size=SIZE,
                           n_playouts=8, n_tasks=2, seed=0))
    before = run_chunk._cache_size()
    sess = GameSession(eng, "hex", SIZE, base_seed=1)
    reused = []
    for _ in range(6):
        res = serve(eng, sess.make_request(n_playouts=48, n_tasks=6))
        reused.append(res["reused_visits"])
        if res["best_move"] < 0:
            break
        sess.play(res["best_move"])
        if sess.over():
            break
    assert run_chunk._cache_size() == before
    assert len(reused) >= 2 and max(reused) > 0   # reuse actually happened
