"""Level-synchronous batched descent vs the per-lane scalar oracle.

The tentpole contract (DESIGN.md §11): ``select_batch`` /
``select_token_batch`` — all W lanes stepping down the tree in lockstep,
one ``kernels.ops.uct_select`` (W, C) tile per level — must be bit-identical
to ``jax.vmap(select_one)`` / ``jax.vmap(select_token_path)`` under the same
RNG schedule, both per-descent and across whole searches; and sweeping the
traced knobs (Cp, grain, scheduler) must never grow the jit caches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hex as hx
from repro.core.gscpm import (GSCPMConfig, expand_batch, gscpm_search,
                              run_chunk, select_batch, select_one,
                              sync_iteration)
from repro.core.root_parallel import gscpm_search_batch
from repro.core.tree import (Tree, best_child, child_stat_tile, init_tree,
                             reroot_tree)
from repro.kernels import ops
from repro.kernels import uct_select as _us
from repro.serve.mcts_decode import (MCTSDecodeConfig, backup_values,
                                     select_token_batch, select_token_path)


def built_tree(size: int, key, n_playouts: int = 192):
    """A mid-search Hex tree with real stats to descend."""
    board = hx.empty_board(hx.HexSpec(size))
    cfg = GSCPMConfig(board_size=size, n_playouts=n_playouts, n_tasks=8,
                      n_workers=4, tree_cap=4096)
    tree, _ = gscpm_search(board, 1, cfg, key)
    return tree, board, hx.HexGame(size)


# ------------------------------------------------------- descent oracle ----
def open_root_tree(size: int, key):
    """A tree whose root is not fully expanded: no lane steps from it."""
    tree, board, game = built_tree(size, key, n_playouts=8)
    assert int(tree.n_children[0]) < size * size
    return tree, board, game


def terminal_gomoku_root(size: int, key):
    """A Gomoku tree rooted at a won position: no legal move, no child."""
    cfg = GSCPMConfig(game="gomoku", board_size=size, n_playouts=32,
                      n_tasks=4, n_workers=4, tree_cap=256)
    game = cfg.game_obj
    board = game.init_board().at[jnp.arange(5) * size].set(1)   # a column
    tree, _ = gscpm_search(board, 2, cfg, key)
    assert int(tree.n_children[0]) == 0 and float(tree.visits[0]) > 0
    return tree, board, game


def rerooted_tree(size: int, key):
    """A warm tree: a searched tree re-rooted at its most visited move."""
    tree, board, game = built_tree(size, key, n_playouts=512)
    mv = int(best_child(tree))
    warm = reroot_tree(tree, mv)
    assert int(warm.n_nodes) > 1 and int(warm.n_children[0]) > 0
    return warm, game.place(board, jnp.int32(mv), jnp.int8(1)), game


TREES = {"built": built_tree, "root_open": open_root_tree,
         "gomoku_terminal": terminal_gomoku_root, "reroot_warm": rerooted_tree}
DESCENT_CASES = [
    pytest.param(noise_scale, W, size, "built", id=f"{noise_scale}-{W}-{size}")
    for noise_scale in (0.0, 1e-3) for W in (1, 4, 16) for size in (5, 7)
] + [pytest.param(1e-3, 16, 5, kind, id=f"0.001-16-5-{kind}")
     for kind in ("root_open", "gomoku_terminal", "reroot_warm")]


@pytest.mark.parametrize("noise_scale,W,size,kind", DESCENT_CASES)
def test_select_batch_matches_vmapped_select_one(size, W, noise_scale, kind):
    tree, board, spec = TREES[kind](size, jax.random.PRNGKey(size))
    keys = jax.random.split(jax.random.PRNGKey(100 + W), W)
    cp = jnp.float32(1.0)
    want = jax.vmap(
        lambda k: select_one(tree, board, spec, cp, k, noise_scale))(keys)
    got = select_batch(tree, board, spec, cp, keys, noise_scale)
    for name, w, g in zip(("path", "depth", "leaf", "board", "n_empty"),
                          want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=f"{name} diverged")
    if kind in ("root_open", "gomoku_terminal"):
        assert not np.asarray(got[1]).any()        # every lane at the root


def test_select_batch_reads_no_vloss_when_told():
    """With no virtual-loss operand ``select_batch`` reads none: on a grown
    tree its paths are bit-identical to those it takes with an explicit
    zeros operand. An operand given is read: a NaN one changes them."""
    tree, board, spec = built_tree(3, jax.random.PRNGKey(4))
    keys = jax.random.split(jax.random.PRNGKey(9), 16)
    cp = jnp.float32(1.0)
    want = select_batch(tree, board, spec, cp, keys, 1e-3,
                        vloss=jnp.zeros_like(tree.visits))
    got = select_batch(tree, board, spec, cp, keys, 1e-3)
    assert int(np.asarray(want[1]).max()) >= 2    # tiles below the root
    for name, w, g in zip(("path", "depth", "leaf", "board", "n_empty"),
                          want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=f"{name} diverged")
    read = select_batch(tree, board, spec, cp, keys, 1e-3,
                        vloss=jnp.full_like(tree.visits, jnp.nan))
    assert not np.array_equal(np.asarray(read[0]), np.asarray(want[0]))


def sole_unvisited_root(cfg: GSCPMConfig):
    """A 3x3 Hex tree whose root is fully expanded: eight children won
    every one of their 100 playouts, the ninth (the last, node 9) was never
    visited."""
    game = cfg.game_obj
    n = game.n_cells
    tree = init_tree(cfg.tree_cap, game.n_actions, 1)
    tree, _ = expand_batch(tree, jnp.zeros((n,), jnp.int32),
                           jnp.arange(n, dtype=jnp.int32),
                           jnp.ones((n,), bool))
    played = (jnp.arange(tree.cap + 1) >= 1) & (jnp.arange(tree.cap + 1) < n)
    visits = jnp.where(played, 100.0, 0.0).at[0].set(100.0 * (n - 1))
    return tree._replace(visits=visits, wins=jnp.where(played, 100.0, 0.0))


def test_run_chunk_one_round_reads_no_vloss():
    """Virtual loss lives inside one sync iteration. At ``vl_rounds == 3``
    (one lane per round) the first round takes the root's sole unvisited
    child; the rounds after it see that round's virtual loss there and
    avoid it, so one playout passes through it, not three as with one
    round. The next iteration starts with none: two iterations of
    ``run_chunk`` equal two fresh ``sync_iteration`` calls in turn."""
    board = hx.empty_board(hx.HexSpec(3))
    cp = jnp.float32(0.1)
    active = jnp.ones((3,), bool)
    task_keys = jax.random.split(jax.random.PRNGKey(21), 3)
    keys_at = lambda i: jax.vmap(                         # noqa: E731
        lambda k: jax.random.fold_in(k, i))(task_keys)
    through_sole = {}
    for rounds in (1, 3):
        cfg = GSCPMConfig(board_size=3, n_workers=3, vl_rounds=rounds,
                          tree_cap=64)
        step = jax.jit(lambda t, k, cfg=cfg: sync_iteration(
            t, board, cfg, cp, k, active)[0])
        tree = sole_unvisited_root(cfg)
        one = step(tree, keys_at(0))
        through_sole[rounds] = float(one.visits[9])
        two = step(one, keys_at(1))
        chunk = run_chunk(jax.tree.map(jnp.copy, tree), board, cfg,
                          task_keys, active, jnp.int32(2), cp)
        for f in Tree._fields:
            np.testing.assert_array_equal(np.asarray(getattr(two, f)),
                                          np.asarray(getattr(chunk, f)),
                                          err_msg=f"{rounds} rounds: {f}")
    assert through_sole == {1: 3.0, 3: 1.0}


@pytest.mark.parametrize("vl_rounds", [1, 3])
@pytest.mark.parametrize("W,noise", [(4, 1e-3), (8, 0.0), (8, 1e-3)])
def test_full_search_batched_equals_scalar(vl_rounds, W, noise,
                                           per_lane_references):
    """Whole searches — selection, expansion, playout, backup — produce
    bit-identical trees whichever descent runs (same RNG schedule): the
    batched one, or the per-lane ``select_one`` swapped in."""
    board = hx.empty_board(hx.HexSpec(5))
    cfg = GSCPMConfig(board_size=5, n_playouts=128, n_tasks=8, n_workers=W,
                      vl_rounds=vl_rounds, select_noise=noise, tree_cap=2048)
    key = jax.random.PRNGKey(17)
    t_b, s_b = gscpm_search(board, 1, cfg, key)
    with per_lane_references(cfg.game_obj, descent=True):
        t_s, s_s = gscpm_search(board, 1, cfg, key)
    assert int(t_b.n_nodes) == int(t_s.n_nodes)
    nn = int(t_b.n_nodes)
    for f in ("parent", "move", "to_move", "n_children"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_b, f)[:nn]), np.asarray(getattr(t_s, f)[:nn]),
            err_msg=f)
    np.testing.assert_allclose(np.asarray(t_b.visits[:nn]),
                               np.asarray(t_s.visits[:nn]))
    np.testing.assert_allclose(np.asarray(t_b.wins[:nn]),
                               np.asarray(t_s.wins[:nn]))
    assert s_b["best_move"] == s_s["best_move"]


def test_forest_vmap_composes_with_batched_descent():
    """Root-parallel vmap over E members runs the batched descent unchanged:
    each member's forest tree equals its own single-tree search."""
    board = hx.empty_board(hx.HexSpec(5))
    cfg = GSCPMConfig(board_size=5, n_playouts=64, n_tasks=4, n_workers=4,
                      tree_cap=1024)
    key = jax.random.PRNGKey(3)
    forest, _ = gscpm_search_batch(board, 1, cfg, key, n_trees=2)
    for e in range(2):
        single, _ = gscpm_search(
            board, 1, cfg, jax.random.fold_in(key, e))
        np.testing.assert_allclose(np.asarray(forest.visits[e]),
                                   np.asarray(single.visits))


# ----------------------------------------------------- token-tree oracle ----
def token_tree(cfg: MCTSDecodeConfig, seed: int):
    """Synthetic token tree: dedup-expanded tokens + scored backups.

    Proposals target distinct non-full leaves only (as ``propose_token``
    guarantees in the real path), so ``branch`` is never exceeded.
    """
    tree = init_tree(cfg.tree_cap, cfg.branch, 1)
    rng = np.random.default_rng(seed)
    for i in range(6):
        nn = int(tree.n_nodes)
        nc = np.asarray(tree.n_children[:nn])
        open_leaves = np.flatnonzero(nc < cfg.branch)
        leaves = rng.choice(open_leaves, size=min(4, len(open_leaves)),
                            replace=False).astype(np.int32)
        W = len(leaves)
        toks = rng.integers(1, 50, size=(W,)).astype(np.int32)
        tree, new_ids = expand_batch(tree, jnp.asarray(leaves),
                                     jnp.asarray(toks), jnp.ones((W,), bool))
        paths = jnp.where(new_ids[:, None] < tree.cap,
                          jnp.stack([jnp.zeros((W,), jnp.int32), new_ids], 1),
                          tree.cap)
        vals = jnp.asarray(rng.uniform(0.1, 1.0, size=(W,)), jnp.float32)
        tree = backup_values(tree, paths, vals, jnp.ones((W,)))
    return tree


@pytest.mark.parametrize("W", [1, 4, 8])
def test_select_token_batch_matches_oracle(W):
    cfg = MCTSDecodeConfig(branch=4, max_depth=3, tree_cap=128)
    tree = token_tree(cfg, seed=0)
    keys = jax.random.split(jax.random.PRNGKey(W), W)
    cp = jnp.float32(1.0)
    want = jax.vmap(lambda k: select_token_path(tree, cfg, k, cp))(keys)
    got = select_token_batch(tree, cfg, cp, keys)
    for name, w, g in zip(("path", "depth", "leaf"), want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=f"{name} diverged")


# ------------------------------------------------------------- gather op ----
def test_child_stat_tile_matches_scalar_gather():
    tree, _, _ = built_tree(5, jax.random.PRNGKey(1))
    nodes = jnp.asarray([0, 1, int(tree.n_nodes) - 1, 0], jnp.int32)
    safe, valid, wins, visits, vloss, ptot = child_stat_tile(tree, nodes)
    C = tree.max_children
    for i, n in enumerate(np.asarray(nodes)):
        nk = int(tree.n_children[n])
        v = np.arange(C) < nk
        np.testing.assert_array_equal(np.asarray(valid[i]), v)
        s = np.where(v, np.asarray(tree.children[n]), tree.cap)
        np.testing.assert_array_equal(np.asarray(safe[i]), s)
        np.testing.assert_allclose(np.asarray(wins[i]),
                                   np.asarray(tree.wins)[s])
        np.testing.assert_allclose(np.asarray(ptot[i]),
                                   float(tree.visits[n]))
        assert not np.asarray(vloss[i]).any()


# --------------------------------------------------------- compile counts ----
def test_cp_grain_scheduler_sweeps_do_not_retrace():
    """The fig7/ablation sweep contract: Cp, grain and scheduler are traced
    or host-only knobs, so the whole grid shares ONE compiled chunk."""
    board = hx.empty_board(hx.HexSpec(5))
    key = jax.random.PRNGKey(0)
    gscpm_search(board, 1, GSCPMConfig(board_size=5, n_playouts=32,
                                       n_tasks=4, n_workers=4,
                                       tree_cap=512), key)
    before = run_chunk._cache_size()
    for cp in (0.3, 1.0, 2.4):
        for n_tasks in (2, 4, 16):
            for sched in ("fifo", "rebalance"):
                cfg = GSCPMConfig(board_size=5, n_playouts=32,
                                  n_tasks=n_tasks, n_workers=4,
                                  tree_cap=512, cp=cp, scheduler=sched)
                gscpm_search(board, 1, cfg, key)
    assert run_chunk._cache_size() == before


def test_kernel_jit_cp_is_traced():
    """The Pallas kernel itself never recompiles across Cp values."""
    W, C = 8, 16
    z = jnp.zeros((W, C))
    valid = jnp.ones((W, C), bool)
    ptot = jnp.ones((W,))
    _us.uct_select(z, z, z, ptot, valid, jnp.float32(1.0), interpret=True)
    before = _us.uct_select._cache_size()
    for cp in (0.25, 0.7, 3.0):
        _us.uct_select(z, z, z, ptot, valid, jnp.float32(cp), interpret=True)
    assert _us.uct_select._cache_size() == before


# ------------------------------------------------------- done-lane masking ----
def test_lane_mask_holds_done_lanes():
    """A masked lane's row is fully invalid -> deterministic slot 0; live
    lanes' picks are unaffected by other lanes' masks."""
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    W, C = 6, 8
    visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 9)
    wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
    valid = jax.random.uniform(ks[2], (W, C)) > 0.3
    ptot = jnp.maximum(visits.sum(-1), 1.0)
    mask = jnp.asarray([True, False, True, False, True, True])
    cp = jnp.float32(1.0)
    free = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid, cp)
    held = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid, cp,
                          lane_mask=mask)
    np.testing.assert_array_equal(np.asarray(held)[np.asarray(mask)],
                                  np.asarray(free)[np.asarray(mask)])
    assert (np.asarray(held)[~np.asarray(mask)] == 0).all()
    # pallas kernel agrees on the masked tile
    interp = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid, cp,
                            lane_mask=mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(held), np.asarray(interp))
