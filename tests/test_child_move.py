"""The per-node child-move table against the move gather it replaced.

``Tree.child_move`` holds each node's child moves in the row layout of
``Tree.children`` (``child_move[p, s] == move[children[p, s]]`` on valid
slots, ``NO_NODE`` past them), so a proposal reads a leaf's tried moves as
one row. The oracle here is the formula the proposals used before: gather
``tree.move[children[leaf]]`` element by element and, for board games,
scatter it into a mask over cells. Every proposal must equal the oracle's
exactly, at every allocated node of trees grown by real searches: Hex 5x5
and 7x7, a Gomoku tree with terminal leaves, a re-rooted warm tree, a
two-member forest under ``vmap``, and a token tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_batched_descent import built_tree, rerooted_tree, token_tree

from repro.core.gscpm import GSCPMConfig, gscpm_search, propose_move
from repro.core.root_parallel import gscpm_search_batch
from repro.core.tree import (NO_NODE, check_invariants, forest_member,
                             init_tree)
from repro.serve.mcts_decode import MCTSDecodeConfig, propose_token

KEYS_PER_NODE = 3


def propose_move_oracle(tree, leaf, board, game, key):
    """``propose_move`` as it read the tree before the child-move table: a
    (C,) gather of ``move`` through the leaf's children, then a scatter of
    those moves into a mask over the cells."""
    n_cells, C, cap = game.n_cells, tree.max_children, tree.cap
    legal = game.legal_mask(board)
    slots = tree.children[leaf]
    valid = jnp.arange(C, dtype=jnp.int32) < tree.n_children[leaf]
    tried_moves = jnp.where(valid, tree.move[jnp.where(valid, slots, cap)],
                            n_cells)
    tried = jnp.zeros((n_cells + 1,), bool).at[tried_moves].set(
        True)[:n_cells]
    untried = legal & ~tried
    u = jax.random.uniform(key, (n_cells,))
    mv = jnp.argmax(jnp.where(untried, u, -1.0)).astype(jnp.int32)
    return jnp.where(untried.any(), mv, jnp.int32(NO_NODE))


def propose_token_oracle(tree, leaf, leaf_logits, cfg, depth, key):
    """``propose_token`` as it read the tree before the child-move table."""
    C, cap = tree.max_children, tree.cap
    _, top_tok = jax.lax.top_k(leaf_logits, cfg.branch)
    slots = tree.children[leaf]
    valid = jnp.arange(C, dtype=jnp.int32) < tree.n_children[leaf]
    tried = jnp.where(valid, tree.move[jnp.where(valid, slots, cap)], -1)
    is_tried = (top_tok[:, None] == tried[None, :]).any(axis=1)
    can = ~is_tried & (depth < cfg.max_depth)
    g = jax.random.gumbel(key, (cfg.branch,))
    pick = jnp.argmax(jnp.where(can, g, -jnp.inf))
    return jnp.where(can.any(), top_tok[pick], NO_NODE).astype(jnp.int32)


def node_boards(tree, root_board, game, rows: int) -> jnp.ndarray:
    """(rows, n_cells) position at each node id, by replaying its move onto
    its parent's position in allocation order (``parent[i] < i``); rows
    past ``n_nodes`` hold the root position."""
    place = jax.jit(game.place)
    parent, move = np.asarray(tree.parent), np.asarray(tree.move)
    to_move = np.asarray(tree.to_move)
    boards = [root_board] * rows
    for i in range(1, int(tree.n_nodes)):
        p = int(parent[i])
        boards[i] = place(boards[p], jnp.int32(move[i]), jnp.int8(to_move[p]))
    return jnp.stack(boards)


def proposals(fn, tree, boards, game, key):
    """(rows, KEYS_PER_NODE) proposals of ``fn`` at node ids 0..rows-1."""
    rows = boards.shape[0]
    keys = jax.random.split(key, rows * KEYS_PER_NODE).reshape(
        rows, KEYS_PER_NODE)
    per_node = jax.vmap(lambda l, b, k: fn(tree, l, b, game, k),
                        in_axes=(None, None, 0))
    return jax.jit(jax.vmap(per_node))(
        jnp.arange(rows, dtype=jnp.int32), boards, keys)


def gomoku_tree_with_terminal_leaves(size: int, key):
    """Black holds four in a column and is to move: the searched tree holds
    the winning child, a won position with no legal move."""
    cfg = GSCPMConfig(game="gomoku", board_size=size, n_playouts=256,
                      n_tasks=16, n_workers=4, tree_cap=2048)
    game = cfg.game_obj
    board = game.init_board().at[jnp.arange(4) * size].set(1)
    tree, _ = gscpm_search(board, 1, cfg, key)
    return tree, board, game


TREES = {
    "hex5": lambda k: built_tree(5, k),
    "hex7": lambda k: built_tree(7, k, n_playouts=384),
    "gomoku7_terminal": lambda k: gomoku_tree_with_terminal_leaves(7, k),
    "hex5_reroot_warm": lambda k: rerooted_tree(5, k),
}


@pytest.mark.parametrize("kind", sorted(TREES))
def test_propose_move_matches_move_gather_oracle(kind):
    tree, board, game = TREES[kind](jax.random.PRNGKey(11))
    check_invariants(tree)
    boards = node_boards(tree, board, game, int(tree.n_nodes))
    key = jax.random.key(5)
    want = np.asarray(proposals(propose_move_oracle, tree, boards, game, key))
    got = np.asarray(proposals(propose_move, tree, boards, game, key))
    np.testing.assert_array_equal(got, want)
    # the trees hold both fully expanded nodes (no proposal) and open ones
    assert (want == NO_NODE).any() and (want >= 0).any()
    if kind == "gomoku7_terminal":
        terminal = ~np.asarray(jax.vmap(game.legal_mask)(boards)).any(-1)
        assert terminal[1:].any()
        assert (want[terminal] == NO_NODE).all()


def test_propose_move_matches_oracle_in_a_vmapped_forest():
    size = 5
    cfg = GSCPMConfig(board_size=size, n_playouts=128, n_tasks=8,
                      n_workers=4, tree_cap=1024)
    game = cfg.game_obj
    board = game.init_board()
    forest, _ = gscpm_search_batch(board, 1, cfg, jax.random.PRNGKey(3),
                                   n_trees=2)
    members = [forest_member(forest, e) for e in range(2)]
    for m in members:
        check_invariants(m)
    rows = max(int(m.n_nodes) for m in members)
    boards = jnp.stack([node_boards(m, board, game, rows) for m in members])
    key = jax.random.key(8)

    def run(fn):
        return np.asarray(jax.vmap(
            lambda t, b: proposals(fn, t, b, game, key))(forest, boards))

    want = run(propose_move_oracle)
    np.testing.assert_array_equal(run(propose_move), want)
    assert (want == NO_NODE).any() and (want >= 0).any()


def assert_child_move_rows(tree):
    """``child_move[p, s] == move[children[p, s]]`` on every valid slot,
    ``NO_NODE`` on every other, the pad row's included."""
    cm, ch = np.asarray(tree.child_move), np.asarray(tree.children)
    nc, mv = np.asarray(tree.n_children), np.asarray(tree.move)
    valid = np.arange(ch.shape[1])[None, :] < nc[:, None]
    np.testing.assert_array_equal(cm[valid], mv[ch[valid]])
    assert (cm[~valid] == NO_NODE).all()
    assert (cm[tree.cap] == NO_NODE).all()


def test_propose_token_matches_move_gather_oracle():
    # a synthetic token tree: its backups credit values, not playouts, so
    # only the child-move rows are checked, not the visit bounds
    cfg = MCTSDecodeConfig(branch=4, max_depth=3, tree_cap=128)
    tree = token_tree(cfg, seed=0)
    assert_child_move_rows(tree)
    n, V = int(tree.n_nodes), 64
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(n, V)).astype(np.float32)
    # lift each even node's own child tokens into its top-k, so the tried
    # test decides its proposal
    cm = np.asarray(tree.child_move)[:n]
    for i in range(0, n, 2):
        kids = cm[i][cm[i] != NO_NODE]
        logits[i, kids] += 10.0
    depths = rng.integers(0, cfg.max_depth + 1, size=(n,)).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(2), n)

    def run(fn):
        return np.asarray(jax.vmap(
            lambda l, ll, d, k: fn(tree, l, ll, cfg, d, k))(
                jnp.arange(n, dtype=jnp.int32), jnp.asarray(logits),
                jnp.asarray(depths), keys))

    want = run(propose_token_oracle)
    np.testing.assert_array_equal(run(propose_token), want)
    assert (want == NO_NODE).any() and (want >= 0).any()


def test_child_move_rows_follow_children():
    """``init_tree`` pads every row; expansion writes a child's move into
    the slot its id takes, and nothing into the pad row."""
    tree = init_tree(8, 5, 1)
    assert tree.child_move.shape == tree.children.shape
    assert (np.asarray(tree.child_move) == NO_NODE).all()
    tree, _, _ = built_tree(5, jax.random.PRNGKey(2))
    assert int(tree.n_nodes) > 1
    assert_child_move_rows(tree)
