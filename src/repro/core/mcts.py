"""Sequential UCT search (paper Fig 1) — oracle + Table II baseline.

Single-worker, one-iteration-at-a-time. Selection reuses the deterministic
``select_one`` primitive; expansion and backup are written independently with
scalar updates so the batched dedup/scatter machinery in ``gscpm.py`` has a
simple implementation to be tested against (same RNG schedule ⇒ bit-identical
trees; see tests/test_gscpm.py). Game-agnostic like the rest of the search
stack (DESIGN.md §13): every game-specific step routes through the batched
``Game`` protocol (``repro.core.game``), and the scalar backup credits draws
(playout value 0) with 0.5 exactly as ``tree.backup_paths`` does.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.core import game as game_mod
from repro.core.gscpm import propose_move, select_one
from repro.core.tree import NO_NODE, Tree, best_child, init_tree, root_value


def uct_iteration(tree: Tree, root_board: jnp.ndarray, game,
                  cp: float, key: jax.Array) -> Tree:
    """One select→expand→playout→backup iteration (scalar updates)."""
    k_noise, k_move, k_po = jax.random.split(key, 3)
    path, depth, leaf, board, n_empty = select_one(
        tree, root_board, game, cp, k_noise, noise_scale=0.0)
    mv = propose_move(tree, leaf, board, game, k_move)
    expanding = mv >= 0

    # ---- scalar expansion (the lock-protected region in the paper) ----
    new = jnp.where(expanding & (tree.n_nodes < tree.cap), tree.n_nodes, tree.cap)
    did = new < tree.cap
    slot = tree.n_children[leaf]
    tgt_leaf = jnp.where(did, leaf, tree.cap)
    tree = tree._replace(
        parent=tree.parent.at[new].set(jnp.where(did, leaf, NO_NODE)),
        move=tree.move.at[new].set(jnp.where(did, mv, NO_NODE)),
        to_move=tree.to_move.at[new].set(jnp.where(did, 3 - tree.to_move[leaf], 0)),
        children=tree.children.at[tgt_leaf, jnp.where(did, slot, 0)].set(
            jnp.where(did, new, tree.children[tgt_leaf, jnp.where(did, slot, 0)])),
        child_move=tree.child_move.at[tgt_leaf, jnp.where(did, slot, 0)].set(
            jnp.where(did, mv, NO_NODE)),
        n_children=tree.n_children.at[tgt_leaf].add(did.astype(jnp.int32)),
        n_nodes=tree.n_nodes + did.astype(jnp.int32),
    )
    tree = tree._replace(
        parent=tree.parent.at[tree.cap].set(NO_NODE),
        move=tree.move.at[tree.cap].set(NO_NODE),
        n_children=tree.n_children.at[tree.cap].set(0),
    )
    path = path.at[depth + 1].set(jnp.where(did, new, tree.cap))

    # ---- playout (the game's batched evaluation stage at width 1: same
    # fill RNG, per-game winner dispatch through kernels.ops) ----
    mover = tree.to_move[leaf]
    b2 = jnp.where(expanding, game.place(board, jnp.maximum(mv, 0), mover),
                   board)
    nxt = jnp.where(expanding, 3 - mover, mover)
    w = game.playout_batch(b2[None], nxt[None], k_po[None])[0]

    # ---- scalar backup (the paper's atomic w_j / n_j walk) ----
    wv = w.astype(jnp.int32)

    def body(i, t):
        node = path[i]
        on = node != t.cap
        # 1 if the mover-into-node won the playout, 0.5 on a draw (value 0)
        credit = jnp.where(
            wv == 0, 0.5,
            ((3 - t.to_move[node]) == wv).astype(jnp.float32))
        tgt = jnp.where(on, node, t.cap)
        t = t._replace(visits=t.visits.at[tgt].add(jnp.where(on, 1.0, 0.0)),
                       wins=t.wins.at[tgt].add(jnp.where(on, credit, 0.0)))
        return t

    tree = jax.lax.fori_loop(0, path.shape[0], body, tree)
    return tree._replace(visits=tree.visits.at[tree.cap].set(0.0),
                         wins=tree.wins.at[tree.cap].set(0.0))


@functools.partial(jax.jit, static_argnames=("game", "cp", "n_iters"),
                   donate_argnums=(0,))
def _run(tree: Tree, root_board: jnp.ndarray, game, cp: float,
         task_key: jax.Array, n_iters: int) -> Tree:
    def body(i, t):
        return uct_iteration(t, root_board, game, cp,
                             jax.random.fold_in(task_key, i))
    return jax.lax.fori_loop(0, n_iters, body, tree)


def uct_search(board: jnp.ndarray, to_move: int, n_playouts: int, key: jax.Array,
               *, board_size: int = 11, cp: float = 1.0,
               tree_cap: int = 1 << 15, game: str = "hex") -> tuple[Tree, dict]:
    """Sequential UCTSearch(r, m) with the same RNG schedule as GSCPM's
    task 0 (``fold_in(fold_in(key, 0), i)``) for oracle comparisons."""
    g = game_mod.make_game(game, board_size)
    tree = init_tree(tree_cap, g.n_actions, to_move)
    task_key = jax.random.fold_in(key, 0)
    t0 = time.perf_counter()
    tree = _run(tree, board, g, cp, task_key, n_playouts)
    jax.block_until_ready(tree.visits)
    dt = time.perf_counter() - t0
    stats = {
        "time_s": dt,
        "playouts": n_playouts,
        "playouts_per_s": n_playouts / max(dt, 1e-9),
        "tree_nodes": int(tree.n_nodes),
        "root_value": float(root_value(tree)),
        "best_move": int(best_child(tree)),
    }
    return tree, stats
