"""GSCPM — Grain-Size Controlled Parallel MCTS (paper Fig 4), TPU-native.

The paper splits ``nPlayouts`` UCT iterations into ``nTasks`` tasks of grain
``m = nPlayouts / nTasks`` and schedules them on a thread pool against one
shared tree. Here (DESIGN.md §2):

- a *lane* (vmapped worker) plays the role of a hardware thread;
- a *task* is an ``m``-iteration chunk executed as a ``lax.fori_loop`` of
  batch-synchronous iterations;
- a *sync iteration* selects W leaves (in ``vl_rounds`` virtual-loss rounds)
  via a level-synchronous batched descent — all W lanes step down the tree
  in lockstep, one ``kernels.ops.uct_select`` (W, C) tile per level, the
  TPU twin of the paper's 512-bit VPU-vectorized UCT loop (DESIGN.md §11) —
  then dedup-expands the proposed (leaf, move) pairs with prefix-sum slot
  allocation (the paper's atomic child index), evaluates W playouts as ONE
  fused (W, cells) stage through the game's batched playout primitive
  (``game.playout_batch`` — for Hex one batched place, one sort-free
  parity fill, one connectivity solve via ``kernels.ops.hex_winner``,
  DESIGN.md §12) — and scatter-adds the results along the W paths (the
  paper's atomic w_j/n_j);
- per-task RNG streams come from ``fold_in`` (the paper's per-task MKL
  streams).

This module is game-agnostic (DESIGN.md §13): every game-specific
computation routes through the batched ``Game`` protocol
(``repro.core.game`` — ``GSCPMConfig.game`` names a registry entry), so the
same compiled machinery searches Hex, Gomoku, or any future registration.

Grain size trades scheduling overhead against parallel width exactly as in
the paper's Table I; the scheduling disciplines live in
``repro.core.scheduler``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game as game_mod
from repro.core import scheduler as sched
from repro.core.game import EMPTY
from repro.core import uct as uct_mod
from repro.core.tree import (
    NO_NODE,
    Tree,
    add_vloss,
    backup_paths,
    best_child,
    child_stat_tile,
    init_tree,
    path_levels,
    root_value,
)
from repro.kernels import ops
from repro.obsv.trace import span


@dataclasses.dataclass(frozen=True)
class GSCPMConfig:
    """Knobs of the paper's experiment grid + the TPU-specific ones.

    Fields marked compare=False are excluded from the config's hash/eq:
    ``cp`` reaches the jitted chunks as a traced scalar operand, and
    ``n_playouts``/``n_tasks``/``scheduler`` only shape the host-side task
    schedule (the grain arrives as the traced ``m``), so configs differing
    only in those knobs share one compiled program ("knobs traced ⇒ zero
    recompiles" — the fig7/ablation sweeps pay one compile total). Traced
    code must never read a compare=False field — it would silently bake the
    first value seen into the cached program.
    """

    game: str = "hex"               # Game-registry name (core/game.py)
    board_size: int = 11
    # paper: 1,048,576 playouts (scaled for CPU harness)
    n_playouts: int = dataclasses.field(default=4096, compare=False)
    # the grain dial: m = n_playouts / n_tasks
    n_tasks: int = dataclasses.field(default=64, compare=False)
    n_workers: int = 16             # parallel lanes (hardware-thread analogue)
    vl_rounds: int = 1              # virtual-loss rounds per sync iteration
    virtual_loss: float = 1.0
    cp: float = dataclasses.field(default=1.0, compare=False)  # paper: Cp = 1.0
    select_noise: float = 1e-3      # per-lane UCT tie-break jitter
    tree_cap: int = 1 << 15
    # fifo | rebalance | one_per_core | sequential
    scheduler: str = dataclasses.field(default="fifo", compare=False)
    # device-plane observability (DESIGN.md §15): thread a SearchMetrics
    # accumulator through the compiled chunks. HASHED static flag: each
    # game class compiles exactly two programs (metrics on / off), and the
    # search results are bit-identical either way (tests/test_obsv.py).
    metrics: bool = False
    # root-parallel ensemble width when the config names a FOREST tenant
    # class (repro.serve.games): the forest's leading axis is a program
    # shape, so it is HASHED — each (game, E) pair is its own class with
    # its own compiled quantum, and the default E=1 keeps every existing
    # single-tree class key unchanged.
    n_trees: int = 1

    @property
    def game_obj(self):
        """The resolved Game instance (hashable; safe to close over in jit)."""
        return game_mod.make_game(self.game, self.board_size)

    @property
    def grain(self) -> int:
        return max(1, self.n_playouts // max(1, self.n_tasks))


# ------------------------------------------------------------- selection ----
def select_one(tree: Tree, root_board: jnp.ndarray, game, cp: float,
               noise_key: jax.Array, noise_scale: float, vloss=None):
    """Descend from the root to a not-fully-expanded (or terminal) node.

    The per-lane reference of ``select_batch``: tests compare the two, and
    the search runs only the batched one.

    Returns (path, depth, leaf, board_at_leaf, n_empty_at_leaf). ``path`` is
    (max_depth,) int32 padded with the tree's PAD row index. A node counts
    as fully expanded only when its children cover every EMPTY cell; games
    that end mid-board (e.g. a Gomoku five) never get there — their
    terminal nodes keep zero children because ``game.legal_mask`` is empty,
    so the descent stops at them without a per-level terminal test.
    ``vloss`` is the iteration's virtual-loss array, or None for none.
    """
    max_depth = game.max_moves + 1
    cap = tree.cap
    C = tree.max_children
    if vloss is None:
        vloss = jnp.zeros_like(tree.visits)

    path0 = jnp.full((max_depth,), cap, dtype=jnp.int32).at[0].set(0)
    n_empty0 = (root_board == EMPTY).sum().astype(jnp.int32)

    def cond(st):
        node, board, depth, path, n_empty, done = st
        return ~done

    def body(st):
        node, board, depth, path, n_empty, _ = st
        n_kids = tree.n_children[node]
        terminal = n_empty == 0
        fully = (n_kids == n_empty) & ~terminal
        # score children
        slots = tree.children[node]  # (C,)
        valid = jnp.arange(C, dtype=jnp.int32) < n_kids
        safe = jnp.where(valid, slots, cap)
        scores = uct_mod.uct_scores(
            tree.wins[safe], tree.visits[safe], vloss[safe],
            tree.visits[node] + vloss[node], cp, valid)
        noise = None
        if noise_scale > 0.0:
            noise = noise_scale * jax.random.uniform(
                jax.random.fold_in(noise_key, depth), (C,))
        pick = uct_mod.select_child(scores, noise)
        child = safe[pick]
        mv = tree.move[child]
        new_board = game.place(board, mv, tree.to_move[node])
        nxt = (child, new_board, depth + 1,
               path.at[depth + 1].set(child), n_empty - 1, False)
        stay = (node, board, depth, path, n_empty, True)
        return jax.tree.map(
            lambda a, b: jnp.where(fully & (depth < max_depth - 2), a, b), nxt, stay)

    node, board, depth, path, n_empty, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), root_board, jnp.int32(0), path0, n_empty0, False))
    return path, depth, node, board, n_empty


def level_noise(noise_keys: jax.Array, depths: jnp.ndarray, n_slots: int,
                scale: float) -> jnp.ndarray:
    """(W, C) tie-break noise for one descent level.

    Lane w draws from ``fold_in(noise_keys[w], depths[w])`` — exactly the
    stream the scalar per-lane oracle consumes at that depth, which is what
    makes the lockstep descent bit-identical to it.
    """
    return scale * jax.vmap(
        lambda k, d: jax.random.uniform(jax.random.fold_in(k, d), (n_slots,))
    )(noise_keys, depths)


def advance_paths(paths: jnp.ndarray, depths: jnp.ndarray, child: jnp.ndarray,
                  step: jnp.ndarray) -> jnp.ndarray:
    """Write each stepping lane's chosen child at path level depth + 1."""
    D = paths.shape[1]
    return jnp.where(
        (jnp.arange(D)[None, :] == (depths + 1)[:, None]) & step[:, None],
        child[:, None], paths)


def select_batch(tree: Tree, root_board: jnp.ndarray, game, cp,
                 noise_keys: jax.Array, noise_scale: float, vloss=None):
    """Level-synchronous batched descent: all W lanes in lockstep.

    A lane steps from its node while the node is fully expanded, not
    terminal and above the depth cap (``go``). Each level scores all W
    lanes' children with a single ``kernels.ops.uct_select`` call — the
    Pallas VPU kernel on TPU, the jnp reference elsewhere (DESIGN.md §11) —
    and lanes that do not step are masked out of the tile and held in place.
    Every lane starts at the root, so level 0 reads the root's child stats
    once, as one (1, C) row broadcast to the W lanes. Below it, each trip of
    the level loop gathers the lanes' (W, C) tile (``tree.child_stat_tile``)
    and ``any(go)`` is the loop's own condition: a trip runs only where some
    lane steps, so the level at which every lane stops gathers no tile (and
    a root that is not fully expanded costs its row and no tile).
    ``vloss`` is the iteration's virtual-loss array (``sync_iteration``
    with more than one selection round), or None: then no virtual loss is
    read at all.

    Bit-identical to ``jax.vmap(select_one)`` under the same RNG schedule
    (the per-lane oracle; pinned in tests/test_batched_descent.py).

    Returns (paths, depths, leaves, boards, n_empty), each batched over W.
    """
    max_depth = game.max_moves + 1
    cap = tree.cap
    C = tree.max_children
    W = noise_keys.shape[0]

    def go_at(nodes, depths, n_empty):
        fully = (tree.n_children[nodes] == n_empty) & (n_empty != 0)
        return fully & (depths < max_depth - 2)

    def pick(tile, depths, go):
        safe, valid, wins, visits, vl, ptot = tile
        noise = (level_noise(noise_keys, depths, C, noise_scale)
                 if noise_scale > 0.0 else None)
        picks = ops.uct_select(wins, visits, vl, ptot, valid, cp,
                               noise=noise, lane_mask=go)
        return safe[jnp.arange(W), picks]

    def step(st, child):
        nodes, boards, depths, paths, n_empty, go = st
        mv = tree.move[child]
        new_boards = jax.vmap(game.place)(boards, mv, tree.to_move[nodes])
        nodes = jnp.where(go, child, nodes)
        boards = jnp.where(go[:, None], new_boards, boards)
        paths = advance_paths(paths, depths, child, go)
        depths = jnp.where(go, depths + 1, depths)
        n_empty = jnp.where(go, n_empty - 1, n_empty)
        return nodes, boards, depths, paths, n_empty, go_at(nodes, depths,
                                                             n_empty)

    n_empty_root = (root_board == EMPTY).sum().astype(jnp.int32)
    zeros = jnp.zeros((W,), jnp.int32)
    st = (zeros, jnp.tile(root_board[None, :], (W, 1)), zeros,
          jnp.full((W, max_depth), cap, dtype=jnp.int32).at[:, 0].set(0),
          jnp.broadcast_to(n_empty_root, (W,)),
          jnp.broadcast_to(go_at(jnp.int32(0), jnp.int32(0), n_empty_root),
                           (W,)))
    root_row = child_stat_tile(tree, zeros[:1], vloss=vloss)
    st = step(st, pick(
        [jnp.broadcast_to(x, (W,) + x.shape[1:]) for x in root_row],
        zeros, st[-1]))

    def body(st):
        nodes, _, depths, _, _, go = st
        tile = child_stat_tile(tree, nodes, vloss=vloss)
        return step(st, pick(tile, depths, go))

    nodes, boards, depths, paths, n_empty, _ = jax.lax.while_loop(
        lambda st: st[-1].any(), body, st)
    return paths, depths, nodes, boards, n_empty


def propose_move(tree: Tree, leaf: jnp.ndarray, board: jnp.ndarray,
                 game, key: jax.Array) -> jnp.ndarray:
    """Sample a uniformly-random untried move at `leaf` (-1 if none).

    "Random unexplored child" of the paper's expansion step. -1 (no
    expansion) also covers TERMINAL leaves: ``game.legal_mask`` is all-False
    there, so won/drawn positions are evaluated in place, never grown.
    """
    n_cells = game.n_cells
    legal = game.legal_mask(board)
    # one row of the leaf's child moves; the NO_NODE pads match no cell
    tried_moves = tree.child_move[leaf]                            # (C,)
    tried = (tried_moves[:, None]
             == jnp.arange(n_cells, dtype=jnp.int32)[None, :]).any(axis=0)
    untried = legal & ~tried
    # argmax of iid uniforms over the untried set IS a uniform choice — the
    # gumbel transform (two transcendental maps) buys nothing here
    u = jax.random.uniform(key, (n_cells,))
    mv = jnp.argmax(jnp.where(untried, u, -1.0)).astype(jnp.int32)
    return jnp.where(untried.any(), mv, jnp.int32(NO_NODE))


# -------------------------------------------------------- dedup expansion ----
def expand_batch(tree: Tree, leaves: jnp.ndarray, moves: jnp.ndarray,
                 active: jnp.ndarray):
    """Batch-insert unique (leaf, move) proposals; return per-worker node ids.

    The scatter/prefix-sum replacement for the paper's expansion-phase lock +
    atomic child index: proposals are sorted by (leaf, move) key, duplicates
    collapse onto their first occurrence, slots are rank-allocated.
    """
    W = leaves.shape[0]
    cap = tree.cap
    INVALID = jnp.int32(np.int32(2**30))

    valid = (moves >= 0) & active
    leaf_k = jnp.where(valid, leaves, INVALID)
    move_k = jnp.where(valid, moves, INVALID)
    idx = jnp.arange(W, dtype=jnp.int32)
    # lexicographic (leaf, move) sort — no key packing, so `move` may be any
    # int32 (Hex cell index or LM token id alike)
    leaf_s, move_s, order = jax.lax.sort(
        (leaf_k, move_k, idx), num_keys=2, is_stable=True)
    valid_s = leaf_s < INVALID
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (leaf_s[1:] != leaf_s[:-1]) | (move_s[1:] != move_s[:-1])]) & valid_s
    uniq_rank = jnp.cumsum(first.astype(jnp.int32)) - 1  # dup shares first's rank
    can = (tree.n_nodes + uniq_rank < cap) & valid_s
    alloc = first & can
    new_id_s = jnp.where(can, tree.n_nodes + uniq_rank, cap)

    leaf_s = jnp.where(valid_s, leaf_s, cap)
    move_s = jnp.where(valid_s, move_s, NO_NODE)

    # child-slot = existing n_children[leaf] + rank of this unique within its
    # leaf group (uniques of one leaf are contiguous in sorted order)
    leaf_prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), leaf_s[:-1]])
    group_start = leaf_s != leaf_prev
    start_rank = jax.lax.cummax(jnp.where(group_start, uniq_rank, -1))
    within = uniq_rank - start_rank
    slot = jnp.clip(tree.n_children[leaf_s] + within, 0, tree.max_children - 1)

    tgt = jnp.where(alloc, new_id_s, cap)
    src_leaf = jnp.where(alloc, leaf_s, cap)
    parent = tree.parent.at[tgt].set(jnp.where(alloc, leaf_s, NO_NODE))
    move_arr = tree.move.at[tgt].set(jnp.where(alloc, move_s, NO_NODE))
    to_move = tree.to_move.at[tgt].set(
        jnp.where(alloc, 3 - tree.to_move[leaf_s], 0))
    children = tree.children.at[src_leaf, jnp.where(alloc, slot, 0)].set(
        jnp.where(alloc, new_id_s, tree.children[src_leaf, jnp.where(alloc, slot, 0)]))
    child_move = tree.child_move.at[src_leaf, jnp.where(alloc, slot, 0)].set(
        jnp.where(alloc, move_s, NO_NODE))
    n_children = tree.n_children.at[src_leaf].add(alloc.astype(jnp.int32))
    n_new = alloc.sum().astype(jnp.int32)

    # hygiene: pad row never owns state
    parent = parent.at[cap].set(NO_NODE)
    move_arr = move_arr.at[cap].set(NO_NODE)
    n_children = n_children.at[cap].set(0)

    tree = tree._replace(parent=parent, move=move_arr, to_move=to_move,
                         children=children, child_move=child_move,
                         n_children=n_children,
                         n_nodes=tree.n_nodes + n_new)
    # map back to worker order: duplicates get their first occurrence's id
    per_sorted = jnp.where(valid_s & can, new_id_s, cap)
    new_ids = jnp.zeros((W,), jnp.int32).at[order].set(per_sorted)
    return tree, new_ids


# ---------------------------------------------------------- sync iteration ----
def sync_iteration(tree: Tree, root_board: jnp.ndarray, cfg: GSCPMConfig,
                   cp, iter_keys: jnp.ndarray, active: jnp.ndarray,
                   metrics=None):
    """One batched GSCPM iteration of width W = cfg.n_workers.

    ``cp`` is the traced exploration constant (never read from cfg here —
    see GSCPMConfig). Selection runs the level-synchronous batched descent
    (``select_batch``) in ``vl_rounds`` rounds of W / R lanes; the playout
    phase is the fused (W, cells) ``game.playout_batch``.

    Virtual loss lives only inside this call: with R > 1 selection rounds a
    zeroed (cap+1,) array rides the scan over the rounds, each round adds
    its lanes' paths into it and the next round reads it; it is dropped at
    the end. With R == 1 there is none.

    ``metrics`` is a ``repro.obsv.SearchMetrics`` accumulator or None; the
    call returns ``(tree, metrics)`` either way. The metric updates are pure
    extra reductions over values this function computes anyway — no RNG
    consumed, nothing fed back — so the produced tree is bit-identical with
    or without them.
    """
    game = cfg.game_obj
    W = cfg.n_workers
    R = max(1, min(cfg.vl_rounds, W))
    while W % R != 0:  # static fixup; R is a python int
        R -= 1
    Wr = W // R

    def round_body(vloss, inp):
        keys_g, act_g = inp
        # per-lane (noise, move, playout) keys from one split of the lane's
        # iteration key: the RNG schedule the per-lane references share
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(keys_g)
        k_noise, k_move, k_po = ks[:, 0], ks[:, 1], ks[:, 2]
        paths, depths, leaves, boards, _ = select_batch(
            tree, root_board, game, cp, k_noise, cfg.select_noise,
            vloss=vloss)
        mvs = jax.vmap(
            lambda l, b, k: propose_move(tree, l, b, game, k)
        )(leaves, boards, k_move)
        if vloss is not None:
            with jax.named_scope("backup"):
                vloss = add_vloss(vloss, paths, act_g.astype(jnp.float32),
                                  cfg.virtual_loss)
        return vloss, (paths, depths, leaves, boards, mvs, k_po)

    keys_r = iter_keys.reshape(R, Wr, *iter_keys.shape[1:])
    active_r = active.reshape(R, Wr)

    # one named scope per phase (descent, expand, leaf_eval, backup): the
    # compiled ops carry it in their op_name metadata, so a device trace
    # attributes each op to its phase; metadata only, the program's values
    # are the same. An op under two phases belongs to the inner one.
    with jax.named_scope("descent"):
        vloss = jnp.zeros_like(tree.visits) if R > 1 else None
        _, outs = jax.lax.scan(round_body, vloss, (keys_r, active_r))

    paths = outs[0].reshape(W, -1)
    depths = outs[1].reshape(W)
    leaves = outs[2].reshape(W)
    boards = outs[3].reshape(W, -1)
    moves = outs[4].reshape(W)
    po_keys = outs[5].reshape(W, *outs[5].shape[2:])

    n_nodes_before = tree.n_nodes
    with jax.named_scope("expand"):
        tree, new_ids = expand_batch(tree, leaves, moves, active)

        expanded = new_ids < tree.cap
        # the new node joins the backup path
        paths = jnp.where(
            jnp.arange(paths.shape[1])[None, :] == (depths + 1)[:, None],
            jnp.where(expanded[:, None], new_ids[:, None], tree.cap),
            paths)

    with jax.named_scope("leaf_eval"):
        # place each lane's proposed move (if any) — game-agnostic given
        # the shared board convention; lanes that proposed nothing evaluate
        # the leaf position itself (terminal leaves included)
        movers = tree.to_move[leaves]
        do = moves >= 0
        placed = jax.vmap(game.place)(boards, jnp.maximum(moves, 0), movers)
        b2 = jnp.where(do[:, None], placed, boards)
        nxt = jnp.where(do, 3 - movers, movers)
        # fused leaf evaluation: ONE batched (W, cells) playout stage for
        # all W lanes (bit-identical values to the per-lane
        # ``game.playout_scalar`` — tests/test_game_protocol.py)
        winners = game.playout_batch(b2, nxt, po_keys)
    with jax.named_scope("backup"):
        tree = backup_paths(tree, paths, winners, active.astype(jnp.float32))
    if metrics is not None:
        from repro.obsv.search_metrics import accumulate_iteration

        metrics = accumulate_iteration(
            metrics, depths_grouped=outs[1], active=active, leaves=leaves,
            moves=moves, eval_boards=b2,
            backup_levels=path_levels(paths, tree.cap),
            n_nodes_before=n_nodes_before, n_nodes_after=tree.n_nodes)
    return tree, metrics


def chunk_body(tree: Tree, root_board: jnp.ndarray, cfg: GSCPMConfig,
          task_keys: jnp.ndarray, active: jnp.ndarray, m: jnp.ndarray, cp,
          metrics=None):
    """The compiled round's body: ``m`` sync iterations of one tree.

    Iteration ``i`` of task lane ``w`` draws from ``fold_in(task_keys[w],
    i)``. ``metrics`` (a ``SearchMetrics`` accumulator or None) rides the
    loop beside the tree; returns ``(tree, metrics)``. ``run_chunk`` jits
    it, ``root_parallel`` runs it under ``jax.vmap`` for a forest and that
    vmap under ``shard_map`` across chips. ``cfg.metrics`` must say whether
    an accumulator rides along: the flag is hashed, so each arm is its own
    compiled program.
    """
    if cfg.metrics != (metrics is not None):     # trace-time consistency
        raise ValueError(
            f"cfg.metrics={cfg.metrics} but metrics accumulator "
            f"{'missing' if metrics is None else 'provided'} — pass "
            "repro.obsv.init_search_metrics() iff the flag is set")

    def body(i, carry):
        tr, mx = carry
        iter_keys = jax.vmap(lambda tk: jax.random.fold_in(tk, i))(task_keys)
        return sync_iteration(tr, root_board, cfg, cp, iter_keys, active, mx)

    return jax.lax.fori_loop(0, m, body, (tree, metrics))


def chunk_result(tree, metrics):
    """A chunk's return value: the tree alone, or ``(tree, metrics)`` where
    an accumulator rides along."""
    return tree if metrics is None else (tree, metrics)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def run_chunk(tree: Tree, root_board: jnp.ndarray, cfg: GSCPMConfig,
              task_keys: jnp.ndarray, active: jnp.ndarray,
              m: jnp.ndarray, cp, metrics=None):
    """Run `m` sync iterations (one task-grain per lane) — jitted once per
    cfg; ``m`` and ``cp`` are traced, so grain/Cp sweeps never retrace.

    With ``cfg.metrics`` a ``SearchMetrics`` accumulator must ride along
    and the chunk returns ``(tree, metrics)`` — the flag is hashed, so a
    game class owns exactly TWO compiled programs: one per metrics arm.
    """
    return chunk_result(*chunk_body(tree, root_board, cfg, task_keys,
                                    active, m, cp, metrics))


# ------------------------------------------------------------------ driver ----
@jax.jit
def fold_task_keys(key: jax.Array, task_ids: jnp.ndarray) -> jax.Array:
    """Per-task RNG streams (jitted: per-round key building is dispatch-only,
    not a re-traced eager vmap)."""
    return jax.vmap(lambda t: jax.random.fold_in(key, t))(task_ids)


def run_schedule_round(tree: Tree, board: jnp.ndarray, cfg: GSCPMConfig,
                       key: jax.Array, rnd: sched.Round, cp, metrics=None):
    """Advance one schedule ``Round``: the atomic dispatch unit of a search.

    Both the uninterrupted driver (``gscpm_search``) and the TPFIFO
    game-serving engine (``repro.serve.games``) run searches as a sequence
    of these calls — a round's RNG streams depend only on (``key``,
    ``rnd.task_ids``), never on wall-clock interleaving, so a search served
    in grain-sized quanta with preemptions in between is BIT-IDENTICAL to
    the same round sequence run back to back (pinned in
    tests/test_serve_games.py). With ``cfg.metrics`` the accumulator rides
    along and the round returns ``(tree, metrics)``.
    """
    task_keys = fold_task_keys(key, jnp.asarray(rnd.task_ids, dtype=jnp.int32))
    args = (tree, board, cfg, task_keys, jnp.asarray(rnd.active),
            jnp.asarray(rnd.m, dtype=jnp.int32), cp)
    if cfg.metrics:
        return run_chunk(*args, metrics)
    return run_chunk(*args)


def warm_tree_check(tree: Tree, to_move: int, cfg: GSCPMConfig) -> None:
    """Eagerly validate a warm-start tree against the config (DESIGN.md §16).

    A warm tree with the wrong capacity or children width would not crash —
    it would silently compile a SECOND program for the game class, defeating
    the zero-recompile serving discipline — so shape mismatches fail loudly
    here. The side-to-move must also match: a re-rooted tree already knows
    whose turn it is, and searching it for the other player would corrupt
    the retained statistics' meaning.
    """
    if tree.cap != cfg.tree_cap:
        raise ValueError(
            f"warm tree cap {tree.cap} != cfg.tree_cap {cfg.tree_cap}; "
            "re-root with new_cap=cfg.tree_cap to match the serving class")
    n_actions = cfg.game_obj.n_actions
    if tree.max_children != n_actions:
        raise ValueError(
            f"warm tree max_children {tree.max_children} != game n_actions "
            f"{n_actions} — tree built for a different game class")
    tm = int(tree.to_move[..., 0].reshape(-1)[0])
    if tm != to_move:
        raise ValueError(
            f"warm tree root to_move {tm} != requested to_move {to_move}")


def gscpm_search(board: jnp.ndarray, to_move: int, cfg: GSCPMConfig,
                 key: jax.Array, *, tree: Tree | None = None,
                 tracer=None) -> tuple[Tree, dict[str, Any]]:
    """Full GSCPM search (paper Fig 4): schedule tasks, return tree + stats.

    ``tree`` warm-starts the search from an existing tree — typically the
    output of ``reroot_tree`` after a move was played (DESIGN.md §16).
    The schedule is exactly ``cfg``'s either way, so a warm search from
    tree T is bit-identical to a cold search whose ``init_tree`` was
    hand-replaced by T: warm start changes the starting evidence, never
    the program. The caller keeps ownership semantics in mind: the passed
    tree's buffers are DONATED to the first chunk (``run_chunk``), so the
    input object must not be reused afterwards.

    ``cfg.metrics`` adds a device-plane ``SearchMetrics`` summary under
    ``stats["metrics"]`` (one host readback at the end of the search).

    Host spans (``repro.obsv.trace.span``, always on and non-blocking) mark
    the search on the profiler's clock: ``gscpm_search`` around the call,
    ``search_init``, one ``gscpm_round`` per schedule round (args
    ``round``, ``m``, ``tasks``), ``search_wait`` on the final device sync
    and ``search_stats`` around the readbacks. ``tracer`` (a
    ``repro.obsv.TraceRecorder``) also records the ``gscpm_round`` spans,
    annotated with the round's work so ``obsv.profile`` can fit the
    measured dispatch burden; it then blocks on the device after every
    round to attribute device time to its round — a profiling mode, not
    the fastest way to run a search.
    """
    with span("gscpm_search"):
        with span("search_init"):
            reused_nodes = 0
            reused_visits = 0.0
            if tree is None:
                tree = init_tree(cfg.tree_cap, cfg.game_obj.n_actions,
                                 to_move)
            else:
                warm_tree_check(tree, to_move, cfg)
                reused_nodes = int(tree.n_nodes) - 1   # cold trees own a root
                reused_visits = float(tree.visits[0])
            metrics = None
            if cfg.metrics:
                from repro.obsv.search_metrics import init_search_metrics
                metrics = init_search_metrics(tree_nodes_reused=reused_nodes)
            schedule = sched.make_schedule(
                cfg.n_playouts, cfg.n_tasks, cfg.n_workers, cfg.scheduler)

        cp = jnp.asarray(cfg.cp, jnp.float32)
        t0 = time.perf_counter()
        playouts = 0
        masked_lane_iters = 0
        for r, rnd in enumerate(schedule):
            m, tasks = int(rnd.m), int(rnd.active.sum())
            with span("gscpm_round", tracer, round=r, m=m, tasks=tasks,
                      rounds=1, iterations=m, workers=cfg.n_workers):
                out = run_schedule_round(tree, board, cfg, key, rnd, cp,
                                         metrics)
                tree, metrics = out if cfg.metrics else (out, metrics)
                if tracer:
                    jax.block_until_ready(tree.visits)
            if tracer:
                tracer.poll_compiles()
            playouts += tasks * m
            masked_lane_iters += (cfg.n_workers - tasks) * m
        with span("search_wait"):
            jax.block_until_ready(tree.visits)
        dt = time.perf_counter() - t0

        with span("search_stats"):
            stats = {
                "time_s": dt,
                "playouts": playouts,
                "playouts_per_s": playouts / max(dt, 1e-9),
                "rounds": len(schedule),
                "grain": cfg.grain,
                "masked_lane_fraction": masked_lane_iters
                / max(1, playouts + masked_lane_iters),
                "tree_nodes": int(tree.n_nodes),
                "root_value": float(root_value(tree)),
                "best_move": int(best_child(tree)),
            }
            if reused_nodes or reused_visits:
                stats["reused_nodes"] = reused_nodes
                stats["reused_visits"] = reused_visits
            if cfg.metrics:
                from repro.obsv.search_metrics import summarize_metrics
                stats["metrics"] = summarize_metrics(metrics)
    return tree, stats
