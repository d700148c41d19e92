"""Array-of-structs MCTS tree, fixed capacity, scatter-update friendly.

The paper keeps, per node, a preallocated vector of children plus atomic
counters (`w_j`, `n_j`, child-allocation index). The TPU-native equivalent is
a struct-of-arrays tree with one PAD row (index == capacity) that absorbs
masked scatter writes, and deterministic `.at[].add` scatter updates in place
of atomics (DESIGN.md §2).

All shapes are static; the tree is a pytree and can be carried through
`lax.fori_loop` / `lax.while_loop` and `jit`.

Every op indexes node axes from the RIGHT (``shape[-1]``), so a *forest* — E
independent trees stacked along a leading ensemble axis (DESIGN.md §3) — is
also a valid ``Tree`` whose per-member ops are recovered with ``jax.vmap``.
``init_forest`` / ``forest_member`` / ``forest_size`` are the ensemble
helpers; the root-parallel search layer lives in ``repro.core.root_parallel``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NO_NODE = -1  # null child / parent sentinel


class Tree(NamedTuple):
    """MCTS tree with `cap` usable rows and one pad row at index `cap`.

    wins[j] is from the perspective of the player who MOVED INTO node j
    (i.e. ``3 - to_move[j]``), matching the UCT bookkeeping in the paper:
    X_j = w_j / n_j is the win rate child j offers its parent's mover.
    """

    parent: jnp.ndarray      # (cap+1,) i32
    move: jnp.ndarray        # (cap+1,) i32  move from parent that made this node
    to_move: jnp.ndarray     # (cap+1,) i32  player to move at this node (1|2)
    children: jnp.ndarray    # (cap+1, max_children) i32, NO_NODE padded
    # (cap+1, max_children) i32, NO_NODE padded: child_move[p, s] ==
    # move[children[p, s]] on every slot s < n_children[p], so a node's tried
    # moves are one row, read as ``children[p]`` is
    child_move: jnp.ndarray
    n_children: jnp.ndarray  # (cap+1,) i32
    visits: jnp.ndarray      # (cap+1,) f32  n_j
    wins: jnp.ndarray        # (cap+1,) f32  w_j
    n_nodes: jnp.ndarray     # ()      i32  allocation counter (the paper's atomic index)

    @property
    def cap(self) -> int:
        # shape[-1], not shape[0]: a forest (leading ensemble axis) must
        # report the same per-member capacity as a single tree
        return self.parent.shape[-1] - 1

    @property
    def max_children(self) -> int:
        return self.children.shape[-1]


def init_tree(cap: int, max_children: int, root_to_move) -> Tree:
    """Fresh tree containing only the root (node 0)."""
    return Tree(
        parent=jnp.full((cap + 1,), NO_NODE, dtype=jnp.int32),
        move=jnp.full((cap + 1,), NO_NODE, dtype=jnp.int32),
        to_move=jnp.zeros((cap + 1,), dtype=jnp.int32)
        .at[0]
        .set(jnp.asarray(root_to_move, dtype=jnp.int32)),
        children=jnp.full((cap + 1, max_children), NO_NODE, dtype=jnp.int32),
        child_move=jnp.full((cap + 1, max_children), NO_NODE,
                            dtype=jnp.int32),
        n_children=jnp.zeros((cap + 1,), dtype=jnp.int32),
        visits=jnp.zeros((cap + 1,), dtype=jnp.float32),
        wins=jnp.zeros((cap + 1,), dtype=jnp.float32),
        n_nodes=jnp.asarray(1, dtype=jnp.int32),
    )


def path_levels(paths: jnp.ndarray, cap: int) -> jnp.ndarray:
    """How many leading columns of a (W, D) path array can hold a node: the
    last column with an entry other than the pad row ``cap``, plus one."""
    held = (paths != cap).any(axis=0)
    return jnp.max(jnp.where(held, jnp.arange(1, paths.shape[1] + 1), 0))


# path columns the backup scatters per trip of its level loop, W x K
# entries each (chosen from v5e timings, PERF.md §6)
_BACKUP_BLOCK = 4


def backup_paths(tree: Tree, paths: jnp.ndarray, values: jnp.ndarray,
                 weights: jnp.ndarray) -> Tree:
    """Batched backpropagation — the scatter-add analogue of atomic w_j/n_j.

    paths:   (W, max_depth) i32 node ids, PAD (== cap) where unused
    values:  (W,) playout outcomes: winning player (1|2) or 0 for a DRAW
    weights: (W,) f32 1.0 for active lanes, 0.0 for masked lanes

    Only the leading ``path_levels`` columns can hold a node, so a loop
    scatters those, ``_BACKUP_BLOCK`` columns per trip, and never the pad
    columns below the deepest lane. The sums are those of one scatter over
    every column: visits are counts and wins multiples of 0.5, exact in f32
    in any order below 2^24.
    """
    W, D = paths.shape
    K, cap = _BACKUP_BLOCK, tree.cap
    # trip t covers columns t*K .. t*K+K-1, column-major: (trips, K*W)
    blocks = jnp.pad(paths.T, ((0, -D % K), (0, 0)),
                     constant_values=cap).reshape(-1, K * W)
    vals = jnp.tile(values.astype(jnp.int32), K)
    lane_w = jnp.tile(weights, K)

    def trip(t, stats):
        visits, wins = stats
        nodes = blocks[t]
        # credit: 1 if the player who moved into the node won the playout,
        # 0.5 on a draw (the value every player is indifferent to — keeps
        # X_j = w_j / n_j in [0, 1] with 0.5 as the draw point)
        mover = 3 - tree.to_move[nodes]
        win = jnp.where(vals == 0, 0.5, (mover == vals).astype(jnp.float32))
        w = lane_w * (nodes != cap)  # mask pads & inactive lanes
        return visits.at[nodes].add(w), wins.at[nodes].add(w * win)

    trips = (path_levels(paths, cap) + K - 1) // K
    visits, wins = jax.lax.fori_loop(0, trips, trip, (tree.visits, tree.wins))
    # pad row may have accumulated; zero it for hygiene
    visits = visits.at[cap].set(0.0)
    wins = wins.at[cap].set(0.0)
    return tree._replace(visits=visits, wins=wins)


def add_vloss(vloss: jnp.ndarray, paths: jnp.ndarray, weights: jnp.ndarray,
              amount: float = 1.0) -> jnp.ndarray:
    """Scatter virtual loss along selected paths (diversifies later rounds).

    ``vloss`` is a (cap+1,) f32 array that lives only inside one sync
    iteration (``gscpm.sync_iteration``); the tree itself holds none.
    """
    D = paths.shape[1]
    cap = vloss.shape[-1] - 1
    flat = paths.reshape(-1)
    w = jnp.repeat(weights, D) * (flat != cap) * amount
    return vloss.at[flat].add(w).at[cap].set(0.0)


def child_stat_tile(tree: Tree, nodes: jnp.ndarray, vloss=None):
    """Gather the child statistics of a (W,) node batch as (W, C) tiles.

    Returns ``(safe, valid, wins, visits, vloss, parent_total)``: ``safe``
    holds child ids with invalid slots redirected to the PAD row (whose
    stats are all zero), ``valid`` masks real slots, and ``parent_total`` is
    each node's visits + virtual loss. This is the gather feeding one
    level-synchronous ``kernels.ops.uct_select`` call — all W lanes of a
    descent score one tree level in a single (W, C) tile (DESIGN.md §11).

    ``vloss`` is the iteration's (cap+1,) virtual-loss array, or None where
    there is none (one selection round per iteration): the virtual-loss
    tile is then zeros made without a gather and the parent total is the
    node's visits.
    """
    C = tree.max_children
    cap = tree.cap
    slots = tree.children[nodes]                                   # (W, C)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < tree.n_children[nodes][:, None]
    safe = jnp.where(valid, slots, cap)
    wins, visits = tree.wins[safe], tree.visits[safe]
    if vloss is None:
        return (safe, valid, wins, visits, jnp.zeros_like(visits),
                tree.visits[nodes])
    parent_total = tree.visits[nodes] + vloss[nodes]               # (W,)
    return safe, valid, wins, visits, vloss[safe], parent_total


def best_child(tree: Tree) -> jnp.ndarray:
    """Most-visited root child's move (the paper's final move selection)."""
    slots = tree.children[0]  # (max_children,)
    valid = jnp.arange(slots.shape[0]) < tree.n_children[0]
    safe = jnp.where(valid, slots, tree.cap)
    counts = jnp.where(valid, tree.visits[safe], -jnp.inf)
    return tree.move[safe[jnp.argmax(counts)]]


def root_value(tree: Tree) -> jnp.ndarray:
    """Root win-rate estimate for the root's to-move player.

    wins[child] is from the mover-into-child = root's to-move perspective, so
    the root player's value is sum(child wins)/sum(child visits).
    """
    slots = tree.children[0]
    valid = jnp.arange(slots.shape[0]) < tree.n_children[0]
    safe = jnp.where(valid, slots, tree.cap)
    w = jnp.where(valid, tree.wins[safe], 0.0).sum()
    n = jnp.where(valid, tree.visits[safe], 0.0).sum()
    return w / jnp.maximum(n, 1.0)


# --------------------------------------------------------------- forests ----
def init_forest(n_trees: int, cap: int, max_children: int,
                root_to_move) -> Tree:
    """E fresh trees stacked along a leading ensemble axis (DESIGN.md §3).

    ``root_to_move`` is a scalar (shared by all members) or an (E,) vector
    (one independent root position per member, e.g. multi-request serving).
    """
    tm = jnp.broadcast_to(jnp.asarray(root_to_move, dtype=jnp.int32),
                          (n_trees,))
    return jax.vmap(lambda t: init_tree(cap, max_children, t))(tm)


def forest_size(forest: Tree) -> int:
    """Number of ensemble members E (leading axis of every leaf)."""
    return forest.parent.shape[0]


def forest_member(forest: Tree, e: int) -> Tree:
    """Extract member `e` as a plain single tree (host-side helper)."""
    return jax.tree.map(lambda x: x[e], forest)


def root_move_stats(tree: Tree, n_moves: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dense per-move (visits, wins) of the root's children.

    Returns two (n_moves,) f32 arrays indexed by move id; moves without a
    root child are zero. This is the merge currency of root parallelism:
    per-member child *slots* are in discovery order, but per-move dense
    vectors add across ensemble members (DESIGN.md §3).
    """
    slots = tree.children[0]
    valid = jnp.arange(slots.shape[0]) < tree.n_children[0]
    safe = jnp.where(valid, slots, tree.cap)
    mv = jnp.where(valid, tree.move[safe], n_moves)  # pad bucket == n_moves
    mv = jnp.clip(mv, 0, n_moves)
    visits = jnp.zeros((n_moves + 1,), jnp.float32).at[mv].add(
        jnp.where(valid, tree.visits[safe], 0.0))[:n_moves]
    wins = jnp.zeros((n_moves + 1,), jnp.float32).at[mv].add(
        jnp.where(valid, tree.wins[safe], 0.0))[:n_moves]
    return visits, wins


def root_summary(tree: Tree, n_moves: int,
                 reused_visits: int | None = None) -> dict:
    """Host-side snapshot of the root decision — "whatever stats the tree
    has now".

    Dense per-move visit/win vectors (``root_move_stats``), the
    most-visited move, and the root value, pulled to numpy. This is the
    retire currency of game-search serving (``repro.serve.games``): a
    deadline-expired request ships this snapshot mid-search, a finished one
    ships it at budget exhaustion, and the serving-equivalence suite
    compares it bit-for-bit against an uninterrupted search's snapshot. A
    tree with no root children yet reports ``best_move == NO_NODE`` (-1).

    Works unchanged on RE-ROOTED trees (``reroot_tree``), whose root
    carries nonzero visits before the first fresh playout — the snapshot is
    always "retained + new" evidence. Pass ``reused_visits`` (the root
    visit count the search started from; warm sessions know it) to expose
    how much of the evidence was inherited; it is reported only when
    present so cold-search snapshots stay bit-comparable across versions.
    """
    visits, wins = root_move_stats(tree, n_moves)
    out = {
        "root_visits": np.asarray(visits),
        "root_wins": np.asarray(wins),
        "best_move": int(best_child(tree)),
        "root_value": float(root_value(tree)),
        "tree_nodes": int(tree.n_nodes),
    }
    if reused_visits is not None:
        out["reused_visits"] = int(reused_visits)
    return out


@functools.partial(jax.jit, static_argnames=("n_moves",))
def root_summary_device(tree: Tree, n_moves: int) -> dict:
    """Device-side twin of ``root_summary``: the same reductions as ONE
    async-dispatched jitted program, nothing pulled to host.

    The pipelined serving engine (``repro.serve.games``, DESIGN.md §18)
    dispatches this when it detects retirement and materializes the result
    a tick later, so the host readback overlaps the next tick's quanta
    instead of stalling the whole pool on one finished search.
    """
    visits, wins = root_move_stats(tree, n_moves)
    return {"root_visits": visits, "root_wins": wins,
            "best_move": best_child(tree), "root_value": root_value(tree),
            "tree_nodes": tree.n_nodes}


def materialize_root_summary(dev: dict,
                             reused_visits: int | None = None) -> dict:
    """Pull a ``root_summary_device`` dict to the exact host types
    ``root_summary`` ships — the deferred half of the pipelined retire."""
    out = {
        "root_visits": np.asarray(dev["root_visits"]),
        "root_wins": np.asarray(dev["root_wins"]),
        "best_move": int(dev["best_move"]),
        "root_value": float(dev["root_value"]),
        "tree_nodes": int(dev["tree_nodes"]),
    }
    if reused_visits is not None:
        out["reused_visits"] = int(reused_visits)
    return out


# -------------------------------------------------------------- re-rooting ----
def _reroot_impl(tree: Tree, move: jnp.ndarray, new_cap: int) -> Tree:
    """Traced body of ``reroot_tree`` (see its docstring for the contract).

    Everything is masked scatter/gather over static shapes:

    1. locate the root child that carries ``move`` (may not exist);
    2. subtree membership by pointer doubling on the parent array — the
       old root and the played child become self-loops, so every allocated
       node's ancestor pointer converges to one of the two in
       ``ceil(log2(cap))`` gather rounds;
    3. per-node depth by the companion (ancestor, distance) doubling;
    4. BFS renumbering = one two-key ``lax.sort`` by (depth, old id):
       parents sort strictly before children, so the new ids satisfy the
       ``parent[i] < i`` allocation-order invariant every host-side walk
       (``node_depths``, ``check_invariants``) relies on;
    5. one gather per field copies the retained rows into a fresh layout;
       non-retained rows source the old PAD row, whose fields are exactly
       the ``init_tree`` values — so the compacted tree is bit-identical
       to a freshly grown one node-for-node.
    """
    cap = tree.cap
    C = tree.max_children
    n = tree.n_nodes
    idx = jnp.arange(cap + 1, dtype=jnp.int32)
    alloc = idx < n

    # 1. the played child (old pad row when the move was never expanded)
    slots = tree.children[0]
    valid = jnp.arange(C, dtype=jnp.int32) < tree.n_children[0]
    safe = jnp.where(valid, slots, cap)
    hit = valid & (tree.move[safe] == move)
    exists = hit.any()
    child = jnp.where(exists, safe[jnp.argmax(hit)], cap)

    # 2. membership: ancestor pointers converge to a self-loop at the old
    # root (non-members) or at the played child (members)
    rounds = max(1, int(cap + 1).bit_length())
    anc = jnp.where(alloc, tree.parent, idx)   # unallocated/pad: self-loop
    anc = jnp.where(idx == 0, 0, anc)
    anc = jnp.where(idx == child, idx, anc)
    # 3. distance-to-root rides the same doubling (root contributes 0)
    par = jnp.where(alloc, tree.parent, idx)
    par = jnp.where(idx == 0, 0, par)
    dist = ((idx != 0) & alloc).astype(jnp.int32)

    def _double(_, s):
        anc, dist, par = s
        return anc[anc], dist + dist[par], par[par]

    # fori_loop, not a Python loop: unrolling ~14 gather rounds makes the
    # XLA:CPU compile take minutes at tree_cap=16k
    anc, dist, par = jax.lax.fori_loop(0, rounds, _double, (anc, dist, par))
    member = alloc & (anc == child)
    n_sub = member.sum().astype(jnp.int32)

    # 4. BFS order: members sorted by (depth, old id); non-members sink
    BIG = jnp.int32(2**30)
    key_depth = jnp.where(member, dist, BIG)
    _, order = jax.lax.sort((key_depth, idx), num_keys=2)
    rank = jnp.arange(cap + 1, dtype=jnp.int32)
    is_m = rank < n_sub
    # old id -> new id; everything outside the subtree maps to the new PAD
    new_of_old = jnp.full((cap + 1,), new_cap, jnp.int32).at[
        jnp.where(is_m, order, cap)].set(
        jnp.where(is_m, rank, new_cap))

    # 5. gather rows into the fresh layout (new row k copies old row
    # order[k]; rows past the subtree copy the old PAD row == init state)
    kk = jnp.arange(new_cap + 1, dtype=jnp.int32)
    take = kk < n_sub
    src = jnp.where(take, order[jnp.minimum(kk, cap)], cap)

    parent = jnp.where(take, new_of_old[jnp.clip(tree.parent[src], 0, cap)],
                       NO_NODE).at[0].set(NO_NODE)
    mv_arr = jnp.where(take, tree.move[src], NO_NODE).at[0].set(NO_NODE)
    to_move = jnp.where(take, tree.to_move[src], 0).at[0].set(
        3 - tree.to_move[0])
    ch_old = tree.children[src]                          # (new_cap+1, C)
    children = jnp.where((ch_old >= 0) & take[:, None],
                         new_of_old[jnp.clip(ch_old, 0, cap)],
                         NO_NODE).astype(jnp.int32)
    return Tree(
        parent=parent.astype(jnp.int32),
        move=mv_arr.astype(jnp.int32),
        to_move=to_move.astype(jnp.int32),
        children=children,
        # moves are cells, not node ids: retained rows carry over as they are
        child_move=jnp.where(take[:, None], tree.child_move[src], NO_NODE),
        n_children=jnp.where(take, tree.n_children[src], 0),
        visits=jnp.where(take, tree.visits[src], 0.0),
        wins=jnp.where(take, tree.wins[src], 0.0),
        n_nodes=jnp.maximum(n_sub, 1),
    )


_reroot_jit = jax.jit(_reroot_impl, static_argnames=("new_cap",))


@functools.partial(jax.jit, static_argnames=("new_cap",))
def _reroot_forest_jit(forest: Tree, moves: jnp.ndarray, new_cap: int) -> Tree:
    return jax.vmap(lambda t, m: _reroot_impl(t, m, new_cap))(forest, moves)


def _check_reroot_cap(cap: int, new_cap: int | None) -> int:
    if new_cap is None:
        return cap
    if new_cap < cap:
        # the retained subtree holds at most cap-1 nodes, so new_cap >= cap
        # always fits; anything smaller cannot be proven to fit from traced
        # shapes alone — refuse loudly instead of silently truncating the
        # subtree (the stats-retention contract would be broken)
        raise ValueError(
            f"reroot capacity overflow risk: new_cap={new_cap} < "
            f"source cap={cap}; a re-rooted subtree can hold up to cap-1 "
            "nodes, so the fresh budget must be >= the source capacity "
            "(shrinking a tree would silently drop retained statistics)")
    return new_cap


def reroot_tree(tree: Tree, move, new_cap: int | None = None) -> Tree:
    """Re-root the tree at the root child carrying ``move`` (compaction).

    The played child's whole subtree is BFS-renumbered into a fresh
    fixed-capacity tree whose node 0 is that child: the warm start of the
    NEXT move's search (DESIGN.md §16). Jittable — ``move`` is traced, the
    pass is one compiled program per (cap, max_children, new_cap) shape.

    Retention contract (asserted by ``check_reroot_retention`` and the
    test suite): every retained node's ``visits``/``wins``/``to_move``/
    ``move``, its child COUNT and child set, and its depth (shifted by
    exactly -1) are bit-identical to the corresponding node of the source
    tree. Rows outside the subtree are indistinguishable from a fresh
    ``init_tree``'s, so a search continuing from the result behaves exactly
    like one hand-seeded with the retained statistics.

    Re-rooting onto a move the root never expanded (or an unvisited child)
    yields a valid 1-node tree: root ``to_move`` flipped, zero statistics —
    a cold start in warm clothing. ``new_cap`` (default: source capacity)
    must be >= the source capacity; smaller budgets raise ``ValueError`` at
    trace time rather than silently truncating the subtree.
    """
    new_cap = _check_reroot_cap(tree.cap, new_cap)
    return _reroot_jit(tree, jnp.asarray(move, jnp.int32), new_cap=new_cap)


def reroot_forest(forest: Tree, moves, new_cap: int | None = None) -> Tree:
    """``reroot_tree`` for all E members in ONE vmapped call.

    ``moves`` is a scalar (every member re-roots at the same played move —
    the ensemble self-play case) or an (E,) vector (independent positions).
    Each member keeps its own subtree; members that never expanded the move
    come back as 1-node trees (the partial-merge twin of root parallelism's
    "a member cannot host stats for a move it never discovered").
    """
    new_cap = _check_reroot_cap(forest.cap, new_cap)
    E = forest_size(forest)
    mv = jnp.broadcast_to(jnp.asarray(moves, jnp.int32), (E,))
    return _reroot_forest_jit(forest, mv, new_cap=new_cap)


def check_reroot_retention(src: Tree, dst: Tree, move: int) -> int:
    """Host-side assertion of the re-root retention contract; returns the
    number of retained nodes.

    Walks the source subtree under the played child and checks every node
    against its image in ``dst``: bit-identical ``visits``/``wins``,
    matching ``to_move``/``move``/child count, child moves as a set, and
    depth shifted by exactly one. Used by the tests and available to
    drivers as a debugging probe (it is O(subtree), host-side, eager).
    """
    s = jax.tree.map(np.asarray, src)
    d = jax.tree.map(np.asarray, dst)
    kids0 = s.children[0][: int(s.n_children[0])]
    hits = [int(k) for k in kids0 if int(s.move[k]) == int(move)]
    if not hits:
        assert int(d.n_nodes) == 1, "unexpanded move must yield 1-node tree"
        assert d.visits[0] == 0.0 and d.wins[0] == 0.0
        assert int(d.to_move[0]) == 3 - int(s.to_move[0])
        return 0
    root = hits[0]
    sdep = node_depths(src)
    ddep = node_depths(dst)
    # BFS pairing: source subtree nodes in (depth, old id) order ARE the
    # destination nodes 0..n_sub-1 in id order (the renumbering's contract)
    members = []
    stack = [root]
    while stack:
        u = stack.pop()
        members.append(u)
        stack.extend(int(c) for c in s.children[u][: int(s.n_children[u])])
    members.sort(key=lambda u: (int(sdep[u]), u))
    n_sub = len(members)
    assert int(d.n_nodes) == n_sub, \
        f"retained {int(d.n_nodes)} nodes, subtree has {n_sub}"
    new_of_old = {u: k for k, u in enumerate(members)}
    for u, k in new_of_old.items():
        assert s.visits[u] == d.visits[k], f"visits differ at node {u}->{k}"
        assert s.wins[u] == d.wins[k], f"wins differ at node {u}->{k}"
        assert int(s.to_move[u]) == int(d.to_move[k])
        if k != 0:
            assert int(s.move[u]) == int(d.move[k])
            assert new_of_old[int(s.parent[u])] == int(d.parent[k])
        assert int(s.n_children[u]) == int(d.n_children[k])
        assert (s.child_move[u] == d.child_move[k]).all(), \
            f"child moves differ at node {u}->{k}"
        su = {int(new_of_old[int(c)])
              for c in s.children[u][: int(s.n_children[u])]}
        du = set(d.children[k][: int(d.n_children[k])].tolist())
        assert su == du, f"child set differs at node {u}->{k}"
        assert int(sdep[u]) == int(ddep[k]) + 1, "depth must shift by one"
    return n_sub


def node_depths(tree: Tree) -> np.ndarray:
    """Host-side per-node depth (root = 0); unallocated slots report -1.

    Walks parent pointers in allocation order — ``expand_batch`` only ever
    attaches new nodes to existing ones, so ``parent[i] < i`` and a single
    forward pass resolves every depth. Used by the observability tests to
    cross-check the device-plane depth counters (``repro.obsv``) against
    the tree the search actually built.
    """
    parent = np.asarray(tree.parent)[:-1]      # drop the null slot
    n = int(tree.n_nodes)
    depth = np.full(parent.shape, -1, np.int64)
    if n > 0:
        depth[0] = 0
    for i in range(1, n):
        depth[i] = depth[parent[i]] + 1
    return depth


# ------------------------------------------------------------ invariants ----
def check_invariants(tree: Tree, *, discrete_credits: bool = True) -> None:
    """Host-side structural invariant checks (used by the property tests).

    ``discrete_credits=True`` (board-game trees) additionally asserts the
    draw-aware credit structure: backups add 0, 0.5 (draw) or 1 win per
    visit, so accumulated wins are half-integers. Token trees backed up
    with continuous values (``serve.mcts_decode.backup_values``) must pass
    ``discrete_credits=False``; the value-range check applies to both.

    Every check here holds for RE-ROOTED trees (``reroot_tree``) too, by
    design: the root of a warm tree may start with nonzero visits/wins
    (retained evidence), which is fine because no invariant equates root
    visits with the playout count — only the one-sided "children's visits
    never exceed the parent's" bound is asserted, and the retention
    contract carries both sides of that inequality bit-exactly. The
    ``parent[i] < i`` allocation-order assumption (see ``node_depths``) is
    preserved by the BFS renumbering's (depth, old id) sort key.
    """
    import numpy as np

    t = jax.tree.map(np.asarray, tree)
    n = int(t.n_nodes)
    cap = tree.cap
    assert 1 <= n <= cap
    for i in range(1, n):
        p = t.parent[i]
        assert 0 <= p < n, f"node {i}: bad parent {p}"
        assert t.to_move[i] == 3 - t.to_move[p], f"node {i}: to_move not alternating"
        kids = t.children[p][: t.n_children[p]]
        assert i in kids.tolist() or True  # membership checked below globally
    for i in range(n):
        k = int(t.n_children[i])
        kids = t.children[i][:k]
        assert (kids >= 0).all() and (kids < n).all(), f"node {i}: invalid child ids"
        moves = t.move[kids]
        assert len(set(moves.tolist())) == k, f"node {i}: duplicate child moves"
        assert (t.child_move[i][:k] == moves).all(), \
            f"node {i}: child_move row != move[children]"
        assert (t.child_move[i][k:] == NO_NODE).all(), \
            f"node {i}: stale child_move slots"
        assert (t.parent[kids] == i).all(), f"node {i}: child parent mismatch"
        assert (t.children[i][k:] == NO_NODE).all(), f"node {i}: stale child slots"
        # visits of children never exceed the parent's visits
        assert t.visits[kids].sum() <= t.visits[i] + 1e-6
        assert 0.0 <= t.wins[i] <= t.visits[i] + 1e-6
        # draw-aware value range: playout credits are 0, 0.5 (draw) or 1,
        # so accumulated wins are half-integers; 0 <= wins <= visits above
        # already bounds the signed value 2*(w/n) - 1 to [-1, 1] with 0
        # (all-draw) allowed
        if discrete_credits:
            assert abs(2.0 * t.wins[i] - round(2.0 * float(t.wins[i]))) < 1e-4, \
                f"node {i}: wins {t.wins[i]} not a half-integer credit sum"
    assert (t.child_move[n:] == NO_NODE).all(), \
        "child_move rows of unallocated nodes or the pad row are set"
    # every allocated non-root node is some node's child exactly once
    all_kids = []
    for i in range(n):
        all_kids += t.children[i][: int(t.n_children[i])].tolist()
    assert sorted(all_kids) == list(range(1, n)), "child lists != allocated nodes"
