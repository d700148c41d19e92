"""Plain reference of GSC-PM search on Hex, written apart from the program.

It restates the search the configuration files describe, one sync iteration
at a time, in numpy:

- W lanes descend the same tree snapshot level by level. At each level a lane
  scores its node's children with UCT (paper eq. 1) plus its tie-break noise,
  takes the lowest slot among the maxima, and stops at a node that is not
  fully expanded.
- A stopped lane proposes a uniformly random untried legal move.
- The unique (leaf, move) proposals become new nodes in (leaf, move) order.
- Every lane fills the rest of its board with alternating stones in a random
  order and scores the filled board by a flood fill of BLACK's stones from
  the top edge (the Hex theorem: exactly one side connects).
- Each lane's result is added along its path: one visit, and one win to each
  node whose mover (the player who moved into it) won.

The random numbers are the search's stated streams: one ``fold_in`` per task
id, one per iteration, a three-way split per lane (noise, move, playout), one
``fold_in`` per descent depth. They are drawn with ``jax.random`` on the host
CPU; the UCT score is computed in float32 by XLA on the host CPU in the order
paper eq. 1 is written, so that a replay and a search agree bit for bit.

Besides the replay, ``audit_tree`` checks a whole searched tree for what
every GSC-PM tree must satisfy, whatever the random streams: structure,
legal and distinct moves, and that every playout was counted exactly once on
each node of its path (visit and win conservation).
"""

from __future__ import annotations

import copy
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from scipy import ndimage

NO_NODE = -1
BIG = np.float32(1e30)
NEAR_ULPS = 8          # a near tie: two UCT scores this close in float32
MAX_WALKERS = 4        # walkers a lane may hold in one descent
MAX_SPLIT = 12         # lanes with near ties one iteration settles exactly
MAX_BRANCHES = 4       # readings of the search followed at once
# Hex adjacency on the rhombus, as a 3x3 structure over (dr, dc):
# (-1, 0), (-1, +1), (0, -1), (0, +1), (+1, -1), (+1, 0)
_HEX_STRUCTURE = np.array([[0, 1, 1], [1, 1, 1], [1, 1, 0]], bool)


def cpu_device():
    return jax.devices("cpu")[0]


# ----------------------------------------------------------------- rules ----
def hex_winner(filled: np.ndarray, size: int) -> np.ndarray:
    """(B, size*size) filled boards -> (B,) winners: 1 if BLACK joins the
    top and bottom edges, else 2 (WHITE joins left and right)."""
    B = filled.shape[0]
    black = (filled.reshape(B, size, size) == 1)
    structure = np.zeros((3, 3, 3), bool)
    structure[1] = _HEX_STRUCTURE           # no links across boards
    labels, _ = ndimage.label(black, structure=structure)
    top, bottom = labels[:, 0, :], labels[:, -1, :]
    both = np.intersect1d(top[top > 0], bottom[bottom > 0])
    return np.where(np.isin(top, both).any(axis=1), 1, 2).astype(np.int8)


def fill_boards(boards: np.ndarray, to_move: np.ndarray,
                u: np.ndarray) -> np.ndarray:
    """Fill each board's empty cells in increasing (u, cell) order with
    alternating stones, ``to_move`` first."""
    empty = boards == 0
    order = np.argsort(np.where(empty, u, 2.0), axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(boards.shape[1])[None, :], axis=1)
    tm = to_move[:, None].astype(np.int8)
    colour = np.where(rank % 2 == 0, tm, 3 - tm).astype(np.int8)
    return np.where(empty, colour, boards)


# ------------------------------------------------------------ randomness ----
@jax.jit
def _task_keys(key, task_ids):
    return jax.vmap(lambda t: jax.random.fold_in(key, t))(task_ids)


@jax.jit
def _lane_streams(task_keys, i):
    """Per-lane (noise, move, playout) keys of sync iteration ``i``."""
    ks = jax.vmap(lambda tk: jax.random.split(jax.random.fold_in(tk, i), 3)
                  )(task_keys)
    return ks[:, 0], ks[:, 1], ks[:, 2]


@functools.partial(jax.jit, static_argnames=("n",))
def _uniforms(keys, n):
    return jax.vmap(lambda k: jax.random.uniform(k, (n,)))(keys)


@functools.partial(jax.jit, static_argnames=("n",))
def _level_noise(keys, depths, scale, n):
    return scale * jax.vmap(
        lambda k, d: jax.random.uniform(jax.random.fold_in(k, d), (n,))
    )(keys, depths)


@jax.jit
def _uct_pick(wins, visits, parent_visits, valid, noise, cp):
    """Paper eq. 1 in float32, unvisited children first (1e30), invalid
    slots last (-1e30), the lowest slot among equal maxima. Also returns the
    runner-up slot and whether the two finite scores lie within NEAR_ULPS
    units in the last place: a near tie, which a chip's float32 ``log``,
    ``sqrt`` or division may round the other way."""
    x = wins / jnp.maximum(visits, 1.0)
    n_p = jnp.maximum(parent_visits, 1.0)[:, None]
    explore = cp * jnp.sqrt(jnp.log(n_p) / jnp.maximum(visits, 1.0))
    score = x + explore + noise
    score = jnp.where(visits <= 0.0, BIG + noise, score)
    score = jnp.where(valid, score, -BIG)
    slot = jnp.arange(score.shape[1])[None, :]
    C = score.shape[1]

    def top(sc):
        best = sc.max(axis=1, keepdims=True)
        return best[:, 0], jnp.min(jnp.where(sc == best, slot, C), axis=1)

    best, pick = top(score)
    runner, second = top(jnp.where(slot == pick[:, None], -3 * BIG, score))
    # children with equal statistics differ by their noise alone, which
    # every platform adds to the same rounded value: no tie to settle
    at = lambda a, i: jnp.take_along_axis(a, i[:, None], axis=1)[:, 0]  # noqa: E731
    differ = ((at(wins, pick) != at(wins, second))
              | (at(visits, pick) != at(visits, second)))
    near = ((best < 1e29) & (runner > -1e29) & differ
            & (best - runner <= NEAR_ULPS * 2.0**-23 * jnp.abs(best)))
    return pick, second, near


def search_key(key_seed: int, member: int | None = None):
    """The search's key from its integer seed, on the host CPU; an ensemble
    member's key is one more ``fold_in`` of it, by the member's index."""
    with jax.default_device(cpu_device()):
        key = jax.random.key(key_seed)
        return key if member is None else jax.random.fold_in(key, member)


# ------------------------------------------------------------------ tree ----
class RefTree:
    """Growable tree in plain numpy arrays; node 0 is the root."""

    def __init__(self, root_to_move: int, n_children_max: int, cap: int,
                 rows: int):
        self.cap = cap                      # the configuration's capacity
        rows = min(rows, cap) + 1           # + one all-zero pad row
        self.pad = rows - 1
        self.parent = np.full(rows, NO_NODE, np.int64)
        self.move = np.full(rows, NO_NODE, np.int64)
        self.to_move = np.zeros(rows, np.int64)
        self.to_move[0] = root_to_move
        self.children = np.full((rows, n_children_max), NO_NODE, np.int32)
        self.n_children = np.zeros(rows, np.int64)
        self.visits = np.zeros(rows, np.float32)
        self.wins = np.zeros(rows, np.float32)
        self.n_nodes = 1


def schedule(n_playouts: int, n_tasks: int, n_workers: int):
    """The FIFO task schedule: [(m, task_ids, active)] per round."""
    n_tasks = max(1, min(n_tasks, n_playouts))
    m = max(1, n_playouts // n_tasks)
    rounds = []
    for r in range(-(-n_tasks // n_workers)):
        ids = r * n_workers + np.arange(n_workers, dtype=np.int32)
        rounds.append((m, ids, ids < n_tasks))
    return rounds


class Branch:
    """One reading of the search: a tree, and how many near ties it settled
    for the runner-up."""

    def __init__(self, tree: RefTree, cost: int = 0):
        self.tree, self.cost = tree, cost
        self.off = False            # made nodes the guide did not make

    def copy(self) -> "Branch":
        t = copy.copy(self.tree)
        for f in ("parent", "move", "to_move", "children", "n_children",
                  "visits", "wins"):
            setattr(t, f, getattr(self.tree, f).copy())
        return Branch(t, self.cost)


class Replay:
    """Replays a GSC-PM search from an empty tree, one sync iteration at a
    time. ``step`` runs one iteration; ``run`` runs a whole schedule.

    With a ``guide`` (the searched tree), a near tie of the UCT score that
    a chip may round the other way is settled by the guide's next nodes.
    Where two readings make the same nodes and differ only in a lane's path,
    both go on as branches (at most MAX_BRANCHES) until the guide's later
    nodes, or at the end its statistics, tell them apart."""

    def __init__(self, board, to_move: int, *, size: int, n_workers: int,
                 tree_cap: int, cp: float, select_noise: float, key,
                 rows: int, guide: dict | None = None):
        self.size = size
        self.n = size * size
        self.W = n_workers
        self.board = np.asarray(board, np.int8).copy()
        self.cp = np.float32(cp)
        self.noise_scale = np.float32(select_noise)
        self.key = key
        self.branches = [Branch(RefTree(to_move, self.n, tree_cap, rows))]
        self.max_depth = self.n + 1
        self.guide = guide
        self.near_ties = 0          # lanes whose descent met a near tie
        self.unsettled = 0          # iterations no reading of them fitted

    @property
    def tree(self) -> RefTree:
        return self.branches[0].tree

    @property
    def taken_runner_up(self) -> int:
        return self.branches[0].cost

    def step(self, kn, km, kp, active: np.ndarray) -> None:
        """One sync iteration of lanes with streams (kn, km, kp)."""
        out = []
        for br in self.branches:
            out += self._step(br, kn, km, kp, active)
        out.sort(key=lambda b: (b.off, b.cost))   # the float32 reading first
        on = [b for b in out if not b.off]
        self.branches = (on or out)[:MAX_BRANCHES]

    def _step(self, br: Branch, kn, km, kp, active) -> list:
        t, W, n = br.tree, self.W, self.n
        pad = t.pad
        n_before = t.n_nodes
        # walkers descend; each belongs to a lane (owner). A near tie adds a
        # walker that takes the runner-up child (only with a guide)
        owner = np.arange(W)
        nodes = np.zeros(W, np.int64)
        boards = np.tile(self.board, (W, 1))
        depths = np.zeros(W, np.int64)
        n_empty = np.full(W, int((self.board == 0).sum()))
        done = np.zeros(W, bool)
        first = np.ones(W, bool)            # took the float32 choice throughout
        paths = [[0] for _ in range(W)]
        with jax.default_device(cpu_device()):
            while not done.all():
                k = len(owner)
                n_kids = t.n_children[nodes]
                fully = (n_kids == n_empty) & (n_empty > 0)
                slots = t.children[nodes]
                valid = (np.arange(n)[None, :] < n_kids[:, None]) \
                    & ~done[:, None]
                safe = np.where(valid, slots, pad)
                noise = np.asarray(_level_noise(
                    kn[jnp.asarray(owner)], jnp.asarray(depths, jnp.int32),
                    self.noise_scale, n))
                picks, second, near = (np.asarray(a) for a in _uct_pick(
                    t.wins[safe], t.visits[safe], t.visits[nodes], valid,
                    noise, self.cp))
                child = safe[np.arange(k), picks]
                step = fully & (depths < self.max_depth - 2) & ~done
                branch = []
                if self.guide is not None:
                    per_lane = np.bincount(owner, minlength=W)
                    branch = [w for w in np.flatnonzero(step & near)
                              if per_lane[owner[w]] < MAX_WALKERS]
                alt = [int(safe[w, second[w]]) for w in branch]
                for w in np.flatnonzero(step):
                    boards[w, t.move[child[w]]] = t.to_move[nodes[w]]
                    paths[w].append(int(child[w]))
                nodes = np.where(step, child, nodes)
                depths = np.where(step, depths + 1, depths)
                n_empty = np.where(step, n_empty - 1, n_empty)
                done |= ~step
                for w, c in zip(branch, alt):
                    b = boards[w].copy()
                    b[t.move[int(child[w])]] = 0
                    b[t.move[c]] = t.to_move[t.parent[c]]
                    owner = np.append(owner, owner[w])
                    nodes = np.append(nodes, c)
                    boards = np.vstack([boards, b[None]])
                    depths = np.append(depths, depths[w])
                    n_empty = np.append(n_empty, n_empty[w])
                    done = np.append(done, False)
                    first = np.append(first, False)
                    paths.append(paths[w][:-1] + [c])
            u_move = np.asarray(_uniforms(km, n))
            u_fill = np.asarray(_uniforms(kp, n))

        # propose a uniformly random untried legal move at each leaf
        moves = np.full(len(owner), NO_NODE, np.int64)
        for w in range(len(owner)):
            leaf = nodes[w]
            untried = boards[w] == 0
            untried[t.move[t.children[leaf, :t.n_children[leaf]]]] = False
            if untried.any():
                moves[w] = int(np.argmax(np.where(untried, u_move[owner[w]],
                                                  -1.0)))

        # playouts from each walker's leaf with its proposed move placed
        movers = t.to_move[nodes]
        do = moves >= 0
        b2 = boards.copy()
        b2[do, moves[do]] = movers[do]
        nxt = np.where(do, 3 - movers, movers)
        winners = hex_winner(fill_boards(b2, nxt, u_fill[owner]), self.size)

        readings, fits = self._readings(owner, nodes, moves, first, active,
                                        n_before, check=len(self.branches) > 1)
        br.off |= not fits
        out = []
        for i, (cost, pick) in enumerate(readings):
            b = br if i == len(readings) - 1 else br.copy()
            b.cost += cost
            self._commit(b.tree, [nodes[w] for w in pick],
                         [moves[w] for w in pick], [paths[w] for w in pick],
                         [winners[w] for w in pick], active)
            out.append(b)
        return out

    def _commit(self, t: RefTree, nodes, moves, paths, winners, active):
        """Make the unique proposals nodes in (leaf, move) order, then add
        each active lane's playout along its path."""
        W = self.W
        props = sorted({(int(nodes[w]), int(moves[w])) for w in range(W)
                        if active[w] and moves[w] >= 0})
        new_id = {}
        for leaf, mv in props:
            if t.n_nodes >= t.cap:
                break
            v = t.n_nodes
            t.parent[v], t.move[v] = leaf, mv
            t.to_move[v] = 3 - t.to_move[leaf]
            t.children[leaf, t.n_children[leaf]] = v
            t.n_children[leaf] += 1
            t.n_nodes += 1
            new_id[(leaf, mv)] = v
        for w in np.flatnonzero(active):
            path = paths[w]
            v = new_id.get((int(nodes[w]), int(moves[w])))
            if v is not None:
                path = path + [v]
            path = np.asarray(path)
            t.visits[path] += 1.0
            t.wins[path] += (3 - t.to_move[path] == winners[w])

    def _readings(self, owner, nodes, moves, first, active, n_before: int,
                  check: bool = False) -> tuple[list, bool]:
        """([(runner-ups taken, one walker per lane)], whether they fit the
        guide): the float32 choices, unless a near tie gave lanes more than
        one. Then the guide settles it: an iteration's nodes are its unique
        (leaf, move) proposals, numbered from ``n_before`` in (leaf, move)
        order, so a reading must propose exactly the guide's next nodes.
        Readings that do, fewest runner-ups first; the float32 choices
        where none does. ``check`` tests the float32 choices alone too."""
        W = self.W
        plain = [(0, list(range(W)))]
        g = self.guide
        if len(owner) == W and not (check and g is not None):
            return plain, True
        pair = lambda w: (int(nodes[w]), int(moves[w]))  # noqa: E731
        makes = lambda w: bool(active[owner[w]]) and moves[w] >= 0  # noqa: E731
        split = sorted(set(owner[W:].tolist()))
        self.near_ties += len(split)
        base = {pair(w) for w in range(W) if w not in split and makes(w)}
        cands = [np.flatnonzero(owner == lane) for lane in split]
        room = self.tree.cap - n_before     # nodes the capacity still allows
        guide_pairs = [(int(g["parent"][v]), int(g["move"][v]))
                       for v in range(n_before, min(int(g["n_nodes"]),
                                                    n_before + W))]

        def fits_guide(made):
            return sorted(made)[:room] == guide_pairs[:min(len(made), room)] \
                and min(len(made), room) <= len(guide_pairs)

        if len(split) > MAX_SPLIT:
            self.unsettled += 1
            return plain, False
        fits = []
        for combo in itertools.product(*cands):
            made = base | {pair(w) for w in combo if makes(w)}
            if fits_guide(made):
                pick = list(range(W))
                for lane, w in zip(split, combo):
                    pick[lane] = int(w)
                fits.append((sum(not first[w] for w in combo), pick))
        if not fits:
            self.unsettled += 1
            return plain, False
        fits.sort(key=lambda f: f[0])
        return fits[:MAX_BRANCHES], True

    def finish(self, tree: dict | None = None) -> None:
        """Put first the branch whose statistics equal ``tree``'s, if any."""
        if tree is None:
            return
        for i, b in enumerate(self.branches):
            if tree_mismatch(b.tree, tree) == 0:
                self.branches.insert(0, self.branches.pop(i))
                return

    def run_round(self, m: int, task_ids, active, iterations=None) -> None:
        with jax.default_device(cpu_device()):
            tk = _task_keys(self.key, jnp.asarray(task_ids, jnp.int32))
        for i in range(m if iterations is None else min(m, iterations)):
            with jax.default_device(cpu_device()):
                kn, km, kp = _lane_streams(tk, i)
            self.step(kn, km, kp, np.asarray(active))

    def run(self, n_playouts: int, n_tasks: int) -> None:
        for m, ids, active in schedule(n_playouts, n_tasks, self.W):
            self.run_round(m, ids, active)

    # the answer a search gives: dense root statistics and the move
    def root_answer(self) -> dict:
        t = self.tree
        kids = t.children[0, :t.n_children[0]]
        visits = np.zeros(self.n, np.float32)
        wins = np.zeros(self.n, np.float32)
        visits[t.move[kids]] = t.visits[kids]
        wins[t.move[kids]] = t.wins[kids]
        best = int(t.move[kids[np.argmax(t.visits[kids])]]) if len(kids) \
            else NO_NODE
        return {"root_visits": visits, "root_wins": wins, "best_move": best,
                "tree_nodes": t.n_nodes}


def make_replay(cfg: dict, board, to_move: int, key_seed: int,
                iterations: int, member: int | None = None,
                guide: dict | None = None) -> Replay:
    """A replay of ``cfg``'s search with the key ``key_seed`` (of ensemble
    member ``member``), sized for ``iterations`` sync iterations. ``guide``
    is the searched tree (``parent``, ``move``, ``n_nodes``): it settles
    near ties of the UCT score, and nothing else."""
    return Replay(board, to_move, size=cfg["board_size"],
                  n_workers=cfg["n_workers"], tree_cap=cfg["tree_cap"],
                  cp=cfg["cp"], select_noise=cfg["select_noise"],
                  key=search_key(key_seed, member),
                  rows=1 + cfg["n_workers"] * iterations, guide=guide)


def replay_prefix(cfg: dict, board, to_move: int, key_seed: int,
                  iterations: int, member: int | None = None,
                  rounds: int | None = None, guide: dict | None = None
                  ) -> Replay:
    """The replay after the first ``iterations`` sync iterations of
    ``cfg``'s schedule, within its first ``rounds`` rounds (all by
    default)."""
    rep = make_replay(cfg, board, to_move, key_seed, iterations, member,
                      guide)
    plan = schedule(cfg["n_playouts"], cfg["n_tasks"], cfg["n_workers"])
    left = iterations
    for m, ids, active in plan[:rounds]:
        if left <= 0:
            break
        rep.run_round(m, ids, active, left)
        left -= m
    return rep


def replay_mismatch(rep: Replay, tree: dict) -> int:
    """The fewest prefix mismatches any followed branch of ``rep`` has
    against the searched ``tree``."""
    return min(prefix_mismatch(b.tree, tree["parent"], tree["move"],
                               tree["children"], tree["n_children"],
                               int(tree["n_nodes"])) for b in rep.branches)


def prefix_mismatch(ref: RefTree, parent, move, children, n_children,
                    n_nodes: int) -> int:
    """Nodes of the replayed prefix whose parent, move or child slots differ
    in the searched tree (nodes never change once made, and child slots
    only grow at their end)."""
    k = ref.n_nodes
    if n_nodes < k:
        return k
    bad = (parent[1:k] != ref.parent[1:k]) | (move[1:k] != ref.move[1:k])
    count = int(bad.sum())
    nc = ref.n_children[:k]
    if (n_children[:k] < nc).any():
        count += int((n_children[:k] < nc).sum())
    cols = np.arange(ref.children.shape[1])[None, :]
    mask = cols < nc[:, None]
    diff = (children[:k] != ref.children[:k]) & mask
    count += int(diff.any(axis=1).sum())
    return count


def tree_mismatch(ref: RefTree, tree: dict) -> int:
    """Nodes whose parent, move, visits or wins differ between a whole
    replay and the searched tree (every node counts if the sizes differ)."""
    k = ref.n_nodes
    if int(tree["n_nodes"]) != k:
        return max(k, int(tree["n_nodes"]))
    bad = ((tree["parent"][:k] != ref.parent[:k])
           | (tree["move"][:k] != ref.move[:k])
           | (tree["visits"][:k] != ref.visits[:k])
           | (tree["wins"][:k] != ref.wins[:k]))
    return int(bad.sum())


# ----------------------------------------------------------------- audit ----
def audit_tree(tree: dict, root_board, root_to_move: int, playouts: int,
               *, injected_root: bool = False) -> dict:
    """Violations of what any GSC-PM tree must satisfy, counted by kind.

    ``tree`` holds numpy copies of the searched tree's arrays. With
    ``injected_root`` (a root-parallel member after root synchronisation)
    the root and its children hold other members' statistics too, so
    conservation is checked below them and only bounded at them.
    """
    N = int(tree["n_nodes"])
    cap = tree["parent"].shape[0] - 1
    out = {"structure": 0, "moves": 0, "visits": 0, "wins": 0, "root": 0}
    if not 1 <= N <= cap:
        out["structure"] += 1
        return out
    parent = tree["parent"][:N].astype(np.int64)
    move = tree["move"][:N].astype(np.int64)
    to_move = tree["to_move"][:N].astype(np.int64)
    n_children = tree["n_children"][:N].astype(np.int64)
    children = tree["children"][:N].astype(np.int64)
    visits = tree["visits"][:N].astype(np.float64)
    wins = tree["wins"][:N].astype(np.float64)
    n_cells = children.shape[1]
    ids = np.arange(N)

    # structure: parents made before children, sides alternate, child slots
    # list exactly the nodes that name the parent, unused rows are empty
    s = 0
    s += int(to_move[0] != root_to_move)
    s += int(((parent[1:] < 0) | (parent[1:] >= ids[1:])).sum())
    ok_par = np.clip(parent, 0, N - 1)
    s += int((to_move[1:] != 3 - to_move[ok_par[1:]]).sum())
    cols = np.arange(n_cells)[None, :]
    used = cols < n_children[:, None]
    s += int((children[used] < 1).sum() + (children[used] >= N).sum())
    s += int((children[~used] != NO_NODE).sum())
    listed = np.where(used, children, 0)
    s += int((parent[np.clip(listed[used], 0, N - 1)]
              != np.nonzero(used)[0]).sum())
    s += int((np.bincount(ok_par[1:], minlength=N) != n_children).sum())
    rest = slice(N, cap)
    s += int((tree["visits"][rest] != 0).sum() + (tree["wins"][rest] != 0).sum())
    out["structure"] = s

    # moves: on the board, not on a stone of the root position, not played
    # by an ancestor, and distinct among siblings
    mv = 0
    mv += int(((move[1:] < 0) | (move[1:] >= n_cells)).sum())
    safe_mv = np.clip(move, 0, n_cells - 1)
    root_board = np.asarray(root_board)
    mv += int((root_board[safe_mv[1:]] != 0).sum())
    anc = ok_par.copy()
    for _ in range(n_cells + 1):
        live = anc > 0
        if not live[1:].any():
            break
        mv += int(((move == move[anc]) & live)[1:].sum())
        anc = np.where(live, ok_par[anc], 0)
    pair = ok_par[1:] * n_cells + safe_mv[1:]
    mv += len(pair) - len(np.unique(pair))
    out["moves"] = mv

    # conservation: a node's own playouts (those that ended there) are its
    # visits less its children's; each is a win for its mover or not
    kid_visits = np.bincount(ok_par[1:], weights=visits[1:], minlength=N)
    kid_losses = np.bincount(ok_par[1:], weights=(visits - wins)[1:],
                             minlength=N)
    own = visits - kid_visits
    own_wins = wins - kid_losses
    integral = (visits == np.round(visits)) & (wins == np.round(wins))
    need = np.where(ids > 0, 1, 0)          # the playout that made the node
    out["visits"] = int((~integral).sum() + (own < need).sum())
    bounded = (own_wins >= 0) & (own_wins <= own)
    if injected_root:
        # injected root wins balance the children's only where the member
        # holds every move another member holds; the root is bounded below
        bounded[0] = True
    out["wins"] = int((~bounded).sum() + ((wins < 0) | (wins > visits)).sum())
    out["root"] = int(visits[0] != playouts)
    return out


def best_child_move(tree: dict) -> int:
    """The most visited root child's move, the lowest slot among ties."""
    kids = tree["children"][0, :int(tree["n_children"][0])]
    if len(kids) == 0:
        return NO_NODE
    return int(tree["move"][kids[np.argmax(tree["visits"][kids])]])


def dense_root(tree: dict, n_moves: int):
    """Per-move (visits, wins) of the root's children."""
    kids = tree["children"][0, :int(tree["n_children"][0])]
    v = np.zeros(n_moves, np.float64)
    w = np.zeros(n_moves, np.float64)
    v[tree["move"][kids]] = tree["visits"][kids]
    w[tree["move"][kids]] = tree["wins"][kids]
    return v, w, np.isin(np.arange(n_moves), tree["move"][kids])
