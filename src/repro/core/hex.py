"""Pure-JAX Hex game environment.

The paper's benchmark application is a from-scratch 11x11 Hex engine. Board
cells are indexed row-major. Player 1 (BLACK) connects the TOP edge to the
BOTTOM edge; player 2 (WHITE) connects LEFT to RIGHT. A *move* is the flat
index of an empty cell.

Hardware adaptation (DESIGN.md §2/§9/§12): the paper uses a disjoint-set
(union-find) structure for connectivity. Union-find is pointer-chasing and
hostile to vector hardware, so we use vectorizable equivalents:

- a frontier flood-fill to a fixpoint (`lax.while_loop` over neighbor
  dilation) — the scalar oracle (`connected`/`winner`), O(board diameter)
  steps, tested against a python union-find oracle in tests/test_hex.py;
  its batched gather-free twin (`winner_flood_batch`) is the off-TPU
  winner dispatch, and the `kernels/hex_winner.py` Pallas kernel runs the
  same six-shift dilation on TPU for a fixed, proven n_cells - 1 steps;
- **batched pointer-doubling** connected-component labeling
  (`cc_labels_batch` / `connected_batch`) — the Shiloach–Vishkin/FastSV
  hook-and-jump scheme over a whole (W, n_cells) tile at once, converging
  in O(log n_cells) rounds with ONE convergence loop for all W lanes. The
  TPU kernel used it until a chip measurement (1,991 us per 244-board
  11x11 call on a v5e); it stays as an independent oracle, bit-exact vs
  the flood fill (tests/test_hex_batch.py, tests/test_kernels.py).

`winner_batch`/`playout_batch` pick the right body per backend through
``kernels.ops.hex_winner`` (DESIGN.md §12).

The playout exploits the Hex theorem: a completely filled board has exactly
one winner, so a playout = randomly fill all empty cells with alternating
stones, then run ONE connectivity check for BLACK (if BLACK is not connected,
WHITE is). This mirrors the paper's "highly optimized" engine, which also
evaluates terminal positions only. ``playout_batch`` fuses
place→fill→winner for W lanes: one sort-free fill pass + one connectivity
solve per sync iteration instead of W interleaved while-loops.

Everything is fixed-shape and `vmap`/`jit` friendly.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game as game_mod

EMPTY = jnp.int8(0)
BLACK = jnp.int8(1)  # connects top <-> bottom
WHITE = jnp.int8(2)  # connects left <-> right


class HexSpec(NamedTuple):
    """Static board description (python ints; safe to close over in jit)."""

    size: int

    @property
    def n_cells(self) -> int:
        return self.size * self.size


def neighbor_table(size: int) -> np.ndarray:
    """(n_cells, 6) int32 neighbor indices; `n_cells` acts as a pad sentinel.

    Hex adjacency on a rhombus: (r-1,c), (r-1,c+1), (r,c-1), (r,c+1),
    (r+1,c-1), (r+1,c).
    """
    n = size * size
    tbl = np.full((n, 6), n, dtype=np.int32)
    deltas = [(-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0)]
    for r in range(size):
        for c in range(size):
            i = r * size + c
            for k, (dr, dc) in enumerate(deltas):
                rr, cc = r + dr, c + dc
                if 0 <= rr < size and 0 <= cc < size:
                    tbl[i, k] = rr * size + cc
    return tbl


@functools.lru_cache(maxsize=None)
def _static_tables(size: int):
    """Neighbor table + edge masks as numpy constants (cached per size)."""
    n = size * size
    nbr = neighbor_table(size)
    top = np.zeros(n, dtype=bool)
    top[:size] = True
    bottom = np.zeros(n, dtype=bool)
    bottom[n - size :] = True
    left = np.zeros(n, dtype=bool)
    left[::size] = True
    right = np.zeros(n, dtype=bool)
    right[size - 1 :: size] = True
    return nbr, top, bottom, left, right


# the six hex neighbors as (row, col) offsets on the rhombus board
_DELTAS = ((-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0))


@functools.lru_cache(maxsize=None)
def _shift_tables(size: int):
    """Neighborhood as six STATIC flat shifts + per-cell validity masks.

    The gather-free formulation of hex adjacency: the neighbor of cell i in
    direction (dr, dc) sits at flat offset dr*size + dc, so a whole
    (W, n_cells) tile reads it with one roll — the same trick the Pallas
    kernel uses (`kernels/hex_winner.py`), which keeps the batched hot
    paths free of (W, n, 6) gathers.
    """
    n = size * size
    offs, masks = [], []
    for dr, dc in _DELTAS:
        m = np.zeros(n, dtype=bool)
        for r in range(size):
            cc_lo, cc_hi = max(0, -dc), min(size, size - dc)
            if 0 <= r + dr < size:
                m[r * size + cc_lo : r * size + cc_hi] = True
        offs.append(dr * size + dc)
        masks.append(m)
    return tuple(offs), np.stack(masks)


def empty_board(spec: HexSpec) -> jnp.ndarray:
    return jnp.zeros(spec.n_cells, dtype=jnp.int8)


place = game_mod.place    # one-hot stone placement shared by every game


def legal_mask(board: jnp.ndarray) -> jnp.ndarray:
    return board == EMPTY


def connected(board: jnp.ndarray, player: jnp.ndarray, spec: HexSpec) -> jnp.ndarray:
    """True iff `player` has a chain between their two edges.

    Frontier flood-fill to a fixpoint. The padded board (extra sentinel cell)
    keeps every gather in-bounds without branching.
    """
    nbr, top, bottom, left, right = _static_tables(spec.size)
    nbr = jnp.asarray(nbr)
    player = player.astype(jnp.int8)
    mine = board == player
    start = jnp.where(player == BLACK, jnp.asarray(top), jnp.asarray(left))
    goal = jnp.where(player == BLACK, jnp.asarray(bottom), jnp.asarray(right))

    reach0 = mine & start

    def body(state):
        reach, _ = state
        padded = jnp.concatenate([reach, jnp.zeros((1,), dtype=bool)])
        # cell joins the reach-set if any neighbor is reached and it is ours
        nbr_reached = padded[nbr].any(axis=1)
        new = reach | (nbr_reached & mine)
        return new, (new != reach).any()

    def cond(state):
        _, changed = state
        return changed

    reach, _ = jax.lax.while_loop(cond, body, (reach0, reach0.any()))
    return (reach & goal).any()


def winner(board: jnp.ndarray, spec: HexSpec) -> jnp.ndarray:
    """Winner of a FILLED board (Hex theorem: exactly one exists).

    One flood-fill: if BLACK is not connected, WHITE is. Returns int8 in
    {1, 2}.

    CONTRACT: the board must be completely filled. On a partially filled
    board this silently returns the BLACK connectivity result (1 if black
    is connected else 2) — which is NOT "who is winning"; WHITE may simply
    not have finished a chain yet. Callers that cannot prove the board is
    filled must use `connected` (the general check) or `winner_checked`
    (this function plus a debug assertion). The in-repo filled-board call
    sites (the playout phase) route through the fast batched path
    (`winner_batch` / `playout_batch`).
    """
    black_wins = connected(board, BLACK, spec)
    return jnp.where(black_wins, BLACK, WHITE)


def winner_checked(board: jnp.ndarray, spec: HexSpec) -> jnp.ndarray:
    """`winner` with a guard asserting the filled-board contract.

    Eager calls assert immediately; traced calls assert at runtime via a
    debug callback (so the check survives `jit`, at callback cost — use it
    at boundaries/debugging, not inside the search hot loop).
    """
    filled = (board != EMPTY).all()
    msg = ("winner_checked: board is not completely filled — winner() is "
           "only defined on terminal boards (use `connected` instead)")
    if isinstance(filled, jax.core.Tracer):
        def _assert_filled(ok):
            if not bool(ok):
                raise AssertionError(msg)
        jax.debug.callback(_assert_filled, filled)
    else:
        assert bool(filled), msg
    return winner(board, spec)


# ------------------------------------------------- batched (W, cells) ops ----
def doubling_rounds(n_cells: int) -> int:
    """Fixed pointer-doubling round budget: ceil(log2(n_cells)) + 2.

    The hook-and-jump round below (scatter-min hooking + pointer jump)
    converges well inside this bound — empirically <= 7 rounds on random
    AND adversarial snake/comb/solid boards up to 25x25, against caps of
    9-12 (tests/test_hex_batch.py pins convergence at exactly this budget,
    adversarial shapes included). It is an observed bound, so only the
    fixed-round variant of ``cc_labels_batch`` (``rounds=``) uses it, in
    those tests; the default path early-exits at the batch fixpoint.
    """
    return int(math.ceil(math.log2(max(2, n_cells)))) + 2


def cc_labels_batch(stones: jnp.ndarray, spec: HexSpec,
                    rounds: int | None = None) -> jnp.ndarray:
    """Min-index connected-component labels by pointer doubling.

    stones: (W, n_cells) bool — per-lane membership mask (one player's
    stones). Returns (W, n_cells) int32 labels: cells of one connected
    component share the component's minimum cell index; non-member cells
    keep their own index.

    This is the PRAM pointer-jumping (Shiloach–Vishkin / FastSV) scheme the
    paper's §VPU discussion points at, batched over all W lanes. Each round:

      1. hook (gather):   m[i]    = min over same-stone closed nbhd of P
      2. hook (scatter):  P[P[i]] = min(P[P[i]], m[i])   — roots adopt the
                          best label their subtree has seen (the step that
                          makes convergence O(log n) instead of O(diameter))
      3. jump:            P[i]    = P[P[i]]              — pointer doubling

    Labels are monotone non-increasing ints, so the fixpoint exists and is
    the exact component-min labeling (hook fixpoint => locally constant =>
    min per component). ``rounds=None`` runs ONE `lax.while_loop` to the
    fixpoint of the whole batch (early exit, typical 4-6 rounds);
    ``rounds=k`` runs a fixed `fori_loop` (the variant the fixed-round
    tests exercise).
    """
    nbr, *_ = _static_tables(spec.size)
    nbr = jnp.asarray(nbr)                     # (n, 6), sentinel == n
    W, n = stones.shape
    widx = jnp.arange(W, dtype=jnp.int32)[:, None]
    P0 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (W, n))

    # same-stone adjacency, fixed across rounds: (W, n, 6)
    stones_pad = jnp.concatenate(
        [stones, jnp.zeros((W, 1), dtype=bool)], axis=1)
    ok = stones_pad[:, nbr] & stones[:, :, None]

    def one_round(P):
        P_pad = jnp.concatenate(
            [P, jnp.full((W, 1), n, dtype=jnp.int32)], axis=1)
        nbr_lbl = jnp.where(ok, P_pad[:, nbr], n)            # (W, n, 6)
        m = jnp.minimum(P, nbr_lbl.min(axis=2))              # gather hook
        Q = P.at[widx, P].min(m)                             # scatter hook
        Q = jnp.minimum(Q, m)
        return jnp.take_along_axis(Q, Q, axis=1)             # pointer jump

    if rounds is None:
        def cond(st):
            return st[1]

        def body(st):
            P, _ = st
            Q = one_round(P)
            return Q, (Q != P).any()

        P, _ = jax.lax.while_loop(cond, body, (P0, jnp.bool_(True)))
        return P
    return jax.lax.fori_loop(0, rounds, lambda _, P: one_round(P), P0)


def connected_batch(boards: jnp.ndarray, player, spec: HexSpec) -> jnp.ndarray:
    """Batched `connected`: (W, n_cells) boards -> (W,) bool.

    ``player`` is a scalar or (W,) array. Exactly equal to
    ``jax.vmap(connected)`` (tests/test_hex_batch.py), but evaluates the
    whole batch with one O(log n) pointer-doubling solve instead of W
    coupled O(diameter) flood-fills.
    """
    _, top, bottom, left, right = _static_tables(spec.size)
    W, n = boards.shape
    player = jnp.broadcast_to(jnp.asarray(player, jnp.int8), (W,))
    stones = boards == player[:, None]
    labels = cc_labels_batch(stones, spec)
    is_black = (player == BLACK)[:, None]
    start = jnp.where(is_black, jnp.asarray(top)[None], jnp.asarray(left)[None])
    goal = jnp.where(is_black, jnp.asarray(bottom)[None],
                     jnp.asarray(right)[None])
    widx = jnp.arange(W, dtype=jnp.int32)[:, None]
    # mark the component roots touching the start edge, then test the goal
    src = stones & start
    mark = jnp.zeros((W, n + 1), dtype=bool).at[
        widx, jnp.where(src, labels, n)].set(True)[:, :n]
    reached = stones & goal & jnp.take_along_axis(mark, labels, axis=1)
    return reached.any(axis=1)


def winner_flood_batch(boards: jnp.ndarray, spec: HexSpec) -> jnp.ndarray:
    """Batched `winner` by gather-free frontier flood fill.

    Same filled-board contract as `winner`. One reach set for all W lanes,
    dilated with the six static shifts of ``_shift_tables`` per step and
    ONE convergence check for the whole batch — O(board diameter) steps of
    very cheap boolean work. ``kernels.ops.hex_winner`` dispatches HERE
    off-TPU; on TPU the Pallas kernel (`kernels/hex_winner.py`) runs the
    same dilation for a fixed n_cells - 1 steps, with no convergence
    check. Both beat the O(log n) pointer-doubling solve: its rounds cost
    gathers or one-hot tiles, where a dilation step is a few shifted ANDs
    (DESIGN.md §12).
    """
    offs, masks = _shift_tables(spec.size)
    _, top, bottom, *_ = _static_tables(spec.size)
    masks = jnp.asarray(masks)
    mine = boards == BLACK
    reach0 = mine & jnp.asarray(top)[None, :]

    def body(st):
        reach, _ = st
        acc = reach
        for off, mk in zip(offs, masks):
            acc = acc | (jnp.roll(reach, -off, axis=1) & mk[None, :])
        new = acc & mine
        return new, (new != reach).any()

    reach, _ = jax.lax.while_loop(lambda st: st[1], body, (reach0, reach0.any()))
    black_wins = (reach & jnp.asarray(bottom)[None, :]).any(axis=1)
    return jnp.where(black_wins, BLACK, WHITE)


def winner_batch(boards: jnp.ndarray, spec: HexSpec) -> jnp.ndarray:
    """Batched `winner`: (W, n_cells) FILLED boards -> (W,) int8 in {1, 2}.

    Same contract as `winner` (boards must be filled). Dispatches through
    ``kernels.ops.hex_winner`` — the compiled Pallas flood-fill kernel on
    TPU, the jitted batched flood fill elsewhere (DESIGN.md §12).
    """
    from repro.kernels import ops  # function-level: kernels ref imports hex

    return ops.hex_winner(boards, spec.size)


def random_fill_batch(boards: jnp.ndarray, to_move, keys: jax.Array,
                      spec: HexSpec) -> jnp.ndarray:
    """Batched `random_fill`: fill W boards' empties in one fused pass.

    ``keys`` is a (W,) key batch; lane w consumes exactly the stream the
    scalar ``random_fill`` would with ``keys[w]`` (one uniform draw per
    cell), so this is bit-identical to ``jax.vmap(random_fill)``.

    The stone a cell receives depends only on the PARITY of its rank among
    the empty cells (random order), so instead of materializing the order
    with an argsort (XLA sorts are the slow path on every backend) the rank
    is counted directly: rank[i] = #{empty j : (noise_j, j) < (noise_i, i)}
    — one (W, n, n) boolean compare-and-count, with the same
    index-tie-break a stable argsort would apply. Bit-identical to the
    argsort formulation (ties included) and sort-free. The rank/color core
    is shared with every other registered game
    (``game.empty_fill_ranks`` / ``game.parity_fill_colors``).
    """
    empties = boards == EMPTY
    rank = game_mod.empty_fill_ranks(boards, keys)
    fill_color = game_mod.parity_fill_colors(rank, to_move)
    return jnp.where(empties, fill_color, boards)


def playout_batch(boards: jnp.ndarray, to_move, keys: jax.Array,
                  spec: HexSpec) -> jnp.ndarray:
    """W random playouts fused into one (W, cells) evaluation stage.

    fill (one sort-free parity pass) + winner (one batched connectivity
    solve via the per-backend ``ops.hex_winner`` dispatch). Bit-identical
    winners to ``jax.vmap(playout)`` under the same keys.
    """
    filled = random_fill_batch(boards, to_move, keys, spec)
    return winner_batch(filled, spec)


def random_fill(
    board: jnp.ndarray, to_move: jnp.ndarray, key: jax.Array, spec: HexSpec
) -> jnp.ndarray:
    """Fill every empty cell with alternating stones in a random order.

    Equivalent to playing uniformly-random legal moves to the end of the game
    (the paper's playout policy): assign a random rank to each empty cell; the
    cell with the k-th smallest rank receives the stone of the player who is
    k-th to move. The width-1 case of ``random_fill_batch`` (same noise
    stream, bit-identical board).
    """
    return random_fill_batch(board[None], to_move, key[None], spec)[0]


def playout(
    board: jnp.ndarray, to_move: jnp.ndarray, key: jax.Array, spec: HexSpec
) -> jnp.ndarray:
    """Run one random playout; return the winning player (int8 1|2).

    The width-1 case of ``playout_batch`` (same fill stream, same winner
    dispatch). The genuinely-scalar formulation — per-lane flood-fill
    winner — survives as ``HexGame.playout_scalar``, the oracle the
    bit-identity tests and the ``playout="scalar"`` search config use.
    """
    return playout_batch(board[None], to_move, key[None], spec)[0]


def playout_value(
    board: jnp.ndarray,
    to_move: jnp.ndarray,
    perspective: jnp.ndarray,
    key: jax.Array,
    spec: HexSpec,
) -> jnp.ndarray:
    """Playout result as 1.0 if `perspective` wins else 0.0 (width-1 over
    the batched path; Hex never draws, so the value is always 0 or 1)."""
    w = playout(board, to_move, key, spec)
    return (w == perspective.astype(jnp.int8)).astype(jnp.float32)


def replay_moves(
    moves: jnp.ndarray, n_moves: jnp.ndarray, first_player: jnp.ndarray, spec: HexSpec
) -> jnp.ndarray:
    """Reconstruct a board from a move list — the shared masked-scatter
    (``game.replay_moves``) at Hex's board length; see its contract."""
    return game_mod.replay_moves(moves, n_moves, first_player, spec.n_cells)


# ------------------------------------------------------- the Game protocol ----
class HexGame(NamedTuple):
    """Hex through the batched ``Game`` protocol (``core/game.py``).

    Every method delegates to the module functions above, so a search routed
    through the seam runs the exact computation (and RNG schedule) the
    pre-seam Hex-coupled search ran — bit-identical trees, pinned by
    tests/test_game_protocol.py. Hex never draws (Hex theorem), a game ends
    only when the board fills, and ``winner_batch`` keeps the per-backend
    dispatch of ``kernels.ops.hex_winner`` (DESIGN.md §12).
    """

    size: int

    @property
    def n_cells(self) -> int:
        return self.size * self.size

    @property
    def n_actions(self) -> int:
        return self.n_cells  # a move is an empty cell

    @property
    def max_moves(self) -> int:
        return self.n_cells  # games end exactly when the board fills

    def init_board(self) -> jnp.ndarray:
        return empty_board(self)

    def place(self, board, move, player) -> jnp.ndarray:
        return place(board, move, player)

    def legal_mask(self, board) -> jnp.ndarray:
        return legal_mask(board)

    def terminal_batch(self, boards) -> jnp.ndarray:
        return ~(boards == EMPTY).any(axis=-1)

    def winner_batch(self, boards) -> jnp.ndarray:
        return winner_batch(boards, self)

    def playout_batch(self, boards, to_move, keys) -> jnp.ndarray:
        return playout_batch(boards, to_move, keys, self)

    def playout_scalar(self, board, to_move, key) -> jnp.ndarray:
        # the per-lane oracle: batched fill stream at width 1, but the
        # WINNER via the scalar O(diameter) flood fill — an independent
        # connectivity formulation to hold the fused path against
        filled = random_fill(board, to_move, key, self)
        return winner(filled, self)

    def replay_moves(self, moves, n_moves, first_player) -> jnp.ndarray:
        return replay_moves(moves, n_moves, first_player, self)

    def winner_probe(self, board) -> jnp.ndarray:
        # PARTIAL boards welcome: ``connected_batch`` only needs a chain to
        # exist, not a full board (unlike ``winner``'s full-board
        # contract). Hex never draws, so the outcomes are -1|1|2.
        c1 = connected_batch(board[None], BLACK, self)[0]
        c2 = connected_batch(board[None], WHITE, self)[0]
        return jnp.where(c1, jnp.int8(1),
                         jnp.where(c2, jnp.int8(2), jnp.int8(-1)))


game_mod.register_game("hex", HexGame)
