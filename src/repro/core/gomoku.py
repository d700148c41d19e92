"""Pure-JAX free-style Gomoku (five-in-a-row) — the second `Game` workload.

Board cells are indexed row-major on an n x n square; a *move* is the flat
index of an empty cell; a player wins by owning five (or more — free-style)
consecutive cells along a row, column, or either diagonal, and a full board
with no five is a DRAW — the protocol's first non-win outcome, exercising
the draw path through backup (credit 0.5), UCT, and root merging.

Everything a search consumes is batched over a (W, n_cells) tile with NO
per-lane loops (DESIGN.md §13):

- the win test is four directional 5-window scans built from STATIC flat
  ``roll`` shifts + per-cell window-validity masks (the same gather-free
  trick as Hex's ``_shift_tables``): window(i, dir) is monochrome iff the
  AND of 5 shifted stone masks holds at i;
- the fused ``playout_batch`` never steps move-by-move. It draws the same
  parity fill as Hex (``game.empty_fill_ranks``: rank k among the empties
  = the k-th playout move) and resolves the outcome by COMPLETION TIME:
  a window monochrome in the fully-filled board was completed exactly when
  its last cell was placed (stones are never removed), so its completion
  time is the max fill rank over its 5 cells (pre-existing stones count as
  rank -1). The playout's winner is the color of the window with minimal
  completion time — the truncated random game and the full fill agree on
  every completed window, so this is bit-identical to playing the fill
  order move-by-move and stopping at the first five
  (``playout_scalar`` below IS that sequential oracle, same RNG stream;
  pinned in tests/test_game_protocol.py). No five anywhere -> draw (0).

Two windows of different colors cannot complete at the same time (a window
completes on its own color's placement), so the min-time comparison needs no
tie-break; on illegal boards where BOTH colors already contain a five
(unreachable through the search: ``legal_mask`` is empty at won positions)
the evaluation returns a draw.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game as game_mod

EMPTY = jnp.int8(0)
BLACK = jnp.int8(1)
WHITE = jnp.int8(2)

WIN_RUN = 5  # free-style five-in-a-row

# the four scan directions as (row, col) steps: E, S, SE, SW
_DIRS = ((0, 1), (1, 0), (1, 1), (1, -1))


class GomokuSpec(NamedTuple):
    """Static board description (python ints; safe to close over in jit)."""

    size: int

    @property
    def n_cells(self) -> int:
        return self.size * self.size


@functools.lru_cache(maxsize=None)
def _window_tables(size: int):
    """Per direction: flat shift offset + bool mask of valid window starts.

    Cell i starts a 5-window in direction (dr, dc) iff all of
    i, i+off, ..., i+4*off stay on the board along that line; ``roll``
    wrap-around artifacts land only on masked-out starts.
    """
    n = size * size
    offs, masks = [], []
    for dr, dc in _DIRS:
        m = np.zeros(n, dtype=bool)
        for r in range(size):
            for c in range(size):
                rr, cc = r + (WIN_RUN - 1) * dr, c + (WIN_RUN - 1) * dc
                if 0 <= rr < size and 0 <= cc < size:
                    m[r * size + c] = True
        offs.append(dr * size + dc)
        masks.append(m)
    return tuple(offs), np.stack(masks)


def empty_board(spec: GomokuSpec) -> jnp.ndarray:
    return jnp.zeros(spec.n_cells, dtype=jnp.int8)


place = game_mod.place    # one-hot stone placement shared by every game


# ------------------------------------------------- batched (W, cells) ops ----
def five_windows_batch(stones: jnp.ndarray, spec: GomokuSpec) -> jnp.ndarray:
    """(W, n) bool -> (W, 4, n): window at start i (dir d) is all-stones.

    Four directional run scans, each the AND of five statically-shifted
    copies of the stone mask — no gathers, no per-lane loops.
    """
    offs, masks = _window_tables(spec.size)
    outs = []
    for off, mk in zip(offs, jnp.asarray(masks)):
        acc = stones
        for k in range(1, WIN_RUN):
            acc = acc & jnp.roll(stones, -k * off, axis=1)
        outs.append(acc & mk[None, :])
    return jnp.stack(outs, axis=1)


def has_five_batch(boards: jnp.ndarray, player, spec: GomokuSpec) -> jnp.ndarray:
    """(W, n) boards -> (W,) bool: does `player` own a completed five?"""
    W = boards.shape[0]
    player = jnp.broadcast_to(jnp.asarray(player, jnp.int8), (W,))
    stones = boards == player[:, None]
    return five_windows_batch(stones, spec).any(axis=(1, 2))


def terminal_batch(boards: jnp.ndarray, spec: GomokuSpec) -> jnp.ndarray:
    """(W, n) -> (W,) bool: a five exists, or the board is full (draw)."""
    full = ~(boards == EMPTY).any(axis=1)
    return (full | has_five_batch(boards, BLACK, spec)
            | has_five_batch(boards, WHITE, spec))


def winner_scan_batch(boards: jnp.ndarray, spec: GomokuSpec) -> jnp.ndarray:
    """Winner of TERMINAL boards: {1, 2} for a five, 0 for a full-board draw.

    CONTRACT: boards must be terminal (the search only evaluates positions
    the game has ended on); on a non-terminal board this returns 0, which is
    NOT "drawn" but "no five yet". Reached through the per-game eval
    dispatch ``kernels.ops.gomoku_winner``.
    """
    fb = has_five_batch(boards, BLACK, spec)
    fw = has_five_batch(boards, WHITE, spec)
    return jnp.where(fb, BLACK, jnp.where(fw, WHITE, EMPTY)).astype(jnp.int8)


def first_completion_winner(filled: jnp.ndarray, times: jnp.ndarray,
                            spec: GomokuSpec) -> jnp.ndarray:
    """Outcome of a random fill by completion time (module docstring).

    filled: (W, n) int8 fully-filled boards; times: (W, n) int32 fill rank
    per cell, -1 for stones predating the playout. Returns (W,) int8 in
    {0 draw, 1, 2}.
    """
    n = spec.n_cells
    big = jnp.int32(n)  # > any completion time
    offs, _ = _window_tables(spec.size)

    def win_time(player):
        mono = five_windows_batch(filled == player, spec)     # (W, 4, n)
        best = big
        for d, off in enumerate(offs):
            wt = times
            for k in range(1, WIN_RUN):
                wt = jnp.maximum(wt, jnp.roll(times, -k * off, axis=1))
            cand = jnp.where(mono[:, d], wt, big)
            best = jnp.minimum(best, cand.min(axis=1))        # (W,)
        return best

    tb, tw = win_time(BLACK), win_time(WHITE)
    return jnp.where(tb < tw, BLACK,
                     jnp.where(tw < tb, WHITE, EMPTY)).astype(jnp.int8)


def playout_batch(boards: jnp.ndarray, to_move, keys: jax.Array,
                  spec: GomokuSpec) -> jnp.ndarray:
    """W random playouts fused into one (W, cells) evaluation stage.

    Same fill stream as Hex (one uniform (n,) draw per lane), outcome by
    completion time through the per-game dispatch
    ``kernels.ops.gomoku_first_winner`` — no move-by-move loop.
    """
    from repro.kernels import ops  # function-level: ops imports games' refs

    empties = boards == EMPTY
    ranks = game_mod.empty_fill_ranks(boards, keys)
    colors = game_mod.parity_fill_colors(ranks, to_move)
    filled = jnp.where(empties, colors, boards)
    times = jnp.where(empties, ranks, -1)
    return ops.gomoku_first_winner(filled, times, spec.size)


def playout_scalar(board: jnp.ndarray, to_move, key: jax.Array,
                   spec: GomokuSpec) -> jnp.ndarray:
    """Sequential per-lane playout oracle: place stones one at a time in the
    fill's rank order (argmin of the SAME uniform draw over the remaining
    empties, index tie-break matching ``empty_fill_ranks``), checking the
    placer's five after each move. Bit-identical to one lane of
    ``playout_batch`` — an independent incremental check of the
    completion-time formulation."""
    n = spec.n_cells
    u = jax.random.uniform(key, (n,))

    def five(b, p):
        return has_five_batch(b[None], p, spec)[0]

    fb, fw = five(board, BLACK), five(board, WHITE)
    w0 = jnp.where(fb & fw, EMPTY, jnp.where(fb, BLACK,
                                             jnp.where(fw, WHITE, EMPTY)))
    done0 = fb | fw | ~(board == EMPTY).any()
    player0 = jnp.asarray(to_move, jnp.int32)

    def cond(st):
        return ~st[3]

    def body(st):
        b, p, w, _ = st
        empt = b == EMPTY
        pick = jnp.argmin(jnp.where(empt, u, jnp.inf)).astype(jnp.int32)
        b2 = place(b, pick, p)
        won = five(b2, p.astype(jnp.int8))
        full = ~(b2 == EMPTY).any()
        return b2, 3 - p, jnp.where(won, p.astype(jnp.int8), w), won | full

    _, _, w, _ = jax.lax.while_loop(
        cond, body, (board, player0, w0.astype(jnp.int8), done0))
    return w


# ------------------------------------------------------- the Game protocol ----
class GomokuGame(NamedTuple):
    """Free-style Gomoku through the batched ``Game`` protocol.

    Differs from Hex in everything the protocol abstracts: the terminal
    test (first five ends the game mid-board), the legal-move set (empty at
    won positions, which is what stops the search expanding past a win),
    and the outcome range (draws). Sizes below 5 are legal but all-draw.
    """

    size: int

    @property
    def n_cells(self) -> int:
        return self.size * self.size

    @property
    def n_actions(self) -> int:
        return self.n_cells

    @property
    def max_moves(self) -> int:
        return self.n_cells

    @property
    def _spec(self) -> GomokuSpec:
        return GomokuSpec(self.size)

    def init_board(self) -> jnp.ndarray:
        return empty_board(self._spec)

    def place(self, board, move, player) -> jnp.ndarray:
        return place(board, move, player)

    def legal_mask(self, board) -> jnp.ndarray:
        # no legal moves once a five exists: expansion stops, and the
        # playout of the (terminal) leaf returns the pre-existing winner
        # (its completion time -1 beats every fill rank)
        won = (has_five_batch(board[None], BLACK, self._spec)
               | has_five_batch(board[None], WHITE, self._spec))[0]
        return (board == EMPTY) & ~won

    def terminal_batch(self, boards) -> jnp.ndarray:
        return terminal_batch(boards, self._spec)

    def winner_batch(self, boards) -> jnp.ndarray:
        from repro.kernels import ops

        return ops.gomoku_winner(boards, self.size)

    def playout_batch(self, boards, to_move, keys) -> jnp.ndarray:
        return playout_batch(boards, to_move, keys, self._spec)

    def playout_scalar(self, board, to_move, key) -> jnp.ndarray:
        return playout_scalar(board, to_move, key, self._spec)

    def replay_moves(self, moves, n_moves, first_player) -> jnp.ndarray:
        return game_mod.replay_moves(moves, n_moves, first_player,
                                     self.n_cells)

    def winner_probe(self, board) -> jnp.ndarray:
        # PARTIAL boards welcome (unlike winner_batch's terminal-only
        # contract): a five decides regardless of remaining space, a full
        # board without one is the draw, anything else is ongoing
        fb = has_five_batch(board[None], BLACK, self._spec)[0]
        fw = has_five_batch(board[None], WHITE, self._spec)[0]
        full = ~(board == EMPTY).any()
        return jnp.where(
            fb, jnp.int8(1),
            jnp.where(fw, jnp.int8(2),
                      jnp.where(full, jnp.int8(0),
                                jnp.int8(-1))))


game_mod.register_game("gomoku", GomokuGame)
