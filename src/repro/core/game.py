"""Batched ``Game`` protocol + registry — the game-agnostic seam (DESIGN.md §13).

The paper's contribution — Grain Size Controlled Parallel MCTS on a
work-sharing FIFO — is game-independent, and the group's follow-up work
(arXiv:1704.00325, arXiv:1605.04447) frames the parallel pattern explicitly
as a reusable structure over a pluggable game. This module is that seam: the
search layers (``core/gscpm.py``, ``core/mcts.py``, ``core/root_parallel.py``)
consume ONLY the protocol below and never import a game module directly.

A game is a small hashable NamedTuple (python-int fields only, so it is safe
to close over in ``jit`` and to carry through a static config) exposing the
vectorized primitives the fused pipeline consumes:

===================  ========================================================
``n_cells``          board length; boards are ``(n_cells,)`` int8 arrays
``n_actions``        distinct move ids (== ``n_cells``: a move is a cell)
``max_moves``        longest possible game (bounds the descent path length)
``init_board()``     the empty root position
``place(b, mv, p)``  set cell ``mv`` to player ``p`` (no legality check)
``legal_mask(b)``    bool ``(n_cells,)`` — all-False at TERMINAL positions,
                     which is what stops the search expanding past the end
                     of a game (Hex: empties; Gomoku: empties unless a five
                     exists)
``terminal_batch``   ``(W, n_cells) -> (W,) bool`` — no legal move remains
``playout_batch``    ``(boards, to_move, keys) -> (W,) int8`` values — one
                     fused (W, cells) evaluation of W random playouts
``playout_scalar``   the per-lane oracle twin (same RNG stream per lane;
                     bit-identical to one lane of ``playout_batch``)
``winner_batch``     terminal boards -> ``(W,)`` int8 outcomes
``replay_moves``     masked-scatter board reconstruction from a move list
``winner_probe``     ONE possibly-PARTIAL board -> int8 status: -1 ongoing,
                     0 draw, 1|2 the winner — the game-over test session
                     drivers poll between moves (unlike ``winner_batch``,
                     which assumes terminal boards)
===================  ========================================================

Conventions shared by every game (the search machinery assumes them):

- cells hold ``EMPTY`` (0) or a player id (1 | 2); players alternate
  ``p -> 3 - p``;
- playout/winner values are int8 in ``{0, 1, 2}``: the winning player id, or
  ``DRAW`` (0) for a drawn game. Hex never draws; Gomoku's full-board draw
  is the first non-win outcome through backup (credit 0.5), UCT (X_j = 0.5)
  and root merging — ``core/tree.backup_paths`` handles all three values;
- ``playout_batch`` consumes exactly one ``(n_cells,)`` uniform draw per
  lane key (the rank stream below), so scalar/batched paths and the Hex
  pre-seam RNG schedule are all bit-identical.

The conformance property suite (tests/test_game_protocol.py) runs every
registered game against these contracts.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

EMPTY = jnp.int8(0)
P1 = jnp.int8(1)
P2 = jnp.int8(2)
DRAW = jnp.int8(0)  # playout value of a drawn game


# --------------------------------------------------------------- registry ----
_REGISTRY: dict[str, Callable[[int], Any]] = {}


def stamp_game_identity(cls):
    """Make a Game NamedTuple compare/hash by TYPE as well as fields.

    Plain NamedTuples compare as tuples, so ``HexGame(7) == GomokuGame(7)``
    would be True — and a jitted function taking the game as a STATIC
    argument (e.g. ``mcts._run``) would silently reuse one game's compiled
    program for the other. Every registered game class gets stamped.
    """
    def __eq__(self, other):
        return type(other) is type(self) and tuple(self) == tuple(other)

    def __hash__(self):
        return hash((type(self).__qualname__, *self))

    cls.__eq__ = __eq__
    cls.__ne__ = lambda self, other: not __eq__(self, other)
    cls.__hash__ = __hash__
    return cls


def register_game(name: str, factory: Callable[[int], Any]) -> None:
    """Register ``factory(board_size) -> Game`` under ``name``."""
    if isinstance(factory, type) and issubclass(factory, tuple):
        stamp_game_identity(factory)
    _REGISTRY[name] = factory


def _ensure_builtin_games() -> None:
    # games self-register at import; lazy so game.py itself stays dep-free
    from repro.core import gomoku, hex  # noqa: F401


def available_games() -> tuple[str, ...]:
    _ensure_builtin_games()
    return tuple(sorted(_REGISTRY))


def make_game(name: str, board_size: int):
    """Resolve a registered game — the ``--game`` flag's single entry point."""
    _ensure_builtin_games()
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown game {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name](board_size)


# ------------------------------------------------------ shared batched ops ----
def place(board: jnp.ndarray, move, player) -> jnp.ndarray:
    """Set cell ``move`` of a flat board to ``player`` (no legality check).

    A one-hot select, not a scatter: on a TPU v5e a vmapped single-index
    ``board.at[move].set`` left stones that depended on the batch size, so
    a forest's members searched differently on one chip and on four. A move
    outside the board places nothing.
    """
    cells = jnp.arange(board.shape[-1], dtype=jnp.int32)
    return jnp.where(cells == move, jnp.asarray(player).astype(board.dtype),
                     board)


def empty_fill_ranks(boards: jnp.ndarray, keys: jax.Array) -> jnp.ndarray:
    """(W, n) rank of each cell among the lane's empties in random fill order.

    The shared core of every game's batched playout: lane w draws ONE
    ``(n,)`` uniform vector from ``keys[w]`` and the k-th smallest value
    over the empty cells marks the k-th playout move. The rank is counted
    directly — rank[i] = #{empty j : (noise_j, j) < (noise_i, i)} — one
    (W, n, n) boolean compare-and-count with the index tie-break a stable
    argsort would apply, bit-identical to the argsort formulation and
    sort-free (XLA sorts are the slow path on every backend). Non-empty
    cells get a meaningless rank; callers mask them.
    """
    W, n = boards.shape
    empties = boards == EMPTY
    noise = jax.vmap(lambda k: jax.random.uniform(k, (n,)))(keys)
    idx = jnp.arange(n, dtype=jnp.int32)
    nj, ni = noise[:, None, :], noise[:, :, None]
    earlier = (nj < ni) | ((nj == ni)
                           & (idx[None, None, :] < idx[None, :, None]))
    return jnp.sum(earlier & empties[:, None, :], axis=2)


def parity_fill_colors(ranks: jnp.ndarray, to_move) -> jnp.ndarray:
    """Stone colors of a random fill: rank parity alternates from ``to_move``."""
    W = ranks.shape[0]
    tm = jnp.broadcast_to(jnp.asarray(to_move, jnp.int32), (W,))[:, None]
    other = jnp.int32(3) - tm
    return jnp.where((ranks % 2) == 0, tm, other).astype(jnp.int8)


def replay_moves(moves: jnp.ndarray, n_moves: jnp.ndarray, first_player,
                 n_cells: int) -> jnp.ndarray:
    """Reconstruct a board from a move list (fixed-length, masked by n_moves).

    One masked scatter instead of a per-move ``fori_loop``: move i places
    the (i-even ? first : other) player's stone; moves at or past
    ``n_moves`` land on a pad cell and are dropped. Moves must target
    distinct cells (every legal game's move list does — a move is an empty
    cell); the caller is responsible for the list not running past the
    game's end.
    """
    L = moves.shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)
    first_player = jnp.asarray(first_player, jnp.int32)
    players = jnp.where((idx % 2) == 0, first_player,
                        3 - first_player).astype(jnp.int8)
    tgt = jnp.where(idx < n_moves, moves, n_cells)
    board = jnp.zeros((n_cells + 1,), dtype=jnp.int8).at[tgt].set(players)
    return board[:n_cells]
