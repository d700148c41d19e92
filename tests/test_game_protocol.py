"""Game-protocol conformance suite (DESIGN.md §13).

Every REGISTERED game is run against the seam's contracts: legal_mask/place
round-trips, protocol-driven play reaches a terminal position, winners agree
with a pure-python reference, the fused ``playout_batch`` is bit-identical
to the vmapped per-lane ``playout_scalar`` oracle, and whole GSCPM searches
through the seam hold the tree invariants (``check_invariants`` — including
the draw-aware value range) on random positions. Gomoku-specific tests pin
the draw path (value 0 → credit 0.5) through ``backup_paths``,
``root_move_stats``, and a forced-draw end-to-end search, plus the
mid-board terminal semantics (a five empties ``legal_mask``, so won
positions are evaluated, never expanded). A source check keeps the search
core free of direct game imports.
"""

from __future__ import annotations

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import game as game_mod
from repro.core.gscpm import GSCPMConfig, gscpm_search
from repro.core.tree import (backup_paths, check_invariants, init_tree,
                             root_move_stats, root_value)

GAME_SIZES = {"hex": 5, "gomoku": 7}
GAMES = sorted(game_mod.available_games())


def make(name: str):
    return game_mod.make_game(name, GAME_SIZES[name])


def random_board(game, rng: np.random.Generator, fill: float) -> jnp.ndarray:
    """Alternating random stones on `fill` of the cells (may be terminal)."""
    n = game.n_cells
    b = np.zeros(n, dtype=np.int8)
    idx = rng.permutation(n)[: int(n * fill)]
    for t, i in enumerate(idx):
        b[i] = 1 if t % 2 == 0 else 2
    return jnp.asarray(b)


def played_board(game, rng: np.random.Generator, n_moves: int):
    """A position reached by LEGAL protocol play (never past the end)."""
    b = game.init_board()
    player = 1
    for _ in range(n_moves):
        legal = np.flatnonzero(np.asarray(game.legal_mask(b)))
        if len(legal) == 0:
            break
        b = game.place(b, jnp.int32(rng.choice(legal)), jnp.int32(player))
        player = 3 - player
    return b, player


# ------------------------------------------------------------- registry ----
def test_registry_lists_builtin_games():
    assert {"hex", "gomoku"} <= set(game_mod.available_games())
    with pytest.raises(ValueError, match="unknown game"):
        game_mod.make_game("chess", 8)


def test_games_of_equal_size_are_distinct():
    """Game objects must compare/hash by TYPE, not just fields: plain
    NamedTuple equality would make HexGame(7) == GomokuGame(7), and a jit
    cache keyed on a static game argument (mcts._run) would silently run
    one game's compiled program on the other's boards."""
    h = game_mod.make_game("hex", 7)
    g = game_mod.make_game("gomoku", 7)
    assert h != g and g != h
    assert hash(h) != hash(g)
    assert h == game_mod.make_game("hex", 7)
    assert h != game_mod.make_game("hex", 9)
    # end-to-end: same (shape, cp, n_iters) sequential searches must NOT
    # share a program — the gomoku tree sees draws (half credits), which
    # the hex program can never produce
    from repro.core.mcts import uct_search

    key = jax.random.PRNGKey(0)
    board = h.init_board()
    t_hex, _ = uct_search(board, 1, 48, key, board_size=7, tree_cap=512)
    t_gom, _ = uct_search(board, 1, 48, key, board_size=7, tree_cap=512,
                          game="gomoku")
    assert not np.array_equal(np.asarray(t_hex.wins),
                              np.asarray(t_gom.wins))


def test_search_core_is_game_agnostic():
    """The acceptance bar: no direct game coupling left in the search core."""
    from repro.core import gscpm, mcts, root_parallel

    for mod in (gscpm, mcts, root_parallel):
        src = inspect.getsource(mod)
        assert "import hex" not in src and "hx." not in src, mod.__name__


# ------------------------------------------------------ protocol contracts ----
@pytest.mark.parametrize("name", GAMES)
def test_legal_place_roundtrip(name):
    g = make(name)
    rng = np.random.default_rng(7)
    for fill in (0.0, 0.3, 0.6):
        b = random_board(g, rng, fill)
        legal = np.asarray(g.legal_mask(b))
        assert legal.shape == (g.n_cells,)
        # legal moves are a subset of the empty cells
        assert not (legal & (np.asarray(b) != 0)).any()
        if legal.any():
            mv = int(np.flatnonzero(legal)[0])
            b2 = g.place(b, jnp.int32(mv), jnp.int32(1))
            assert int(b2[mv]) == 1
            np.testing.assert_array_equal(
                np.delete(np.asarray(b2), mv), np.delete(np.asarray(b), mv))
            assert not bool(g.legal_mask(b2)[mv])


@pytest.mark.parametrize("name", GAMES)
def test_batched_place_sets_exactly_one_cell_per_board(name):
    """``place`` under a two-level vmap (forest x lanes, the search's own
    batching) writes each board's move and nothing else; a move off the
    board writes nothing."""
    g = make(name)
    rng = np.random.default_rng(3)
    E, W = 3, 5
    boards = np.stack([np.stack([np.asarray(random_board(g, rng, 0.4))
                                 for _ in range(W)]) for _ in range(E)])
    moves = rng.integers(0, g.n_cells, (E, W)).astype(np.int32)
    moves[0, 0] = -1
    moves[1, 2] = g.n_cells
    players = rng.integers(1, 3, (E, W)).astype(np.int8)
    got = np.asarray(jax.jit(jax.vmap(jax.vmap(g.place)))(
        jnp.asarray(boards), jnp.asarray(moves), jnp.asarray(players)))
    want = boards.copy()
    for e in range(E):
        for w in range(W):
            if 0 <= moves[e, w] < g.n_cells:
                want[e, w, moves[e, w]] = players[e, w]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", GAMES)
def test_protocol_play_reaches_terminal(name):
    """Playing legal moves must end within max_moves, at a position that is
    terminal_batch-positive and legal_mask-empty, with a defined winner."""
    g = make(name)
    rng = np.random.default_rng(11)
    b, _ = played_board(g, rng, g.max_moves + 1)
    assert bool(g.terminal_batch(b[None])[0])
    assert not np.asarray(g.legal_mask(b)).any()
    w = int(g.winner_batch(b[None])[0])
    assert w in (0, 1, 2)
    if name == "hex":
        assert w != 0  # Hex theorem: no draws


def py_hex_winner(board: np.ndarray, size: int) -> int:
    """Flood-fill reference winner of a FILLED hex board."""
    deltas = [(-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0)]
    seen = set()
    stack = [(0, c) for c in range(size) if board[c] == 1]
    while stack:
        r, c = stack.pop()
        if (r, c) in seen:
            continue
        seen.add((r, c))
        if r == size - 1:
            return 1
        for dr, dc in deltas:
            rr, cc = r + dr, c + dc
            if (0 <= rr < size and 0 <= cc < size
                    and board[rr * size + cc] == 1 and (rr, cc) not in seen):
                stack.append((rr, cc))
    return 2


def py_gomoku_winner(board: np.ndarray, size: int) -> int:
    """Line-scan reference: 1/2 if that color owns a five (black priority,
    matching `winner_scan_batch` on illegal double-five boards), else 0."""
    grid = board.reshape(size, size)
    for p in (1, 2):
        for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
            for r in range(size):
                for c in range(size):
                    rr, cc = r + 4 * dr, c + 4 * dc
                    if not (0 <= rr < size and 0 <= cc < size):
                        continue
                    if all(grid[r + k * dr, c + k * dc] == p
                           for k in range(5)):
                        return p
    return 0


@pytest.mark.parametrize("name", GAMES)
def test_winner_matches_python_reference(name):
    g = make(name)
    size = GAME_SIZES[name]
    rng = np.random.default_rng(size)
    ref = {"hex": py_hex_winner, "gomoku": py_gomoku_winner}[name]
    # hex's winner contract needs filled boards; gomoku's scan is defined
    # (five-or-nothing) on any board
    fills = (1.0,) if name == "hex" else (0.3, 0.6, 1.0)
    for fill in fills:
        boards = jnp.stack([random_board(g, rng, fill) for _ in range(16)])
        got = np.asarray(g.winner_batch(boards))
        want = np.asarray([ref(np.asarray(b), size) for b in boards])
        np.testing.assert_array_equal(got, want, err_msg=f"{name} {fill=}")


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), name=st.sampled_from(GAMES))
def test_playout_batch_bit_identical_to_scalar(seed, name):
    """The fused (W, cells) playout equals W vmapped per-lane oracles —
    for Gomoku that pits the completion-time formulation against the
    sequential move-by-move loop (same RNG stream per lane)."""
    g = make(name)
    rng = np.random.default_rng(seed)
    W = 8
    boards = jnp.stack(
        [random_board(g, rng, float(rng.uniform(0.0, 0.6))) for _ in range(W)])
    keys = jax.random.split(jax.random.PRNGKey(seed), W)
    to_move = 1 + seed % 2
    got = g.playout_batch(boards, to_move, keys)
    want = jax.vmap(
        lambda b, k: g.playout_scalar(b, jnp.int32(to_move), k))(boards, keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", GAMES)
def test_winner_probe_contract(name):
    """``winner_probe`` is the PARTIAL-board status probe (-1 ongoing,
    0 draw, 1|2 winner) the session layer polls after every move — unlike
    ``winner_batch``, whose contract only covers terminal boards. An empty
    board is ongoing; a legally played-out terminal board must agree with
    ``winner_batch``."""
    g = make(name)
    assert int(g.winner_probe(g.init_board())) == -1
    rng = np.random.default_rng(23)
    b, _ = played_board(g, rng, g.max_moves + 1)
    w = int(g.winner_probe(b))
    assert w == int(g.winner_batch(b[None])[0])
    assert w >= 0


def test_winner_probe_detects_midboard_wins():
    """A win must register the move it appears, long before the board
    fills: a black top-bottom chain on hex, a black five on gomoku."""
    size = 5
    hexes = make("hex")
    b = np.zeros(size * size, dtype=np.int8)
    for r in range(size):
        b[r * size] = 1                       # column 0, rows 0..4
    assert int(hexes.winner_probe(jnp.asarray(b))) == 1
    g5 = game_mod.make_game("gomoku", size)
    assert int(g5.winner_probe(jnp.asarray(b))) == 1  # a vertical five
    b[2 * size] = 0                           # break both chains
    assert int(hexes.winner_probe(jnp.asarray(b))) == -1


def test_winner_probe_gomoku_draw_only_when_full():
    """The forced-draw position stays ONGOING while empties remain (either
    player could still move) and becomes a DRAW once filled."""
    g5 = game_mod.make_game("gomoku", 5)      # the draw position is 5x5
    b = drawn_gomoku_position()
    assert int(g5.winner_probe(b)) == -1
    full = jnp.asarray(np.where(np.asarray(b) == 0, 1, np.asarray(b)))
    assert int(g5.winner_probe(full)) == 0


# ----------------------------------------------------- search through seam ----
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), name=st.sampled_from(GAMES),
       workers=st.sampled_from([2, 8]))
def test_property_search_invariants_every_game(seed, name, workers):
    """GSCPM through the seam holds the (draw-aware) tree invariants and the
    [0, 1] value range from arbitrary legally-reached positions."""
    g = make(name)
    rng = np.random.default_rng(seed)
    b, player = played_board(g, rng, int(rng.integers(0, 10)))
    cfg = GSCPMConfig(game=name, board_size=GAME_SIZES[name], n_playouts=64,
                      n_tasks=8, n_workers=workers, tree_cap=4096)
    tree, stats = gscpm_search(b, player, cfg, jax.random.PRNGKey(seed))
    check_invariants(tree)
    assert 0.0 <= stats["root_value"] <= 1.0
    assert int(np.asarray(tree.visits[0])) == stats["playouts"]


@pytest.mark.parametrize("name", GAMES)
def test_full_search_scalar_paths_bit_identical(name):
    """descent/playout oracle configs survive the seam for every game."""
    g = make(name)
    base = GSCPMConfig(game=name, board_size=GAME_SIZES[name], n_playouts=64,
                       n_tasks=8, n_workers=4, tree_cap=2048)
    key = jax.random.PRNGKey(29)
    t0, s0 = gscpm_search(g.init_board(), 1, base, key)
    for repl in ({"playout": "scalar"}, {"descent": "scalar"}):
        t1, s1 = gscpm_search(g.init_board(), 1,
                              dataclasses.replace(base, **repl), key)
        nn = int(t0.n_nodes)
        assert nn == int(t1.n_nodes), repl
        for f in ("parent", "move", "to_move", "n_children"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t0, f)[:nn]),
                np.asarray(getattr(t1, f)[:nn]), err_msg=f"{repl} {f}")
        np.testing.assert_allclose(np.asarray(t0.visits[:nn]),
                                   np.asarray(t1.visits[:nn]))
        np.testing.assert_allclose(np.asarray(t0.wins[:nn]),
                                   np.asarray(t1.wins[:nn]))


def test_hex_game_methods_match_module_functions():
    """The seam adds NO computation on Hex: protocol methods are bit-equal
    to the pre-refactor module entry points (same RNG schedule ⇒ the
    pre-seam trees are preserved — the PR 3/4 equivalence pattern)."""
    from repro.core import hex as hx

    g = game_mod.make_game("hex", 5)
    spec = hx.HexSpec(5)
    rng = np.random.default_rng(0)
    W = 8
    boards = jnp.stack([random_board(g, rng, 0.4) for _ in range(W)])
    keys = jax.random.split(jax.random.PRNGKey(1), W)
    np.testing.assert_array_equal(
        np.asarray(g.playout_batch(boards, 1, keys)),
        np.asarray(hx.playout_batch(boards, 1, keys, spec)))
    filled = hx.random_fill_batch(boards, 1, keys, spec)
    np.testing.assert_array_equal(
        np.asarray(g.winner_batch(filled)),
        np.asarray(hx.winner_batch(filled, spec)))
    np.testing.assert_array_equal(
        np.asarray(g.legal_mask(boards[0])),
        np.asarray(hx.legal_mask(boards[0])))
    mvs = jnp.asarray([3, 9, 0, 17], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(g.replay_moves(mvs, jnp.int32(3), jnp.int32(1))),
        np.asarray(hx.replay_moves(mvs, jnp.int32(3), jnp.int32(1), spec)))


# -------------------------------------------------------- gomoku: the draw ----
def drawn_gomoku_position():
    """5x5 free-style position where EVERY completion is a draw: each of the
    12 five-windows already contains both colors among its fixed stones, so
    neither player can ever own one, whatever fills the two empties."""
    pattern = [
        1, 1, 2, 1, 1,
        2, 2, 1, 2, 2,
        1, 1, 2, 1, 1,
        2, 2, 1, 2, 2,
        1, 1, 2, 1, 1,
    ]
    b = np.asarray(pattern, dtype=np.int8)
    b[5] = 0   # (1, 0)
    b[19] = 0  # (3, 4)
    return jnp.asarray(b)


def test_gomoku_draw_credit_through_backup_paths():
    """A draw (value 0) credits every node on the path 0.5 — between the
    loss (0) and the win (1), the first non-{0,1} increment the tree sees."""
    tree = init_tree(16, 25, 1)
    from repro.core.gscpm import expand_batch

    tree, ids = expand_batch(tree, jnp.array([0, 0]), jnp.array([3, 7]),
                             jnp.ones(2, bool))
    paths = jnp.stack([jnp.array([0, ids[0]]), jnp.array([0, ids[1]])])
    values = jnp.array([0, 1], dtype=jnp.int8)   # one draw, one BLACK win
    tree = backup_paths(tree, paths, values, jnp.ones(2))
    assert float(tree.visits[0]) == 2.0
    # mover-into-root is WHITE (to_move=1): draw pays 0.5, BLACK's win 0
    assert float(tree.wins[0]) == 0.5
    # children's mover is BLACK: draw pays 0.5, the BLACK win pays 1
    assert float(tree.wins[ids[0]]) == 0.5
    assert float(tree.wins[ids[1]]) == 1.0
    v, w = root_move_stats(tree, 25)
    assert float(v[3]) == 1.0 and float(w[3]) == 0.5
    assert float(v[7]) == 1.0 and float(w[7]) == 1.0
    check_invariants(tree)


def test_gomoku_all_draw_search_is_exactly_half():
    """End-to-end: from the forced-draw position every playout returns 0,
    so wins == visits/2 at every node and root_value == 0.5 exactly."""
    b = drawn_gomoku_position()
    cfg = GSCPMConfig(game="gomoku", board_size=5, n_playouts=64, n_tasks=8,
                      n_workers=4, tree_cap=512)
    tree, stats = gscpm_search(b, 1, cfg, jax.random.PRNGKey(5))
    check_invariants(tree)
    assert stats["root_value"] == 0.5
    nn = int(tree.n_nodes)
    np.testing.assert_allclose(np.asarray(tree.wins[:nn]),
                               np.asarray(tree.visits[:nn]) / 2.0)
    v, w = root_move_stats(tree, 25)
    np.testing.assert_allclose(np.asarray(w), np.asarray(v) / 2.0)


def test_gomoku_finds_immediate_win():
    """Black has an open four on row 3 of a 7x7 board; either extension
    (cells 21 / 26) wins outright — each winning child's value is exactly 1
    (every playout from a won position returns its pre-existing winner),
    and UCT gives each at least an even share of the root's visits.

    Which move ends up most visited is seed luck at this budget: with an
    open four nearly every random playout is a black win, so many other
    children also score 1.0 over their ~12 visits and the argmax among
    them is decided by the random stream, not by the search."""
    size = 7
    b = np.zeros(size * size, dtype=np.int8)
    for c in (1, 2, 3, 4):
        b[3 * size + c] = 1
    for cell in (0, 6, 42, 48):
        b[cell] = 2
    cfg = GSCPMConfig(game="gomoku", board_size=size, n_playouts=512,
                      n_tasks=16, n_workers=8, tree_cap=8192)
    tree, stats = gscpm_search(jnp.asarray(b), 1, cfg, jax.random.PRNGKey(2))
    kids = np.asarray(tree.children[0][: int(tree.n_children[0])])
    moves = list(np.asarray(tree.move)[kids])
    visits = np.asarray(tree.visits)[kids]
    for mv in (3 * size + 0, 3 * size + 5):
        j = kids[moves.index(mv)]
        assert float(tree.wins[j]) == float(tree.visits[j]) > 0
        assert float(tree.visits[j]) >= visits.mean()


def test_gomoku_won_position_is_terminal_not_expanded():
    """A position already containing a five has NO legal moves: the search
    cannot grow past the end of the game, and every playout backs up the
    pre-existing winner."""
    size = 7
    b = np.zeros(size * size, dtype=np.int8)
    for c in range(5):
        b[2 * size + c] = 1          # black five on row 2
    for cell in (40, 41, 45, 46):
        b[cell] = 2
    g = game_mod.make_game("gomoku", size)
    assert not np.asarray(g.legal_mask(jnp.asarray(b))).any()
    cfg = GSCPMConfig(game="gomoku", board_size=size, n_playouts=32,
                      n_tasks=4, n_workers=4, tree_cap=256)
    tree, stats = gscpm_search(jnp.asarray(b), 2, cfg, jax.random.PRNGKey(0))
    assert int(tree.n_nodes) == 1            # nothing expanded
    # mover into the root is BLACK (to_move=2), who owns the five
    assert float(tree.wins[0]) == float(tree.visits[0]) == stats["playouts"]
