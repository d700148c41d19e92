"""Batched (W, cells) Hex evaluation vs the per-lane scalar oracles.

The tentpole contract (DESIGN.md §12): `connected_batch` (pointer-doubling
CC labeling), `winner_batch` / `winner_flood_batch`, `random_fill_batch`,
and the fused `playout_batch` must be BIT-identical to the vmapped scalar
oracles (`connected` / `winner` / `random_fill` / `playout`) under the same
RNG schedule — across board sizes, batch widths, partial and filled boards,
and under a further vmap over the forest axis. Pointer doubling must also
converge within the fixed ceil(log2(n_cells)) + 2 round budget of its
fixed-round variant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hex as hx

SIZES = (5, 9, 11)
WIDTHS = (1, 8, 16)


def random_boards(rng: np.random.Generator, size: int, W: int,
                  fill: float) -> jnp.ndarray:
    """(W, n) int8 boards with `fill` fraction of alternating stones."""
    n = size * size
    out = np.zeros((W, n), dtype=np.int8)
    for w in range(W):
        k = int(n * fill)
        idx = rng.permutation(n)[:k]
        for t, i in enumerate(idx):
            out[w, i] = 1 if t % 2 == 0 else 2
    return jnp.asarray(out)


# ------------------------------------------------------------ connectivity ----
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("W", WIDTHS)
def test_connected_batch_matches_vmapped_connected(size, W):
    spec = hx.HexSpec(size)
    rng = np.random.default_rng(size * 100 + W)
    for fill in (0.0, 0.3, 0.6, 1.0):
        boards = random_boards(rng, size, W, fill)
        for player in (1, 2):
            got = hx.connected_batch(boards, player, spec)
            want = jax.vmap(
                lambda b: hx.connected(b, jnp.int8(player), spec))(boards)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=f"{size=} {W=} {fill=} "
                                                  f"{player=}")


@pytest.mark.parametrize("size", SIZES)
def test_winner_batch_paths_agree_on_filled(size):
    """Dispatch (`winner_batch`), flood batch, and vmapped scalar winner are
    bit-identical on filled boards."""
    spec = hx.HexSpec(size)
    W = 16
    keys = jax.random.split(jax.random.key(size), W)
    boards = jnp.tile(hx.empty_board(spec)[None], (W, 1))
    filled = hx.random_fill_batch(boards, 1, keys, spec)
    assert (np.asarray(filled) != 0).all()
    want = jax.vmap(lambda b: hx.winner(b, spec))(filled)
    np.testing.assert_array_equal(
        np.asarray(hx.winner_batch(filled, spec)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(hx.winner_flood_batch(filled, spec)), np.asarray(want))


def adversarial_stones(size: int) -> np.ndarray:
    """(3, n) stone masks with worst-case component shape: solid board,
    column comb, and a boustrophedon snake — the long-thin components that
    maximize pointer-doubling rounds."""
    n = size * size
    solid = np.ones(n, dtype=bool)
    comb = np.zeros(n, dtype=bool)
    for r in range(size):
        for c in range(size):
            if c % 2 == 0 or r == 0:
                comb[r * size + c] = True
    snake = np.zeros(n, dtype=bool)
    for r in range(size):
        cols = range(size) if r % 2 == 0 else [size - 1]
        for c in cols:
            snake[r * size + c] = True
    return np.stack([solid, comb, snake])


@pytest.mark.parametrize("size", [11, 17, 25])
def test_fixed_round_budget_adversarial_boards(size):
    """The fixed-round labeling has NO runtime convergence check, so its
    budget must reach the exact CC fixpoint on the worst component shapes
    too — snake/comb/solid boards at sizes beyond the play configs
    (empirically <= 7 rounds vs caps of 9-12; do not tighten the budget
    without this)."""
    spec = hx.HexSpec(size)
    cap = hx.doubling_rounds(size * size)
    stones = jnp.asarray(adversarial_stones(size))
    lab_fix = hx.cc_labels_batch(stones, spec)
    lab_cap = hx.cc_labels_batch(stones, spec, rounds=cap)
    np.testing.assert_array_equal(np.asarray(lab_fix), np.asarray(lab_cap))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), size=st.sampled_from(list(SIZES)),
       W=st.sampled_from(list(WIDTHS)))
def test_fixed_doubling_round_budget(seed, size, W):
    """Pointer doubling reaches the exact CC fixpoint within the fixed
    ceil(log2(n_cells)) + 2 rounds of ``doubling_rounds`` — on random partial boards AND the
    adversarial all-one-color board (worst-case component diameter)."""
    spec = hx.HexSpec(size)
    n = size * size
    cap = hx.doubling_rounds(n)
    rng = np.random.default_rng(seed)
    boards = random_boards(rng, size, W, float(rng.uniform(0.2, 1.0)))
    stones = jnp.concatenate(
        [boards == 1, jnp.ones((1, n), dtype=bool)], axis=0)
    lab_fix = hx.cc_labels_batch(stones, spec)
    lab_cap = hx.cc_labels_batch(stones, spec, rounds=cap)
    np.testing.assert_array_equal(np.asarray(lab_fix), np.asarray(lab_cap))


# ------------------------------------------------------------ fill/playout ----
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("W", WIDTHS)
def test_random_fill_batch_bit_identical(size, W):
    spec = hx.HexSpec(size)
    rng = np.random.default_rng(size + W)
    keys = jax.random.split(jax.random.key(size * 7 + W), W)
    for fill in (0.0, 0.4):
        boards = random_boards(rng, size, W, fill)
        got = hx.random_fill_batch(boards, 2, keys, spec)
        want = jax.vmap(
            lambda b, k: hx.random_fill(b, jnp.int32(2), k, spec))(boards, keys)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (np.asarray(got) != 0).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), size=st.sampled_from(list(SIZES)),
       W=st.sampled_from(list(WIDTHS)))
def test_playout_batch_bit_identical(seed, size, W):
    """Fused playout (one argsort-free fill + one connectivity solve for the
    whole batch) returns exactly the winners of W scalar playouts."""
    spec = hx.HexSpec(size)
    rng = np.random.default_rng(seed)
    boards = random_boards(rng, size, W, float(rng.uniform(0.0, 0.7)))
    keys = jax.random.split(jax.random.key(seed), W)
    to_move = 1 + (seed % 2)
    got = hx.playout_batch(boards, to_move, keys, spec)
    # explicit scalar formulation (fill + per-lane flood-fill winner):
    # `hx.playout` itself is now a width-1 wrapper over the batched path,
    # so the oracle is spelled out to stay an independent implementation
    want = jax.vmap(lambda b, k: hx.winner(
        hx.random_fill(b, jnp.int32(to_move), k, spec), spec))(boards, keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_playout_batch_composes_with_forest_vmap():
    """A further vmap over the ensemble axis (the root-parallel forest path)
    keeps the batch bit-identical: (E, W, cells) playouts in one program."""
    E, W, size = 3, 8, 5
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.key(11), E * W).reshape(E, W)
    boards = jnp.tile(hx.empty_board(spec)[None, None], (E, W, 1))
    got = jax.jit(jax.vmap(
        lambda b, k: hx.playout_batch(b, 1, k, spec)))(boards, keys)
    want = jax.vmap(jax.vmap(lambda b, k: hx.winner(
        hx.random_fill(b, jnp.int32(1), k, spec), spec)))(boards, keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------- winner contract ----
def test_winner_checked_rejects_partial_board():
    spec = hx.HexSpec(5)
    partial = hx.empty_board(spec).at[0].set(1)
    with pytest.raises(AssertionError, match="not completely filled"):
        hx.winner_checked(partial, spec)


def test_winner_checked_passes_filled_board():
    spec = hx.HexSpec(5)
    full = hx.random_fill(hx.empty_board(spec), jnp.int32(1),
                          jax.random.key(0), spec)
    assert int(hx.winner_checked(full, spec)) == int(hx.winner(full, spec))


# ------------------------------------------------------------ replay oracle ----
@pytest.mark.parametrize("n_moves", [0, 3, 7])
def test_replay_moves_matches_sequential_placement(n_moves):
    """The one-shot masked scatter equals move-by-move placement."""
    size = 5
    spec = hx.HexSpec(size)
    moves = jnp.asarray([4, 9, 0, 24, 13, 7, 19], dtype=jnp.int32)
    got = np.asarray(hx.replay_moves(moves, jnp.int32(n_moves),
                                     jnp.int32(2), spec))
    want = np.zeros(size * size, dtype=np.int8)
    for i in range(n_moves):
        want[int(moves[i])] = 2 if i % 2 == 0 else 1
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- search integration ----
@pytest.mark.parametrize("W", [4, 8])
def test_full_search_playout_batched_equals_scalar(W, per_lane_references):
    """Whole GSCPM searches with the fused playout stage produce bit-identical
    trees to the per-lane flood-fill playout reference swapped in (same RNG
    schedule)."""
    from repro.core.gscpm import GSCPMConfig, gscpm_search

    board = hx.empty_board(hx.HexSpec(5))
    cfg = GSCPMConfig(board_size=5, n_playouts=128, n_tasks=8, n_workers=W,
                      tree_cap=2048)
    key = jax.random.PRNGKey(23)
    t_b, s_b = gscpm_search(board, 1, cfg, key)
    with per_lane_references(cfg.game_obj, playout=True):
        t_s, s_s = gscpm_search(board, 1, cfg, key)
    assert int(t_b.n_nodes) == int(t_s.n_nodes)
    nn = int(t_b.n_nodes)
    for f in ("parent", "move", "to_move", "n_children"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_b, f)[:nn]),
            np.asarray(getattr(t_s, f)[:nn]), err_msg=f)
    np.testing.assert_allclose(np.asarray(t_b.visits[:nn]),
                               np.asarray(t_s.visits[:nn]))
    np.testing.assert_allclose(np.asarray(t_b.wins[:nn]),
                               np.asarray(t_s.wins[:nn]))
    assert s_b["best_move"] == s_s["best_move"]
