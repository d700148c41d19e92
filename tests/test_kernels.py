"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

KEY = jax.random.key(7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,d", [
    (2, 4, 2, 256, 64),    # GQA
    (1, 8, 1, 128, 128),   # MQA
    (2, 2, 2, 256, 80),    # odd head dim (pad path)
    (1, 4, 4, 512, 64),    # MHA longer seq
])
def test_flash_attention(B, H, Hkv, S, d, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S + d + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    got = ops.flash_attention(q, k, v, causal=True, layout="bhsd")
    want = ref.flash_attention(q, k, v, causal=True)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    got = ops.flash_attention(q, k, v, causal=False, layout="bhsd")
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_matches_model_sdpa():
    """Kernel agrees with the model's attention path (bshd layout)."""
    from repro.models.attention import causal_mask, sdpa
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    got = ops.flash_attention(q, k, v, causal=True)        # bshd
    want = sdpa(q, k, v, causal_mask(128))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("W,C", [(7, 11), (64, 121), (200, 121), (16, 300),
                                 (128, 128), (1, 5)])
@pytest.mark.parametrize("noise", [False, True])
def test_uct_select_kernel_vs_oracle(W, C, noise):
    """Interpret-mode Pallas kernel (validation-only path) == jnp oracle."""
    ks = jax.random.split(jax.random.fold_in(KEY, W * C + noise), 5)
    visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 10)
    wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
    vloss = jnp.round(jax.random.uniform(ks[2], (W, C)) * 2)
    valid = jax.random.uniform(ks[3], (W, C)) > 0.3
    ptot = jnp.maximum(visits.sum(-1), 1.0)
    nz = 1e-3 * jax.random.uniform(ks[4], (W, C)) if noise else None
    got = ops.uct_select(wins, visits, vloss, ptot, valid, 1.0, noise=nz,
                         interpret=True)
    want = ref.uct_select(wins, visits, vloss, ptot, valid, 1.0, noise=nz)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_uct_select_dispatch_agrees_with_kernel():
    """The auto dispatch the search hot path hits (compiled Pallas on TPU,
    jitted jnp reference elsewhere) selects the same children as the
    interpret-mode Pallas kernel — an independent implementation on every
    backend, so this is non-vacuous on the CPU CI host too — with cp
    traced and a lane mask applied."""
    ks = jax.random.split(KEY, 4)
    W, C = 32, 24
    visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 10)
    wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
    valid = jax.random.uniform(ks[2], (W, C)) > 0.3
    ptot = jnp.maximum(visits.sum(-1), 1.0)
    mask = jax.random.uniform(ks[3], (W,)) > 0.25
    for cp in (jnp.float32(0.5), jnp.float32(1.7)):
        got = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid,
                             cp, lane_mask=mask)
        kernel = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid,
                                cp, lane_mask=mask, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(kernel))


@pytest.mark.parametrize("size,W", [(5, 1), (5, 7), (9, 16), (11, 16)])
def test_hex_winner_kernel_vs_oracle(size, W):
    """Interpret-mode roll-dilation Pallas kernel (validation-only path)
    == the jnp pointer-doubling reference == the scalar flood-fill winner,
    on filled boards (the kernel's contract domain)."""
    from repro.core import hex as hx
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.fold_in(KEY, size * W), W)
    boards = jnp.tile(hx.empty_board(spec)[None], (W, 1))
    filled = hx.random_fill_batch(boards, 1, keys, spec)
    got = ops.hex_winner(filled, size, interpret=True)
    want = ref.hex_winner(filled, size)
    flood = jax.vmap(lambda b: hx.winner(b, spec))(filled)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flood))
    assert got.dtype == jnp.int8


def test_hex_winner_dispatch_agrees_with_kernel():
    """The auto dispatch the playout phase hits (compiled Pallas on TPU,
    jitted batched flood fill elsewhere) returns the same winners as the
    interpret-mode roll-dilation kernel — separate implementations on
    every backend (a Pallas body against a jnp while_loop), so non-vacuous
    on the CPU CI host too."""
    from repro.core import hex as hx
    size, W = 9, 12
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.fold_in(KEY, 99), W)
    filled = hx.random_fill_batch(
        jnp.tile(hx.empty_board(spec)[None], (W, 1)), 2, keys, spec)
    got = ops.hex_winner(filled, size)
    kernel = ops.hex_winner(filled, size, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kernel))


HEX_DELTAS = ((-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0))


def _serpentine(size: int, reach_bottom: bool = True) -> np.ndarray:
    """Black enters at (0, 0), runs along rows 1, 3, 5, ... joined at
    alternate ends, and ends on the bottom row: one path with white walls
    between its rows, so the flood advances one cell per step along it.
    Without ``reach_bottom`` the bottom row is all white, and the path
    stops one cell short of it."""
    b = np.full((size, size), 2, np.int8)
    b[0, 0] = 1
    for i, r in enumerate(range(1, size, 2)):
        b[r] = 1
        if r + 1 < size:
            b[r + 1, size - 1 if i % 2 == 0 else 0] = 1
    if not reach_bottom:
        b[size - 1] = 2
    return b


def _comb(size: int) -> np.ndarray:
    """Black enters at (0, 0) onto a spine along row 1, with dead-end teeth
    down every other column to one row short of the bottom; only the far
    tooth touches the bottom row (odd sizes)."""
    b = np.full((size, size), 2, np.int8)
    b[0, 0] = 1
    b[1] = 1
    b[1:size - 1, ::2] = 1
    b[size - 1, size - 1] = 1
    return b


def _flood_steps(board: np.ndarray) -> int:
    """Dilation steps until black's reach from the top row stops growing
    (breadth-first distances on the Hex neighborhood)."""
    size = board.shape[0]
    dist = np.full(board.shape, -1)
    dist[0, board[0] == 1] = 0
    frontier = [(0, c) for c in range(size) if board[0, c] == 1]
    while frontier:
        nxt = []
        for r, c in frontier:
            for dr, dc in HEX_DELTAS:
                rr, cc = r + dr, c + dc
                if (0 <= rr < size and 0 <= cc < size and board[rr, cc] == 1
                        and dist[rr, cc] < 0):
                    dist[rr, cc] = dist[r, c] + 1
                    nxt.append((rr, cc))
        frontier = nxt
    return int(dist.max())


def _long_path_batch(board: np.ndarray) -> np.ndarray:
    """The board, its half turn (the path entered from the bottom), and its
    transpose with colors swapped (the same path as white's, left to
    right, so the other color wins), flattened: the half turn and the
    transpose keep the Hex neighborhood."""
    swapped = np.where(board.T == 1, 2, 1).astype(np.int8)
    return np.stack([board, board[::-1, ::-1], swapped]).reshape(3, -1)


def _assert_hex_winners_agree(filled: np.ndarray, size: int) -> np.ndarray:
    """Interpret-mode kernel == scalar flood fill == pointer doubling."""
    from repro.core import hex as hx
    spec = hx.HexSpec(size)
    boards = jnp.asarray(filled)
    got = np.asarray(ops.hex_winner(boards, size, interpret=True))
    scalar = np.asarray(jax.vmap(lambda b: hx.winner(b, spec))(boards))
    doubling = np.asarray(ref.hex_winner(boards, size))
    np.testing.assert_array_equal(got, scalar)
    np.testing.assert_array_equal(got, doubling)
    return got


@pytest.mark.parametrize("case,size,black_wins,min_steps", [
    ("serpentine", 11, True, 50),
    ("serpentine", 13, True, 70),
    ("near_miss", 11, False, 50),
    ("near_miss", 13, False, 70),
    ("comb", 11, True, 18),
    ("comb", 13, True, 22),
])
def test_hex_winner_kernel_long_paths(case, size, black_wins, min_steps):
    """Boards whose fixpoint takes many dilation steps: the kernel's fixed
    n_cells - 1 steps must reach it (a step bound that falls short reads a
    connected black path as white's win)."""
    board = {"serpentine": _serpentine,
             "near_miss": lambda s: _serpentine(s, reach_bottom=False),
             "comb": _comb}[case](size)
    assert _flood_steps(board) >= min_steps
    got = _assert_hex_winners_agree(_long_path_batch(board), size)
    w = 1 if black_wins else 2
    assert got.tolist() == [w, w, 3 - w]


@pytest.mark.parametrize("W", [7, 244, 1030])
def test_hex_winner_kernel_batch_shapes(W):
    """W not a multiple of 8 (padded rows), the paper's 244 lanes in one
    block, and a W above the block's row cap (a grid of several blocks),
    with the long-path boards at the first and the last lanes."""
    from repro.core import hex as hx
    size = 11
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.fold_in(KEY, 1000 + W), W)
    filled = np.array(hx.random_fill_batch(
        jnp.zeros((W, spec.n_cells), jnp.int8), 1, keys, spec))
    long_paths = np.concatenate([_long_path_batch(_serpentine(size)),
                                 _long_path_batch(_comb(size))])
    k = min(W, len(long_paths))
    filled[:k] = long_paths[:k]
    filled[W - k:] = long_paths[:k]
    got = _assert_hex_winners_agree(filled, size)
    assert set(got.tolist()) == {1, 2}


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 64), d=st.integers(1, 300),
       dt=st.sampled_from(["float32", "bfloat16"]))
def test_rmsnorm_property(n, d, dt):
    dtype = jnp.dtype(dt)
    x = jax.random.normal(jax.random.fold_in(KEY, n * d), (n, d), dtype)
    w = 1 + 0.1 * jax.random.normal(jax.random.fold_in(KEY, d), (d,),
                                    jnp.float32)
    got = ops.rmsnorm(x, w, 1e-5)
    want = ref.rmsnorm(x, w, 1e-5)
    tol = 3e-2 if dt == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    assert got.dtype == x.dtype


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rmsnorm as model_rmsnorm
    x = jax.random.normal(KEY, (4, 32, 256), jnp.float32)
    w = jnp.ones((256,))
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, w, 1e-5)),
                               np.asarray(model_rmsnorm(x, w, 1e-5)),
                               atol=1e-5, rtol=1e-5)
