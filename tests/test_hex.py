"""Hex environment tests: flood-fill vs union-find oracle, Hex theorem property."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hex as hx


# ---------------------------------------------------------------- oracle ----
class UnionFind:
    def __init__(self, n):
        self.p = list(range(n))

    def find(self, x):
        while self.p[x] != x:
            self.p[x] = self.p[self.p[x]]
            x = self.p[x]
        return x

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.p[ra] = rb


def oracle_connected(board: np.ndarray, player: int, size: int) -> bool:
    """Union-find connectivity — the paper's own data structure."""
    n = size * size
    uf = UnionFind(n + 2)  # two virtual edge nodes
    A, B = n, n + 1
    nbr = hx.neighbor_table(size)
    for i in range(n):
        if board[i] != player:
            continue
        r, c = divmod(i, size)
        if player == 1:  # black: top/bottom
            if r == 0:
                uf.union(i, A)
            if r == size - 1:
                uf.union(i, B)
        else:  # white: left/right
            if c == 0:
                uf.union(i, A)
            if c == size - 1:
                uf.union(i, B)
        for j in nbr[i]:
            if j < n and board[j] == player:
                uf.union(i, int(j))
    return uf.find(A) == uf.find(B)


def random_board(rng: np.random.Generator, size: int, fill: float) -> np.ndarray:
    n = size * size
    b = np.zeros(n, dtype=np.int8)
    k = int(n * fill)
    idx = rng.permutation(n)[:k]
    # alternate stones like a real game
    for t, i in enumerate(idx):
        b[i] = 1 if t % 2 == 0 else 2
    return b


# ----------------------------------------------------------------- tests ----
@pytest.mark.parametrize("size", [3, 5, 7, 11])
def test_connected_matches_union_find(size):
    spec = hx.HexSpec(size)
    rng = np.random.default_rng(0)
    f = jax.jit(lambda b, p: hx.connected(b, p, spec))
    for fill in (0.0, 0.3, 0.6, 1.0):
        for _ in range(8):
            b = random_board(rng, size, fill)
            for player in (1, 2):
                got = bool(f(jnp.asarray(b), jnp.int8(player)))
                want = oracle_connected(b, player, size)
                assert got == want, (size, fill, player, b.reshape(size, size))


def test_straight_line_wins():
    size = 5
    spec = hx.HexSpec(size)
    b = np.zeros(size * size, dtype=np.int8)
    b[2::size] = 1  # black column -> top..bottom
    assert bool(hx.connected(jnp.asarray(b), jnp.int8(1), spec))
    assert not bool(hx.connected(jnp.asarray(b), jnp.int8(2), spec))
    b2 = np.zeros(size * size, dtype=np.int8)
    b2[2 * size : 3 * size] = 2  # white row -> left..right
    assert bool(hx.connected(jnp.asarray(b2), jnp.int8(2), spec))
    assert not bool(hx.connected(jnp.asarray(b2), jnp.int8(1), spec))


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), size=st.sampled_from([3, 5, 7]))
def test_hex_theorem_exactly_one_winner(seed, size):
    """A filled board has exactly one winner (the Hex no-draw theorem).

    This is the property the playout relies on: winner() may run a single
    flood-fill because the two outcomes are mutually exclusive and exhaustive.
    """
    spec = hx.HexSpec(size)
    key = jax.random.PRNGKey(seed)
    board = hx.random_fill(hx.empty_board(spec), jnp.int32(1), key, spec)
    b = np.asarray(board)
    assert (b != 0).all()
    black = oracle_connected(b, 1, size)
    white = oracle_connected(b, 2, size)
    assert black != white  # exactly one
    assert int(hx.winner(board, spec)) == (1 if black else 2)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_random_fill_alternates_fairly(seed):
    """Filling an empty odd-size board gives to_move ceil(n/2) stones."""
    size = 5
    spec = hx.HexSpec(size)
    key = jax.random.PRNGKey(seed)
    board = hx.random_fill(hx.empty_board(spec), jnp.int32(2), key, spec)
    b = np.asarray(board)
    n = size * size
    assert (b == 2).sum() == (n + 1) // 2  # to_move goes first
    assert (b == 1).sum() == n // 2


def test_random_fill_preserves_existing_stones():
    size = 5
    spec = hx.HexSpec(size)
    b0 = hx.empty_board(spec).at[3].set(1).at[7].set(2)
    out = hx.random_fill(b0, jnp.int32(1), jax.random.PRNGKey(3), spec)
    assert int(out[3]) == 1 and int(out[7]) == 2
    assert (np.asarray(out) != 0).all()


def test_replay_moves():
    size = 5
    spec = hx.HexSpec(size)
    moves = jnp.array([0, 6, 12, 18, 24, 0, 0], dtype=jnp.int32)
    board = hx.replay_moves(moves, jnp.int32(5), jnp.int32(1), spec)
    b = np.asarray(board)
    assert b[0] == 1 and b[6] == 2 and b[12] == 1 and b[18] == 2 and b[24] == 1
    assert (b != 0).sum() == 5


def test_playout_value_perspectives_sum_to_one():
    size = 5
    spec = hx.HexSpec(size)
    key = jax.random.PRNGKey(11)
    v1 = hx.playout_value(hx.empty_board(spec), jnp.int32(1), jnp.int32(1), key, spec)
    v2 = hx.playout_value(hx.empty_board(spec), jnp.int32(1), jnp.int32(2), key, spec)
    assert float(v1) + float(v2) == 1.0


def test_playout_vmappable():
    size = 5
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.PRNGKey(0), 16)
    boards = jnp.tile(hx.empty_board(spec)[None], (16, 1))
    f = jax.jit(jax.vmap(lambda b, k: hx.playout(b, jnp.int32(1), k, spec)))
    ws = np.asarray(f(boards, keys))
    assert set(np.unique(ws)).issubset({1, 2})
    # an empty board should not be deterministic across 16 random playouts
    assert len(set(ws.tolist())) == 2
