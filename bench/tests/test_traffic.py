"""The traffic generator."""

import numpy as np
import pytest

from harness import traffic


def test_positions_are_legal_and_undecided():
    rng = np.random.default_rng(3)
    for k in (0, 1, 20, 40):
        board, tm = traffic.random_position(rng, 11, k)
        assert (board == 1).sum() - (board == 2).sum() == k % 2
        assert tm == (1 if k % 2 == 0 else 2)
        assert not traffic.connected(board, 1, 11)
        assert not traffic.connected(board, 2, 11)


def test_connected_follows_each_side_edges():
    board = np.zeros(121, np.int8)
    board[np.arange(11) * 11 + 3] = 1          # a black column: top to bottom
    assert traffic.connected(board, 1, 11) and not traffic.connected(board, 2, 11)
    board = np.zeros(121, np.int8)
    board[44:55] = 2                           # a white row: left to right
    assert traffic.connected(board, 2, 11) and not traffic.connected(board, 1, 11)


def test_closed_loop_starts_empty_and_repeats_by_seed():
    a = traffic.closed_loop(traffic.load("search"), 9, 11)
    b = traffic.closed_loop(traffic.load("search"), 9, 11)
    first = next(a)
    assert first["index"] == 0 and not first["board"].any()
    next(b)
    for _ in range(3):
        x, y = next(a), next(b)
        assert np.array_equal(x["board"], y["board"])
        assert 2 <= np.count_nonzero(x["board"]) <= 20


def test_closed_loop_fixed_openings_vary_only_the_keys():
    search = traffic.load("search")
    a = traffic.closed_loop(search, 9, 11)
    b = traffic.closed_loop(search, 2**33 + 1, 11)
    for _ in range(4):
        x, y = next(a), next(b)
        assert np.array_equal(x["board"], y["board"])
        assert x["to_move"] == y["to_move"]
        assert x["key_seed"] != y["key_seed"]
    free = {k: v for k, v in search.items() if k != "schedule_seed"}
    c, d = traffic.closed_loop(free, 9, 11), traffic.closed_loop(free, 10, 11)
    next(c), next(d)
    assert not np.array_equal(next(c)["board"], next(d)["board"])
