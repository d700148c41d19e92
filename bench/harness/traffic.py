"""The one traffic generator. A mix is a data file under ``bench/traffic``;
everything here reads its parameters from that file and its randomness from
``--seed`` alone.

``closed_loop``: one client searching moves back to back. Each move is a
fresh search of a position; the next starts only when the last has
answered. A ``schedule_seed`` in the mix fixes the sequence of positions,
so the run's seed draws the search keys alone.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
from scipy import ndimage

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"
_HEX = np.array([[0, 1, 1], [1, 1, 1], [1, 1, 0]], bool)


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The mix ``name``: ``<directory>/<name>.json``."""
    return json.loads((directory / f"{name}.json").read_text())


def connected(board: np.ndarray, player: int, size: int) -> bool:
    """Whether ``player``'s stones join that player's two edges (BLACK, 1:
    top and bottom; WHITE, 2: left and right)."""
    grid = board.reshape(size, size) == player
    if player == 2:
        grid = grid.T       # left/right becomes top/bottom, adjacency kept
    labels, _ = ndimage.label(grid, structure=_HEX)
    top, bottom = labels[0], labels[-1]
    return bool(np.intersect1d(top[top > 0], bottom[bottom > 0]).size)


def random_position(rng: np.random.Generator, size: int, n_stones: int):
    """A legal Hex position of ``n_stones`` stones played alternately from
    BLACK on random cells, redrawn until neither side has joined its edges.
    Returns (board int8, side to move)."""
    n = size * size
    while True:
        board = np.zeros(n, np.int8)
        cells = rng.permutation(n)[:n_stones]
        board[cells] = np.where(np.arange(n_stones) % 2 == 0, 1, 2)
        if not (connected(board, 1, size) or connected(board, 2, size)):
            return board, 1 if n_stones % 2 == 0 else 2


def closed_loop(spec: dict, seed: int, size: int):
    """Endless stream of moves: dicts with ``board``, ``to_move`` and the
    search's ``key_seed``. Move 0 is the empty board when the mix says
    ``first_move_empty``; later moves are random openings of
    ``stones[0]..stones[1]`` stones. With a ``schedule_seed`` in the mix the
    openings are one fixed sequence, and the run's seed draws the search
    keys alone."""
    rng = np.random.default_rng(seed)
    positions = (np.random.default_rng(spec["schedule_seed"])
                 if "schedule_seed" in spec else rng)
    lo, hi = spec["stones"]
    i = 0
    while True:
        if i == 0 and spec.get("first_move_empty", False):
            board, tm = np.zeros(size * size, np.int8), 1
        else:
            board, tm = random_position(
                positions, size, int(positions.integers(lo, hi + 1)))
        yield {"index": i, "board": board, "to_move": tm,
               "key_seed": int(rng.integers(0, 2**31 - 1))}
        i += 1
