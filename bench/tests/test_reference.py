"""The plain reference against the program at small sizes on the CPU, and
the audit against trees broken on purpose."""

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference as ref
from harness import traffic

SIZES = [  # board, lanes, playouts, tasks, tree capacity, opening stones
    (5, 16, 16 * 8 * 3, 48, 1 << 10, 0),
    (11, 32, 32 * 16 * 2, 64, 1 << 12, 6),
    (7, 24, 24 * 4 * 5, 100, 200, 3),        # the capacity runs out
]


def program_search(size, W, npo, ntasks, cap, board, tm, seed):
    from repro.core.gscpm import GSCPMConfig, gscpm_search

    cfg = GSCPMConfig(board_size=size, n_workers=W, n_playouts=npo,
                      n_tasks=ntasks, tree_cap=cap)
    tree, st = gscpm_search(jnp.asarray(board), tm, cfg, jax.random.key(seed))
    return {k: np.asarray(getattr(tree, k)) for k in tree._fields}, st


def cfg_dict(size, W, npo, ntasks, cap):
    return dict(board_size=size, n_workers=W, tree_cap=cap, cp=1.0,
                select_noise=1e-3, n_playouts=npo, n_tasks=ntasks)


@pytest.mark.parametrize("size,W,npo,ntasks,cap,stones", SIZES)
def test_replay_builds_the_programs_tree(size, W, npo, ntasks, cap, stones):
    board, tm = traffic.random_position(np.random.default_rng(size), size,
                                        stones)
    seed = 2**31 - 5
    tree, st = program_search(size, W, npo, ntasks, cap, board, tm, seed)
    rep = ref.make_replay(cfg_dict(size, W, npo, ntasks, cap), board, tm,
                          seed, npo // W + W)
    rep.run(npo, ntasks)
    k = rep.tree.n_nodes
    assert k == int(tree["n_nodes"])
    for f in ("parent", "move", "to_move", "n_children", "visits", "wins"):
        assert np.array_equal(getattr(rep.tree, f)[:k], tree[f][:k]), f
    assert ref.best_child_move(tree) == st["best_move"]
    assert rep.root_answer()["best_move"] == st["best_move"]
    assert sum(ref.audit_tree(tree, board, tm, st["playouts"]).values()) == 0
    pre = ref.replay_prefix(cfg_dict(size, W, npo, ntasks, cap), board, tm,
                            seed, 3, guide=tree).tree
    assert ref.prefix_mismatch(pre, tree["parent"], tree["move"],
                               tree["children"], tree["n_children"],
                               int(tree["n_nodes"])) == 0


def bfs_black_joins(board, size):
    grid = board.reshape(size, size)
    todo = deque((0, c) for c in range(size) if grid[0, c] == 1)
    seen = set(todo)
    while todo:
        r, c = todo.popleft()
        if r == size - 1:
            return True
        for dr, dc in ((-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0)):
            q = (r + dr, c + dc)
            if (0 <= q[0] < size and 0 <= q[1] < size and q not in seen
                    and grid[q] == 1):
                seen.add(q)
                todo.append(q)
    return False


def test_hex_winner_is_the_flood_fill():
    rng = np.random.default_rng(0)
    for size in (3, 5, 11):
        n = size * size
        boards = np.stack([np.where(rng.permutation(n) % 2 == 0, 1, 2)
                           for _ in range(64)]).astype(np.int8)
        got = ref.hex_winner(boards, size)
        want = [1 if bfs_black_joins(b, size) else 2 for b in boards]
        assert got.tolist() == want


def test_fill_alternates_from_the_side_to_move():
    boards = np.array([[0, 1, 0, 0, 2, 0]], np.int8)
    u = np.array([[0.9, 0.0, 0.1, 0.5, 0.0, 0.3]])
    filled = ref.fill_boards(boards, np.array([2]), u)
    # empties in (u, cell) order: 2, 5, 3, 0 -> colours 2, 1, 2, 1
    assert filled.tolist() == [[1, 1, 2, 2, 2, 1]]


@pytest.fixture(scope="module")
def searched():
    board = np.zeros(25, np.int8)
    tree, st = program_search(5, 16, 16 * 8 * 2, 32, 1 << 10, board, 1, 11)
    return tree, st, board


def audit(tree, board, playouts):
    return ref.audit_tree(tree, board, 1, playouts)


def test_audit_passes_a_sound_tree(searched):
    tree, st, board = searched
    assert sum(audit(tree, board, st["playouts"]).values()) == 0


def test_audit_finds_a_lost_visit(searched):
    tree, st, board = searched
    t = {k: v.copy() for k, v in tree.items()}
    leaf = int(t["n_nodes"]) - 1
    t["visits"][leaf] -= 1.0
    assert audit(t, board, st["playouts"])["visits"] > 0


def test_audit_finds_a_wrong_win(searched):
    tree, st, board = searched
    t = {k: v.copy() for k, v in tree.items()}
    kid = int(t["children"][0, 0])
    t["wins"][kid] = t["visits"][kid] + 1
    assert audit(t, board, st["playouts"])["wins"] > 0


def test_audit_finds_a_repeated_move(searched):
    tree, st, board = searched
    t = {k: v.copy() for k, v in tree.items()}
    a, b = t["children"][0, :2]
    t["move"][b] = t["move"][a]
    assert audit(t, board, st["playouts"])["moves"] > 0


def test_audit_finds_a_short_root(searched):
    tree, st, board = searched
    assert audit(tree, board, st["playouts"] + 1)["root"] == 1
