"""``tree.backup_paths`` scatters only the path levels that hold a node.

Its oracle is the one full-width scatter it replaced, kept here: every
(lane, column) entry of the (W, D) path array, pads included, added at once
into ``visits`` and ``wins``. The two must agree bit for bit, on a single
tree and under ``jax.vmap`` over a forest whose members differ in depth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tree import backup_paths, init_tree, path_levels

CAP, C, W, D = 96, 6, 10, 11   # D odd: the last trip's block is padded


def full_width_backup(tree, paths, values, weights):
    """The backup as one scatter over every column of every lane."""
    W, D = paths.shape
    flat = paths.reshape(-1)
    mover = 3 - tree.to_move[flat]
    vals = jnp.repeat(values.astype(jnp.int32), D)
    win = jnp.where(vals == 0, 0.5, (mover == vals).astype(jnp.float32))
    w = jnp.repeat(weights, D) * (flat != tree.cap)
    visits = tree.visits.at[flat].add(w).at[tree.cap].set(0.0)
    wins = tree.wins.at[flat].add(w * win).at[tree.cap].set(0.0)
    return tree._replace(visits=visits, wins=wins)


def grown_tree(rng):
    """A tree whose statistics already hold counts and half-credits, and
    whose nodes' movers are mixed."""
    tree = init_tree(CAP, C, 1)
    visits = rng.integers(0, 50, CAP + 1).astype(np.float32)
    wins = np.floor(visits * rng.random(CAP + 1) * 2) / 2
    visits[CAP] = wins[CAP] = 0.0
    return tree._replace(
        to_move=jnp.asarray(rng.integers(1, 3, CAP + 1), jnp.int32),
        visits=jnp.asarray(visits), wins=jnp.asarray(wins, jnp.float32),
        n_nodes=jnp.int32(CAP))


def lane_paths(rng, depths, pool=4):
    """Lane w's path: the root, then ``depths[w] + 1`` nodes drawn from a
    small pool per level (so lanes share nodes), then pads."""
    paths = np.full((W, D), CAP, np.int32)
    paths[:, 0] = 0
    for w, d in enumerate(depths):
        for col in range(1, d + 2):
            paths[w, col] = 1 + (col * pool + rng.integers(0, pool)) % (CAP - 1)
    return paths


def case_inputs(case, rng):
    """(paths, values, weights) for one named case."""
    values = rng.integers(1, 3, W).astype(np.int8)
    weights = np.ones(W, np.float32)
    depths = rng.integers(0, 3, W)
    if case == "masked":
        weights[rng.permutation(W)[:4]] = 0.0
    elif case == "draws":
        values[rng.permutation(W)[:5]] = 0
    elif case == "half_batch":
        weights[W // 2:] = 0.0
    elif case == "one_full_lane":
        depths[3] = D - 2                        # columns 0 .. D-1 all held
    paths = lane_paths(rng, depths)
    if case == "root_only":
        paths[:, 1:] = CAP
    return paths, values, weights


CASES = ["shallow_duplicates", "masked", "draws", "root_only",
         "one_full_lane", "half_batch"]


@pytest.mark.parametrize("forest", [False, True], ids=["tree", "forest"])
@pytest.mark.parametrize("case", CASES)
def test_backup_matches_full_width_scatter(case, forest):
    rng = np.random.default_rng(CASES.index(case))
    tree = grown_tree(rng)
    paths, values, weights = case_inputs(case, rng)
    if case == "one_full_lane":
        assert int(path_levels(jnp.asarray(paths), CAP)) == D
    elif case == "root_only":
        assert int(path_levels(jnp.asarray(paths), CAP)) == 1
    new, old = jax.jit(backup_paths), jax.jit(full_width_backup)
    if forest:
        # a second member of another depth: the loop runs to the deeper one
        other = case_inputs("root_only" if case == "one_full_lane"
                            else "one_full_lane", rng)
        tree = jax.tree.map(lambda a, b: jnp.stack([a, b]), tree,
                            grown_tree(rng))
        paths, values, weights = (np.stack([a, b]) for a, b in
                                  zip((paths, values, weights), other))
        new, old = jax.vmap(new), jax.vmap(old)
    args = (tree, jnp.asarray(paths), jnp.asarray(values),
            jnp.asarray(weights))
    got, want = new(*args), old(*args)
    for field in got._fields:
        g, w = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
        assert np.array_equal(g, w), field
    assert not np.array_equal(np.asarray(got.visits), np.asarray(tree.visits))
