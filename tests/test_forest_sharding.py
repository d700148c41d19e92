"""Ensemble-axis sharding: padding math, pad-member inertness, placement,
the cross-device merge, and sharded-vs-unsharded bit-identity.

The multi-device cases run in one child process that sees four virtual CPU
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``, set before
JAX starts, since the device count is fixed when JAX starts), as
tests/test_dryrun.py runs its meshes. Each is written as an ``@in_child``
test: the module's ``child_results`` fixture runs every such body once in
the child (``python tests/test_forest_sharding.py`` is that child), and
the test of the same name here reads its own result, so each case still
passes or fails on its own."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hex as hx
from repro.core import scheduler as sched
from repro.core.gscpm import GSCPMConfig, fold_task_keys
from repro.core.root_parallel import (
    _init_forest_placed,
    check_forest_invariants,
    ensemble_mesh,
    ensemble_sharding,
    forest_summary,
    gscpm_search_batch,
    init_sync_state,
    merged_root_stats,
    pad_forest_members,
    place_forest,
    run_schedule_round_forest,
    sync_root_stats,
)
from repro.core.tree import forest_size, init_forest, reroot_forest

SIZE = 5
N_MOVES = SIZE * SIZE
N_DEV = len(jax.devices())
CHILD_DEVICES = 4
ROOT = Path(__file__).resolve().parents[1]

CHILD_BODIES: dict = {}


def in_child(body):
    """Register ``body`` to run in the four-device child; the returned test,
    of the same name, asserts that it passed there."""
    CHILD_BODIES[body.__name__] = body

    def test(child_results):
        assert child_results[body.__name__] == "ok", \
            child_results[body.__name__]

    test.__name__, test.__doc__ = body.__name__, body.__doc__
    return test


@pytest.fixture(scope="module")
def child_results():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (str(ROOT / "src"),
                               os.environ.get("PYTHONPATH")) if p),
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count="
                         f"{CHILD_DEVICES}".strip())
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def cfg(**kw):
    base = dict(board_size=SIZE, n_playouts=96, n_tasks=8, n_workers=4,
                tree_cap=768, select_noise=1e-3)
    base.update(kw)
    return GSCPMConfig(**base)


def host(forest) -> dict:
    return {k: np.asarray(v) for k, v in forest._asdict().items()}


def assert_forests_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------- padding math ----
def test_ensemble_sharding_defaults_to_visible_devices():
    sharding, padded = ensemble_sharding(5, mesh=None)
    if N_DEV == 1:
        assert sharding is None and padded == 5     # no mesh -> identity
    else:
        assert sharding is not None
        assert padded % N_DEV == 0 and padded >= 5


def test_single_device_has_no_mesh():
    if N_DEV == 1:
        assert ensemble_mesh() is None
    else:
        assert ensemble_mesh() is not None


@in_child
def test_ensemble_sharding_pads_to_next_device_multiple():
    mesh = ensemble_mesh()
    for n in range(1, 2 * N_DEV + 1):
        sharding, padded = ensemble_sharding(n, mesh)
        assert sharding is not None
        assert padded % N_DEV == 0 and padded >= n
        assert padded - n < N_DEV          # NEXT multiple, not a later one


def test_pad_forest_members_appends_inert_init_trees():
    c = cfg()
    forest = init_forest(3, c.tree_cap, N_MOVES, 1)
    boards = jnp.tile(hx.empty_board(hx.HexSpec(SIZE))[None, :], (3, 1))
    pf, pb = pad_forest_members(forest, boards, 5, c, 1)
    assert forest_size(pf) == 5 and pb.shape[0] == 5
    # pad members are freshly initialized trees: a single root, no stats
    assert np.asarray(pf.n_nodes[3:]).tolist() == [1, 1]
    assert float(np.asarray(pf.visits[3:]).sum()) == 0.0
    # real members are untouched
    np.testing.assert_array_equal(np.asarray(pf.visits[:3]),
                                  np.asarray(forest.visits))


# ---------------------------------------------------------- bit-identity ----
@in_child
def test_sharded_batch_bit_identical_to_unsharded():
    """The whole tentpole contract: shard_map over the ensemble mesh (with
    padding when E % n_devices != 0) changes NOTHING about the answer —
    merged stats, per-member stats, and the forest summary are bitwise
    equal to the single-device vmap path."""
    board = hx.empty_board(hx.HexSpec(SIZE))
    e = N_DEV - 1              # forces padding
    c = cfg(n_playouts=128)
    f_off, s_off = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(7),
                                      n_trees=e, shard="off")
    f_on, s_on = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(7),
                                    n_trees=e, shard="require")
    assert s_off["sharded"] is False and s_on["sharded"] is True
    assert s_on["n_devices"] == N_DEV
    assert s_on["mesh_shape"] == {"ens": N_DEV}
    assert s_on["padded_members"] == N_DEV - e
    assert forest_size(f_on) == e           # pads sliced off before return
    assert_forests_equal(f_off, f_on)
    for k in ("best_move_sum", "best_move_vote", "member_best_moves",
              "tree_nodes", "playouts"):
        assert s_off[k] == s_on[k], k
    check_forest_invariants(f_on)


@in_child
def test_sharded_periodic_sync_bit_identical():
    """sync_root_stats is the ONLY cross-shard exchange; its delta-tracked
    merge must stay exact when the forest lives on a mesh."""
    board = hx.empty_board(hx.HexSpec(SIZE))
    c = cfg(n_playouts=128, n_tasks=16)
    f_off, s_off = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(8),
                                      n_trees=3, merge_every=1, shard="off")
    f_on, s_on = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(8),
                                    n_trees=3, merge_every=1,
                                    shard="require")
    assert s_on["n_syncs"] == s_off["n_syncs"] >= 2
    mv_off, mw_off = merged_root_stats(f_off, N_MOVES)
    mv_on, mw_on = merged_root_stats(f_on, N_MOVES)
    np.testing.assert_array_equal(np.asarray(mv_off), np.asarray(mv_on))
    np.testing.assert_array_equal(np.asarray(mw_off), np.asarray(mw_on))
    # after the final sync every member's root carries the ensemble total
    np.testing.assert_allclose(np.asarray(f_on.visits[:, 0]),
                               float(s_on["playouts"]))
    summ_off = jax.device_get(forest_summary(f_off, N_MOVES))
    summ_on = jax.device_get(forest_summary(f_on, N_MOVES))
    for k in summ_off:
        np.testing.assert_array_equal(np.asarray(summ_off[k]),
                                      np.asarray(summ_on[k]), err_msg=k)


@in_child
def test_sharded_metrics_bit_identical():
    board = hx.empty_board(hx.HexSpec(SIZE))
    c = cfg(metrics=True)
    _, s_off = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(9),
                                  n_trees=2, shard="off")
    _, s_on = gscpm_search_batch(board, 1, c, jax.random.PRNGKey(9),
                                 n_trees=2, shard="require")
    assert s_off["metrics"] == s_on["metrics"]


def test_shard_require_raises_on_single_device():
    if N_DEV > 1:
        pytest.skip("only meaningful with one device")
    board = hx.empty_board(hx.HexSpec(SIZE))
    with pytest.raises(RuntimeError, match="require"):
        gscpm_search_batch(board, 1, cfg(), jax.random.PRNGKey(0),
                           n_trees=2, shard="require")


# ------------------------------------------------------------------ reroot ----
@in_child
def test_reroot_forest_round_trip_under_sharding():
    """Search sharded -> re-root every member -> warm-continue sharded:
    the whole cross-move loop survives device placement, bit-identical to
    the unsharded loop."""
    board = hx.empty_board(hx.HexSpec(SIZE))
    c = cfg(n_playouts=96)

    def loop(shard):
        forest, stats = gscpm_search_batch(
            board, 1, c, jax.random.PRNGKey(11), n_trees=2, shard=shard)
        mv, _ = merged_root_stats(forest, N_MOVES)
        move = int(jnp.argmax(mv))
        warm = reroot_forest(forest, move)
        nb = jnp.tile(board[None, :].at[:, move].set(1), (2, 1))
        forest2, stats2 = gscpm_search_batch(
            nb, 2, c, jax.random.PRNGKey(12), forest=warm, shard=shard)
        return forest2, move

    f_off, m_off = loop("off")
    f_on, m_on = loop("require")
    assert m_off == m_on
    assert_forests_equal(f_off, f_on)


# ------------------------------------------------- placement and the merge ----
@in_child
def test_cold_forest_is_built_in_place():
    """A cold forest is built by one program whose output is already
    sharded: each device builds and holds only its own members (pads
    included), the same trees ``init_forest`` builds."""
    mesh = ensemble_mesh()
    c = cfg()
    boards = jnp.tile(hx.empty_board(hx.HexSpec(SIZE))[None, :], (5, 1))
    sharding, Ep = ensemble_sharding(5, mesh)
    forest, pboards, keys = place_forest(boards, 1, c, jax.random.key(0),
                                         mesh)
    assert forest_size(forest) == Ep == 2 * N_DEV and pboards.shape[0] == Ep
    for leaf in jax.tree.leaves((forest, pboards, keys)):
        assert leaf.sharding == sharding
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == N_DEV
        assert all(s.data.shape[0] == Ep // N_DEV for s in shards)
    assert_forests_equal(forest, init_forest(Ep, c.tree_cap, N_MOVES, 1))
    compiled = _init_forest_placed.lower(
        jnp.ones((Ep,), jnp.int32), n_trees=Ep, cap=c.tree_cap,
        max_children=N_MOVES, sharding=sharding).compile()
    per_member = sum(x.nbytes for x in jax.tree.leaves(
        init_forest(1, c.tree_cap, N_MOVES, 1)))
    # what one device writes: its own members, never the whole forest
    assert (compiled.memory_analysis().output_size_in_bytes
            < (Ep // N_DEV + 1) * per_member)
    assert not re.search(r"all-gather|all-to-all|collective-permute",
                         compiled.as_text())


@in_child
def test_merge_exchanges_only_root_stats():
    """The merge on a sharded forest moves one all-reduce of the per-move
    and root totals, (n_moves,) and scalars, across devices, keeps the
    forest's sharding and updates the donated forest in place; a search
    builds one round program and one merge program for all its rounds, and
    the next search none: no round waits on a reshard of the merge's
    output."""
    mesh = ensemble_mesh()
    c = cfg(tree_cap=640)                   # a class no other check builds
    board = hx.empty_board(hx.HexSpec(SIZE))
    built = []

    def record(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(record)
    try:
        for seed in (1, 2):
            built.clear()
            gscpm_search_batch(board, 1, c, jax.random.key(seed),
                               n_trees=N_DEV, merge_every=1, shard="require")
            programs = sorted(n for n in built if n in (
                "jit(_sharded_chunk)", "jit(sync_root_stats)"))
            assert programs == (
                ["jit(_sharded_chunk)", "jit(sync_root_stats)"]
                if seed == 1 else []), built
    finally:
        jax.monitoring.unregister_event_duration_listener(record)

    forest, _, _ = place_forest(jnp.tile(board[None, :], (N_DEV, 1)), 1, c,
                                jax.random.key(0), mesh)
    state = jax.device_put(init_sync_state(N_DEV, N_MOVES),
                           forest.visits.sharding)
    compiled = sync_root_stats.lower(forest, state, N_MOVES).compile()
    text = compiled.as_text()
    assert not re.search(r"all-gather|all-to-all|collective-permute|"
                         r"reduce-scatter", text)
    reduces = re.findall(r"= \(?([^=]*?)\)? all-reduce(?:-start)?\(", text)
    assert len(reduces) == 1, reduces
    shapes = re.findall(r"[a-z0-9]+\[([0-9,]*)\]", reduces[0])
    assert set(shapes) <= {"", str(N_MOVES)}, reduces
    for s in jax.tree.leaves(compiled.output_shardings):
        assert s == forest.visits.sharding
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * sum(
        x.nbytes for x in jax.tree.leaves(forest)) / N_DEV


def numpy_merge(tree: dict, injected: dict, n_moves: int):
    """The merge stated plainly, on host arrays of a forest and what earlier
    merges injected. Each member's own work is what its tree holds less
    what was injected into it; a member's root child for move a becomes its
    own work plus the sum of the other members' own work on a, for the
    moves it holds; its root, the sum of every member's own root visits.
    Returns the expected visits and wins of every node, and what the merge
    has injected afterwards."""
    E = tree["visits"].shape[0]
    dense_v = np.zeros((E, n_moves), np.float32)
    dense_w = np.zeros((E, n_moves), np.float32)
    node_of = np.full((E, n_moves), -1)
    for e in range(E):
        for s in range(tree["n_children"][e, 0]):
            node = tree["children"][e, 0, s]
            a = tree["move"][e, node]
            dense_v[e, a] = tree["visits"][e, node]
            dense_w[e, a] = tree["wins"][e, node]
            node_of[e, a] = node
    held = node_of >= 0
    own_v = dense_v - injected["visits"]
    own_w = dense_w - injected["wins"]
    own_rv = tree["visits"][:, 0] - injected["root_visits"]
    own_rw = tree["wins"][:, 0] - injected["root_wins"]
    others = lambda x: np.stack(  # noqa: E731
        [np.delete(x, e, axis=0).sum(axis=0) for e in range(E)])
    visits, wins = tree["visits"].copy(), tree["wins"].copy()
    for e, a in zip(*np.nonzero(held)):
        visits[e, node_of[e, a]] = own_v[e, a] + others(own_v)[e, a]
        wins[e, node_of[e, a]] = own_w[e, a] + others(own_w)[e, a]
    visits[:, 0] = own_rv + others(own_rv)
    wins[:, 0] = own_rw + others(own_rw)
    now = {"visits": np.where(held, others(own_v), 0.0),
           "wins": np.where(held, others(own_w), 0.0),
           "root_visits": others(own_rv), "root_wins": others(own_rw)}
    return visits, wins, now


@in_child
def test_sharded_merge_matches_numpy_reference():
    """hex11-forest8's shape cut small: 8 trees on the four devices, 16
    lanes, 2 rounds, a merge after each. Each merge of the sharded forest
    equals the merge stated in numpy (``numpy_merge``) on the forest read
    back before it, every root then holds the ensemble's playouts so far,
    and the whole search equals the unsharded one bit for bit."""
    size, E = 7, 8
    n_moves = size * size
    c = GSCPMConfig(board_size=size, n_workers=16, n_tasks=32,
                    n_playouts=128, tree_cap=256, select_noise=1e-3)
    board = hx.empty_board(hx.HexSpec(size))
    key = jax.random.key(2026)
    mesh = ensemble_mesh()
    forest, boards, member_keys = place_forest(
        jnp.tile(board[None, :], (E, 1)), 1, c, key, mesh)
    state = jax.device_put(init_sync_state(E, n_moves),
                           forest.visits.sharding)
    injected = {k: np.asarray(v) for k, v in state._asdict().items()}
    schedule = sched.make_schedule(c.n_playouts, c.n_tasks, c.n_workers,
                                   c.scheduler)
    assert len(schedule) == 2
    cp = jnp.asarray(c.cp, jnp.float32)
    playouts = 0
    for rnd in schedule:
        forest = run_schedule_round_forest(forest, boards, c, member_keys,
                                           rnd, cp, n_real=E, mesh=mesh)
        playouts += E * int(rnd.active.sum()) * int(rnd.m)
        before = host(forest)
        visits, wins, injected = numpy_merge(before, injected, n_moves)
        forest, state = sync_root_stats(forest, state, n_moves)
        after = host(forest)
        np.testing.assert_array_equal(after["visits"], visits)
        np.testing.assert_array_equal(after["wins"], wins)
        for k in before.keys() - {"visits", "wins"}:
            np.testing.assert_array_equal(after[k], before[k], err_msg=k)
        for k, v in state._asdict().items():
            np.testing.assert_array_equal(np.asarray(v), injected[k],
                                          err_msg=k)
        assert (after["visits"][:, 0] == playouts).all()
    assert playouts == E * c.n_playouts
    for shard in ("require", "off"):
        whole, st = gscpm_search_batch(board, 1, c, key, n_trees=E,
                                       merge_every=1, shard=shard)
        assert st["n_syncs"] == 2
        assert_forests_equal(whole, forest)
    check_forest_invariants(forest, discrete_credits=True)


# ------------------------------------------------------------- key streams ----
def test_member_key_streams_ignore_padding():
    """Real members' RNG streams must not depend on how far the ensemble
    was padded — fold_task_keys(key, arange(Ep))[:E] == fold over arange(E),
    which is what makes padded and unpadded runs bit-identical."""
    key = jax.random.key(5)
    a = fold_task_keys(key, jnp.arange(3, dtype=jnp.int32))
    b = fold_task_keys(key, jnp.arange(8, dtype=jnp.int32))[:3]
    np.testing.assert_array_equal(jax.random.key_data(a),
                                  jax.random.key_data(b))


def main() -> int:
    """The child: run every ``@in_child`` body, print {name: "ok" or the
    traceback} as the last line."""
    if N_DEV != CHILD_DEVICES:
        print(f"the child needs {CHILD_DEVICES} devices, JAX sees {N_DEV}",
              file=sys.stderr)
        return 1
    results = {}
    for name, body in CHILD_BODIES.items():
        try:
            body()
            results[name] = "ok"
        except Exception:  # noqa: BLE001 — each test reads its own result
            results[name] = traceback.format_exc()
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
