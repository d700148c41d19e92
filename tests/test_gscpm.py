"""GSCPM core tests: oracle equivalence, tree invariants, schedulers, quality."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hex as hx
from repro.core import mcts, scheduler
from repro.core.gscpm import GSCPMConfig, expand_batch, gscpm_search
from repro.core.tree import best_child, check_invariants, init_tree, root_value

SIZE = 5


def cfg(**kw):
    base = dict(board_size=SIZE, n_playouts=256, n_tasks=8, n_workers=4,
                tree_cap=4096, select_noise=1e-3)
    base.update(kw)
    return GSCPMConfig(**base)


# ---------------------------------------------------------------- oracle ----
def test_w1_matches_sequential_oracle():
    """GSCPM with one lane, one task, no noise == sequential UCT, bit-exact.

    This pins the batched dedup-expansion + scatter-add backup machinery to
    the scalar reference implementation under an identical RNG schedule.
    """
    key = jax.random.PRNGKey(7)
    board = hx.empty_board(hx.HexSpec(SIZE))
    n = 128
    t_seq, s_seq = mcts.uct_search(board, 1, n, key, board_size=SIZE,
                                   tree_cap=1024)
    c = cfg(n_playouts=n, n_tasks=1, n_workers=1, select_noise=0.0,
            tree_cap=1024, scheduler="fifo")
    t_par, s_par = gscpm_search(board, 1, c, key)

    assert int(t_seq.n_nodes) == int(t_par.n_nodes)
    nn = int(t_seq.n_nodes)
    np.testing.assert_array_equal(np.asarray(t_seq.parent[:nn]),
                                  np.asarray(t_par.parent[:nn]))
    np.testing.assert_array_equal(np.asarray(t_seq.move[:nn]),
                                  np.asarray(t_par.move[:nn]))
    np.testing.assert_allclose(np.asarray(t_seq.visits[:nn]),
                               np.asarray(t_par.visits[:nn]))
    np.testing.assert_allclose(np.asarray(t_seq.wins[:nn]),
                               np.asarray(t_par.wins[:nn]))
    assert s_seq["best_move"] == s_par["best_move"]


@pytest.mark.parametrize("policy", ["fifo", "rebalance", "one_per_core"])
@pytest.mark.parametrize("workers,tasks", [(4, 8), (8, 8), (8, 3), (4, 64)])
def test_invariants_all_schedulers(policy, workers, tasks):
    key = jax.random.PRNGKey(3)
    board = hx.empty_board(hx.HexSpec(SIZE))
    c = cfg(n_workers=workers, n_tasks=tasks, scheduler=policy)
    tree, stats = gscpm_search(board, 1, c, key)
    check_invariants(tree)
    assert stats["playouts"] > 0
    # root visits == executed playouts (every iteration backs up thru root)
    assert int(np.asarray(tree.visits[0])) == stats["playouts"]


def test_vl_rounds_invariants():
    key = jax.random.PRNGKey(9)
    board = hx.empty_board(hx.HexSpec(SIZE))
    tree, stats = gscpm_search(board, 1, cfg(n_workers=8, vl_rounds=4), key)
    check_invariants(tree)


def test_root_visits_equal_budget_fifo():
    key = jax.random.PRNGKey(0)
    board = hx.empty_board(hx.HexSpec(SIZE))
    c = cfg(n_playouts=256, n_tasks=16, n_workers=4)
    tree, stats = gscpm_search(board, 1, c, key)
    assert stats["playouts"] == 256
    assert int(np.asarray(tree.visits[0])) == 256


# ----------------------------------------------------------- search skill ----
def crossing_position():
    """Black column c=2 and white row r=2, both missing only (2,2).

    Whoever takes cell 12 wins instantly; every other black move leaves cell
    12 to a coin-flip in random playouts (≈0.5 value) while taking it is a
    deterministic win (1.0) — a sharply forced test position.
    """
    spec = hx.HexSpec(SIZE)
    b = hx.empty_board(spec)
    for r in (0, 1, 3, 4):
        b = b.at[r * SIZE + 2].set(1)  # black column
    for c in (0, 1, 3, 4):
        b = b.at[2 * SIZE + c].set(2)  # white row
    return b, 2 * SIZE + 2


def test_finds_immediate_win():
    b, win_move = crossing_position()
    tree, stats = gscpm_search(b, 1, cfg(n_playouts=512, n_workers=8),
                               jax.random.PRNGKey(1))
    assert stats["best_move"] == win_move
    assert stats["root_value"] > 0.6
    # the winning child's value estimate must be exactly 1.0 (deterministic win)
    kids = np.asarray(tree.children[0][: int(tree.n_children[0])])
    mv = np.asarray(tree.move)[kids]
    j = kids[list(mv).index(win_move)]
    assert float(tree.wins[j]) == float(tree.visits[j]) > 0


def test_quality_parity_parallel_vs_sequential():
    """Parallel search overhead must not destroy move quality (same winning
    move found by W=8 noisy search and sequential search)."""
    b, win_move = crossing_position()
    _, s_seq = mcts.uct_search(b, 1, 512, jax.random.PRNGKey(2), board_size=SIZE,
                               tree_cap=4096)
    _, s_par = gscpm_search(b, 1, cfg(n_playouts=512, n_workers=8, n_tasks=16),
                            jax.random.PRNGKey(2))
    assert s_seq["best_move"] == win_move
    assert s_par["best_move"] == win_move


# ------------------------------------------------------------ expansion ----
def test_expand_batch_dedup_and_slots():
    tree = init_tree(64, 25, 1)
    leaves = jnp.array([0, 0, 0, 0], dtype=jnp.int32)
    moves = jnp.array([3, 3, 7, -1], dtype=jnp.int32)  # dup (0,3); one invalid
    active = jnp.array([True, True, True, True])
    tree2, ids = expand_batch(tree, leaves, moves, active)
    ids = np.asarray(ids)
    assert int(tree2.n_nodes) == 3  # root + 2 unique children
    assert ids[0] == ids[1] != 64  # duplicates collapse
    assert ids[3] == 64            # invalid proposal -> PAD
    assert int(tree2.n_children[0]) == 2
    kids = np.asarray(tree2.children[0][:2])
    assert sorted(np.asarray(tree2.move)[kids].tolist()) == [3, 7]
    check_invariants(tree2._replace(visits=tree2.visits.at[0].set(1.0)))


def test_expand_batch_multi_leaf():
    tree = init_tree(64, 25, 1)
    # create two children of root first
    tree, _ = expand_batch(tree, jnp.array([0, 0]), jnp.array([1, 2]),
                           jnp.array([True, True]))
    l1, l2 = int(tree.children[0, 0]), int(tree.children[0, 1])
    leaves = jnp.array([l1, l2, l1, l2], dtype=jnp.int32)
    moves = jnp.array([5, 5, 6, 9], dtype=jnp.int32)
    tree2, ids = expand_batch(tree, leaves, moves, jnp.ones(4, bool))
    assert int(tree2.n_nodes) == 7
    assert int(tree2.n_children[l1]) == 2
    assert int(tree2.n_children[l2]) == 2
    ids = np.asarray(ids)
    assert len(set(ids.tolist())) == 4  # all distinct here


def test_expand_batch_capacity_clamp():
    tree = init_tree(2, 25, 1)  # room for root + 1 node only
    tree2, ids = expand_batch(tree, jnp.array([0, 0, 0]),
                              jnp.array([1, 2, 3]), jnp.ones(3, bool))
    ids = np.asarray(ids)
    assert int(tree2.n_nodes) == 2
    assert (ids == 2).sum() == 2  # two proposals hit the PAD row (cap=2)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       workers=st.sampled_from([2, 4, 8]),
       tasks=st.sampled_from([1, 4, 6, 32]),
       policy=st.sampled_from(["fifo", "rebalance"]))
def test_property_invariants_random_positions(seed, workers, tasks, policy):
    """Tree invariants hold from arbitrary midgame positions under any
    (workers × grain × scheduler) combination."""
    rng = np.random.default_rng(seed)
    spec = hx.HexSpec(SIZE)
    b = np.zeros(SIZE * SIZE, dtype=np.int8)
    k = int(rng.integers(0, 12))
    idx = rng.permutation(SIZE * SIZE)[:k]
    for t, i in enumerate(idx):
        b[i] = 1 if t % 2 == 0 else 2
    to_move = 1 if k % 2 == 0 else 2
    c = cfg(n_playouts=64, n_tasks=tasks, n_workers=workers, scheduler=policy)
    tree, stats = gscpm_search(jnp.asarray(b), to_move, c,
                               jax.random.PRNGKey(seed))
    check_invariants(tree)
    assert 0.0 <= stats["root_value"] <= 1.0


# ------------------------------------------------------------- scheduler ----
def test_fifo_masks_tail_lanes():
    s = scheduler.make_schedule(640, n_tasks=10, n_workers=4, policy="fifo")
    assert len(s) == 3
    assert s[-1].active.sum() == 2  # 10 tasks on 4 lanes -> 2 lanes idle
    st_ = scheduler.schedule_stats(s)
    assert st_["utilization"] < 1.0


def test_rebalance_keeps_lanes_busy():
    s = scheduler.make_schedule(640, n_tasks=10, n_workers=4, policy="rebalance")
    st_ = scheduler.schedule_stats(s)
    assert st_["lane_iterations"] == 640
    # only the final sub-width round may mask lanes
    assert all(r.active.all() for r in s[:-1])


def test_schedules_preserve_budget():
    for policy in ("fifo", "rebalance", "one_per_core", "sequential"):
        s = scheduler.make_schedule(512, 16, 8, policy)
        assert scheduler.schedule_stats(s)["lane_iterations"] == 512, policy


@settings(max_examples=15, deadline=None)
@given(n_playouts=st.integers(8, 640),
       tasks=st.sampled_from([1, 3, 5, 8, 10, 32]),
       workers=st.sampled_from([2, 4, 8]))
def test_property_rebalance_stats(n_playouts, tasks, workers):
    """`schedule_stats` properties of the rebalance policy vs fifo:

    - total-playout conservation: both policies schedule exactly the same
      lane-iteration budget (playouts are fungible; the split may floor);
    - idle-lane fraction: rebalance never utilizes lanes worse than fifo
      (it exists to re-split fifo's masked tail across all lanes);
    - rebalance idles lanes only in the final sub-width round, and wastes
      fewer than W lane-iterations doing so.
    """
    fifo = scheduler.make_schedule(n_playouts, tasks, workers, "fifo")
    reb = scheduler.make_schedule(n_playouts, tasks, workers, "rebalance")
    sf = scheduler.schedule_stats(fifo)
    sr = scheduler.schedule_stats(reb)
    assert sr["lane_iterations"] == sf["lane_iterations"]
    assert sr["lane_iterations"] <= n_playouts
    assert sr["utilization"] >= sf["utilization"] - 1e-12
    assert all(r.active.all() for r in reb[:-1])
    assert sr["masked_lane_iterations"] < workers


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       grain=st.sampled_from([1, 2, 3, 8]),
       policy=st.sampled_from(["fifo", "rebalance"]))
def test_property_quantum_plan_serves_mixed_requests(seed, grain, policy):
    """`quantum_plan` over a mixed-game request set — the host-side schedule
    TPFIFO game serving runs on:

    - budget conservation: each request's quanta sum to EXACTLY its GSC-PM
      round count (rounds are commit points; dropping or duplicating one
      would break the bit-identity contract);
    - every quantum makes progress (>=1 round — the PR 2 livelock guard);
    - round-robin tail-requeue service drains the whole set in at most
      max-plan-length queue cycles: no request is ever starved by a mix of
      budgets and game classes.
    """
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(2, 7))
    rounds_of = []
    for _ in range(n_req):
        sch = scheduler.make_schedule(int(rng.integers(8, 1025)),
                                      int(2 ** rng.integers(0, 7)),
                                      int(2 ** rng.integers(1, 4)), "fifo")
        rounds_of.append(len(sch))
    plans = [scheduler.quantum_plan(n, grain, policy) for n in rounds_of]
    for n, plan in zip(rounds_of, plans):
        assert sum(plan) == n
        assert min(plan) >= 1
    queue = collections.deque(range(n_req))
    rem, nxt, cycles = list(rounds_of), [0] * n_req, 0
    while queue:
        cycles += 1
        for _ in range(len(queue)):
            r = queue.popleft()
            q = plans[r][nxt[r]] if nxt[r] < len(plans[r]) else grain
            served = min(q, rem[r])
            assert served >= 1          # progress per admission segment
            rem[r] -= served
            nxt[r] += 1
            if rem[r]:
                queue.append(r)
    assert all(v == 0 for v in rem)
    assert cycles <= max(len(p) for p in plans)


def test_rng_streams_differ_between_tasks():
    """Different tasks must explore differently (per-task MKL-stream analogue)."""
    key = jax.random.PRNGKey(0)
    board = hx.empty_board(hx.HexSpec(SIZE))
    t1, _ = gscpm_search(board, 1, cfg(n_playouts=64, n_tasks=1, n_workers=1,
                                       select_noise=0.0), key)
    t2, _ = gscpm_search(board, 1, cfg(n_playouts=64, n_tasks=2, n_workers=1,
                                       select_noise=0.0), key)
    assert not np.array_equal(np.asarray(t1.visits[:64]),
                              np.asarray(t2.visits[:64]))
