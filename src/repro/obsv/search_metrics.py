"""Device-plane search metrics: traced per-round counters (DESIGN.md §15).

The paper's companion study (arXiv:1409.4297) is pure measurement — where
does a thread's time go, how deep do descents run, how contended is the
shared tree. Its TPU twin cannot poll the device mid-round, so the
counters ride *inside* the compiled program: ``SearchMetrics`` is a small
pytree of scalar accumulators threaded through ``gscpm.sync_iteration`` /
``run_chunk`` / ``run_chunk_forest`` exactly like the tree itself. The
host hands the accumulator in with a quantum dispatch and reads one small
pytree back per chunk — never per round, never mid-program.

Two contracts, both pinned by tests/test_obsv.py:

- **bit-identity**: metric updates are pure extra reductions over values
  the search already computes; they consume no RNG and feed nothing back,
  so a search with metrics on is bit-identical to the same search with
  metrics off.
- **two programs**: ``GSCPMConfig.metrics`` is a *hashed static* flag, so
  each game class compiles exactly two quantum programs — one with the
  accumulator threaded, one without — and Cp/grain/budget sweeps still
  recompile neither.

All counters are int32: at this harness's budgets (<=1e6 playouts,
boards <= a few hundred cells) every counter stays far below 2^31; a
float32 accumulator would silently lose integer precision past 2^24.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SearchMetrics(NamedTuple):
    """Per-search (or per-quantum-stream) counter accumulator.

    Scalars for a single tree; a forest carries the same pytree with a
    leading (E,) member axis (``init_search_metrics_forest``).
    """

    sync_iterations: jnp.ndarray        # batched GSCPM iterations run
    lane_playouts: jnp.ndarray          # active lane-iterations == playouts
    masked_lane_iterations: jnp.ndarray  # idle-lane slots (schedule masking)
    depth_sum: jnp.ndarray              # Σ descent depth over active lanes
    depth_max: jnp.ndarray              # deepest descent seen
    held_levels: jnp.ndarray            # lane-levels idled while peers descended
    # (W, C) child-statistics tiles the level loop gathered: one per level
    # below the root from which some lane stepped (gscpm.select_batch)
    tile_gathers: jnp.ndarray
    # path levels the backup scattered: the leading columns of the
    # iteration's (W, D) paths that hold a node (tree.path_levels)
    backup_levels: jnp.ndarray
    expand_proposals: jnp.ndarray       # (leaf, move) expansion proposals
    expansions: jnp.ndarray             # nodes actually allocated
    expand_collisions: jnp.ndarray      # duplicate proposals collapsed
    leaf_collisions: jnp.ndarray        # lanes sharing a leaf (vloss collisions)
    playout_moves: jnp.ndarray          # Σ cells filled by playout evaluation
    playout_len_max: jnp.ndarray        # longest single playout
    tree_nodes_peak: jnp.ndarray        # max node occupancy observed
    # nodes inherited from a re-rooted tree at search start (DESIGN.md §16)
    # — seeded once by the warm-start entry points, carried through
    # iterations unchanged, summed across merged streams/members so the
    # retention rate shows up in traces next to the growth counters
    tree_nodes_reused: jnp.ndarray


def init_search_metrics(tree_nodes_reused: int = 0) -> SearchMetrics:
    """Fresh all-zero accumulator (scalar leaves).

    ``tree_nodes_reused`` seeds the retention gauge for warm-started
    searches (``gscpm_search(tree=...)``): the node count inherited from a
    re-rooted tree, minus the trivial root (a cold tree also starts with 1
    node, so a cold search reports 0).
    """
    z = jnp.zeros((), jnp.int32)
    m = SearchMetrics(*([z] * len(SearchMetrics._fields)))
    if tree_nodes_reused:
        m = m._replace(
            tree_nodes_reused=jnp.asarray(tree_nodes_reused, jnp.int32))
    return m


def init_search_metrics_forest(n_trees: int) -> SearchMetrics:
    """Per-member accumulator for an E-tree forest: every leaf is (E,)."""
    return jax.tree.map(
        lambda x: jnp.zeros((n_trees,) + x.shape, x.dtype),
        init_search_metrics())


def _sorted_dup_count(keys: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """How many masked-in entries duplicate an earlier equal entry.

    Masked-out lanes get a per-lane-unique sentinel so they can never
    count as duplicates of each other.
    """
    n = keys.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    a = jnp.where(mask, keys, jnp.int32(-1))
    b = jnp.where(mask, jnp.zeros((n,), jnp.int32), lane)
    a_s, b_s = jax.lax.sort((a, b), num_keys=2)
    dup = (a_s[1:] == a_s[:-1]) & (b_s[1:] == b_s[:-1]) & (a_s[1:] >= 0)
    return dup.sum().astype(jnp.int32)


def _pair_dup_count(leaves: jnp.ndarray, moves: jnp.ndarray,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """Duplicate (leaf, move) pairs among masked-in proposals — the same
    lexicographic two-key sort ``gscpm.expand_batch`` allocates with, so
    no key packing (and no int32 overflow) for any cap × cell count."""
    n = leaves.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    lf = jnp.where(mask, leaves, -1 - lane)   # unique negative sentinels
    mv = jnp.where(mask, moves, jnp.int32(0))
    lf_s, mv_s = jax.lax.sort((lf, mv), num_keys=2)
    dup = (lf_s[1:] == lf_s[:-1]) & (mv_s[1:] == mv_s[:-1]) & (lf_s[1:] >= 0)
    return dup.sum().astype(jnp.int32)


def accumulate_iteration(m: SearchMetrics, *, depths_grouped: jnp.ndarray,
                         active: jnp.ndarray, leaves: jnp.ndarray,
                         moves: jnp.ndarray, eval_boards: jnp.ndarray,
                         backup_levels: jnp.ndarray,
                         n_nodes_before: jnp.ndarray,
                         n_nodes_after: jnp.ndarray) -> SearchMetrics:
    """Fold one ``sync_iteration``'s observations into the accumulator.

    Every input is a value the iteration computed anyway:

    - ``depths_grouped``: (R, Wr) descent depths, grouped by virtual-loss
      round — held levels and level-loop trips are counted against each
      group's own deepest lane, because that is the lockstep descent the
      group actually ran;
    - ``leaves``/``moves``: (W,) selected leaves and proposed expansion
      moves (−1 = no proposal);
    - ``eval_boards``: (W, n_cells) positions handed to the playout stage
      (its empty count IS the playout length — the fill stage plays until
      the board is full);
    - ``backup_levels``: the path levels the backup scattered;
    - ``n_nodes_before/after``: allocation counter around ``expand_batch``.
    """
    from repro.core.game import EMPTY

    depths = depths_grouped.reshape(-1)
    act_i = active.astype(jnp.int32)
    w_active = act_i.sum()

    group_max = depths_grouped.max(axis=1, keepdims=True)
    held = (group_max - depths_grouped).sum().astype(jnp.int32)
    # a group's level loop steps all its moving lanes one level per trip,
    # from depth 1 down, so its trips are its deepest lane's depth - 1
    tiles = jnp.maximum(group_max - 1, 0).sum().astype(jnp.int32)

    proposals = ((moves >= 0) & active).astype(jnp.int32).sum()
    playout_len = (eval_boards == EMPTY).sum(axis=1).astype(jnp.int32)

    return SearchMetrics(
        sync_iterations=m.sync_iterations + 1,
        lane_playouts=m.lane_playouts + w_active,
        masked_lane_iterations=m.masked_lane_iterations
        + (active.shape[0] - w_active),
        depth_sum=m.depth_sum + (depths * act_i).sum(),
        depth_max=jnp.maximum(m.depth_max, (depths * act_i).max()),
        held_levels=m.held_levels + held,
        tile_gathers=m.tile_gathers + tiles,
        backup_levels=m.backup_levels + backup_levels,
        expand_proposals=m.expand_proposals + proposals,
        expansions=m.expansions + (n_nodes_after - n_nodes_before),
        expand_collisions=m.expand_collisions
        + _pair_dup_count(leaves, moves, (moves >= 0) & active),
        leaf_collisions=m.leaf_collisions
        + _sorted_dup_count(leaves, active),
        playout_moves=m.playout_moves + (playout_len * act_i).sum(),
        playout_len_max=jnp.maximum(m.playout_len_max,
                                    (playout_len * act_i).max()),
        tree_nodes_peak=jnp.maximum(m.tree_nodes_peak, n_nodes_after),
        tree_nodes_reused=m.tree_nodes_reused,   # seeded at init, not per-iter
    )


def merge_metrics(a: SearchMetrics, b: SearchMetrics) -> SearchMetrics:
    """Combine two accumulators (sums for counters, max for the gauges)."""
    maxed = {"depth_max", "playout_len_max", "tree_nodes_peak"}
    return SearchMetrics(*[
        jnp.maximum(x, y) if f in maxed else x + y
        for f, x, y in zip(SearchMetrics._fields, a, b)])


def summarize_metrics(m: SearchMetrics) -> dict:
    """One host readback -> a plain dict of counters + derived rates.

    Accepts a scalar accumulator or a forest one (leading member axis —
    members are merged first, so the summary is whole-ensemble).
    """
    m = jax.tree.map(jnp.asarray, m)
    if m.sync_iterations.ndim > 0:
        flat = jax.tree.map(lambda x: x.reshape(-1), m)
        n = flat.sync_iterations.shape[0]
        merged = jax.tree.map(lambda x: x[0], flat)
        for e in range(1, n):
            merged = merge_metrics(merged,
                                   jax.tree.map(lambda x, e=e: x[e], flat))
        m = merged
    host = {f: int(v) for f, v in zip(SearchMetrics._fields,
                                      jax.device_get(tuple(m)))}
    playouts = max(1, host["lane_playouts"])
    host["depth_mean"] = host["depth_sum"] / playouts
    host["tile_gathers_per_iter"] = (
        host["tile_gathers"] / max(1, host["sync_iterations"]))
    host["backup_levels_per_iter"] = (
        host["backup_levels"] / max(1, host["sync_iterations"]))
    host["playout_len_mean"] = host["playout_moves"] / playouts
    host["expand_collision_rate"] = (
        host["expand_collisions"] / max(1, host["expand_proposals"]))
    host["leaf_collision_rate"] = host["leaf_collisions"] / playouts
    return host
