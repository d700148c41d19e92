"""Root-parallel batched GSCPM: many trees, one jitted program (DESIGN.md §3).

The source paper scales ONE shared tree across 244 threads (tree
parallelism); its companion studies (arXiv:1409.4297, arXiv:1704.00325) use
the orthogonal axis — *root parallelism*: E independent trees search the same
(or different) root positions and their root statistics are merged. On SPMD
hardware the ensemble axis is free parallel width: the E trees are stacked
into one forest pytree (leading axis on every `Tree` leaf) and a whole GSCPM
round advances ALL of them in a single jitted dispatch — `jax.vmap` over the
single-tree chunk, sharded across devices along the ensemble axis when more
than one device is visible.

Three merge disciplines:

- **visit-sum** (``ensemble_best_move``): per-move root-child visits are
  summed across members; play the argmax. The classic root-parallel merge.
- **majority vote** (``majority_vote_move``): each member votes its own
  most-visited move; play the mode.
- **periodic sync** (``sync_root_stats``): every ``merge_every`` rounds each
  member's root-child statistics are refreshed with the *sum of every other
  member's own contribution*, so later selection is ensemble-informed.
  Contributions are tracked as deltas (``RootSyncState``), which makes the
  merge exact — repeated syncs never double-count, and after a final sync
  every member's root visit count equals the total playouts of the whole
  ensemble (tested in tests/test_root_parallel.py).

The same batching serves two workloads: an ensemble on one position
(stronger move choice) and one tree per position (multi-request serving —
see ``repro.serve.mcts_decode.mcts_decode_search_batch`` for the LM twin).
"""

from __future__ import annotations

import functools
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.core.gscpm import GSCPMConfig, fold_task_keys, sync_iteration
from repro.core.tree import (
    Tree,
    best_child,
    forest_member,
    forest_size,
    init_forest,
    root_move_stats,
    root_value,
)
from repro.obsv.trace import span


# ----------------------------------------------------------- forest chunk ----
def _forest_chunk(forest: Tree, boards: jnp.ndarray, cfg: GSCPMConfig,
                  task_keys: jnp.ndarray, active: jnp.ndarray,
                  m: jnp.ndarray, cp, metrics=None):
    """`gscpm.run_chunk` vmapped over the ensemble axis — one program for E
    trees. All members share the round's grain `m` and traced ``cp``;
    per-member RNG streams keep their searches decorrelated. The batched
    descent's ``ops.uct_select`` tile composes with this vmap (a leading E
    axis on the (W, C) tiles — one fused (E·W, C) selection per level), and
    so does the fused playout stage: the whole forest's leaf evaluations
    become one (E·W, cells) batched ``game.playout_batch`` under vmap
    (DESIGN.md §12/§13 — for Hex a single fill + connectivity solve with
    one convergence loop) instead of E·W interleaved scalar while-loops.
    ``cfg.metrics`` threads a per-member ``SearchMetrics`` accumulator
    ((E,)-leaf pytree, ``init_search_metrics_forest``) through the same
    vmap and returns ``(forest, metrics)``."""
    if cfg.metrics != (metrics is not None):
        raise ValueError(
            "cfg.metrics and the metrics accumulator must agree: "
            f"cfg.metrics={cfg.metrics}, metrics "
            f"{'passed' if metrics is not None else 'omitted'}")

    def one_tree(tree, board, keys, act, mx):
        def body(i, carry):
            tr, acc = carry
            iter_keys = jax.vmap(lambda tk: jax.random.fold_in(tk, i))(keys)
            if cfg.metrics:
                tr, acc = sync_iteration(tr, board, cfg, cp, iter_keys,
                                         act, acc)
            else:
                tr = sync_iteration(tr, board, cfg, cp, iter_keys, act)
            return tr, acc

        return jax.lax.fori_loop(0, m, body, (tree, mx))

    if cfg.metrics:
        return jax.vmap(one_tree)(forest, boards, task_keys, active, metrics)
    forest, _ = jax.vmap(
        lambda t, b, k, a: one_tree(t, b, k, a, 0))(
            forest, boards, task_keys, active)
    return forest


run_chunk_forest = jax.jit(_forest_chunk, static_argnames=("cfg",),
                           donate_argnums=(0,))


def ensemble_mesh(devices=None):
    """The 1-D ensemble mesh over all visible devices (None on one device).

    Built through ``launch.mesh.make_ensemble_mesh`` — the same
    ``make_auto_mesh`` path as the LM production meshes, with the
    ``"ens"`` axis the ``sharding/rules.py`` "ensemble" rule maps onto.
    """
    from repro.launch.mesh import make_ensemble_mesh

    devices = list(jax.devices() if devices is None else devices)
    if len(devices) <= 1:
        return None
    return make_ensemble_mesh(devices)


def ensemble_spec(mesh):
    """``P("ens")`` for the forest's leading member axis, derived through
    the logical-axis rules rather than spelled by hand."""
    from repro.sharding.rules import DEFAULT_RULES, logical_to_spec

    return logical_to_spec(("ensemble",), DEFAULT_RULES, mesh)


def ensemble_sharding(n_trees: int, mesh=None):
    """(NamedSharding over the ensemble axis, padded member count).

    vmap batching is embarrassingly parallel, so placing the forest with its
    leading axis sharded lets XLA partition the whole chunk — the multi-chip
    analogue of the paper's per-thread trees (DESIGN.md §3/§9). Returns
    ``(None, n_trees)`` with fewer than two devices. A member count that
    does not divide the mesh is PADDED up to the next multiple (the second
    return value) instead of the old silent fall-back to unsharded: pad
    members only ever run under all-False ``active`` masks, which leaves
    their trees bit-identical to init and their contribution to every merge
    exactly zero, so real members match the unpadded, unsharded run bit for
    bit (pinned in tests/test_forest_sharding.py).
    """
    mesh = ensemble_mesh() if mesh is None else mesh
    if mesh is None:
        return None, n_trees
    n_dev = int(np.prod(mesh.devices.shape))
    padded = ((n_trees + n_dev - 1) // n_dev) * n_dev
    return jax.sharding.NamedSharding(mesh, ensemble_spec(mesh)), padded


def pad_forest_members(forest: Tree, boards: jnp.ndarray, n_padded: int,
                       cfg: GSCPMConfig, to_move) -> tuple[Tree, jnp.ndarray]:
    """Append inert members until the ensemble axis has ``n_padded`` rows.

    Pad members get fresh init trees and a copy of member 0's board; they
    only ever run with all-False ``active`` masks, so they allocate nothing
    and back up nothing. Callers slice results back to the real count.
    """
    extra = n_padded - forest_size(forest)
    if extra <= 0:
        return forest, boards
    tm = int(np.asarray(to_move).reshape(-1)[0])
    pad = init_forest(extra, cfg.tree_cap, cfg.game_obj.n_actions, tm)
    forest = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), forest, pad)
    boards = jnp.concatenate([boards, jnp.tile(boards[:1], (extra, 1))])
    return forest, boards


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnums=(0,))
def _sharded_chunk(forest, boards, task_keys, active, m, cp, *, cfg, mesh):
    """``shard_map``-partitioned forest chunk: each device runs the vmapped
    per-round body (``_forest_chunk``, unchanged) on its own members with
    ZERO collectives — ``sync_root_stats``, dispatched outside this
    program, stays the only cross-shard exchange. Per-shard RNG is free:
    ``task_keys`` ride in pre-folded and sharded along the ensemble axis,
    so a member's stream is identical no matter which shard hosts it — the
    bit-identity pin of tests/test_forest_sharding.py."""
    spec, rep = ensemble_spec(mesh), jax.sharding.PartitionSpec()
    body = jax.shard_map(
        lambda f, b, k, a, mm, c: _forest_chunk(f, b, cfg, k, a, mm, c),
        mesh=mesh, in_specs=(spec, spec, spec, spec, rep, rep),
        out_specs=spec, check_vma=False)
    return body(forest, boards, task_keys, active, m, cp)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnums=(0,))
def _sharded_chunk_metrics(forest, boards, task_keys, active, m, cp, metrics,
                           *, cfg, mesh):
    """``_sharded_chunk`` with the (E,)-leaf ``SearchMetrics`` accumulator
    riding the same ensemble sharding (pad members see only masked-out
    work; callers slice summaries to the real members)."""
    spec, rep = ensemble_spec(mesh), jax.sharding.PartitionSpec()
    body = jax.shard_map(
        lambda f, b, k, a, mm, c, mx: _forest_chunk(
            f, b, cfg, k, a, mm, c, mx),
        mesh=mesh, in_specs=(spec, spec, spec, spec, rep, rep, spec),
        out_specs=(spec, spec), check_vma=False)
    return body(forest, boards, task_keys, active, m, cp, metrics)


@jax.jit
def fold_member_task_keys(member_keys: jax.Array,
                          task_ids: jnp.ndarray) -> jax.Array:
    """(E,) member streams × (W,) task ids -> (E, W) per-lane streams
    (jitted so per-round key building is dispatch-only)."""
    return jax.vmap(lambda mk: jax.vmap(
        lambda t: jax.random.fold_in(mk, t))(task_ids))(member_keys)


def run_schedule_round_forest(forest: Tree, boards: jnp.ndarray,
                              cfg: GSCPMConfig, member_keys: jax.Array,
                              rnd: sched.Round, cp, metrics=None, *,
                              n_real: int | None = None, mesh=None):
    """Forest twin of ``gscpm.run_schedule_round``: one schedule ``Round``
    for all E members in ONE dispatch — the atomic quantum unit shared by
    the batch driver (``gscpm_search_batch``) and the serving engine
    (``repro.serve.games`` forest tenants), which makes the serving-
    equivalence argument structural: both call the same function with the
    same operands. Round RNG depends only on (member key, task id,
    iteration), never on sharding, padding, or wall-clock interleaving.

    ``n_real`` masks sharding pad members (rows ``>= n_real`` run with
    all-False ``active`` — bitwise inert); ``mesh`` dispatches the
    ``shard_map``-partitioned chunk instead of the single-device one.
    With ``cfg.metrics`` returns ``(forest, metrics)``.
    """
    Ep = forest_size(forest)
    task_keys = fold_member_task_keys(
        member_keys, jnp.asarray(rnd.task_ids, dtype=jnp.int32))
    act = np.tile(np.asarray(rnd.active)[None, :], (Ep, 1))
    if n_real is not None and n_real < Ep:
        act[n_real:] = False
    active = jnp.asarray(act)
    m = jnp.asarray(rnd.m, dtype=jnp.int32)
    if mesh is not None:
        if cfg.metrics:
            return _sharded_chunk_metrics(forest, boards, task_keys, active,
                                          m, cp, metrics, cfg=cfg, mesh=mesh)
        return _sharded_chunk(forest, boards, task_keys, active, m, cp,
                              cfg=cfg, mesh=mesh)
    return run_chunk_forest(forest, boards, cfg, task_keys, active, m, cp,
                            metrics)


# ----------------------------------------------------------------- merges ----
@functools.partial(jax.jit, static_argnames=("n_moves",))
def merged_root_stats(forest: Tree, n_moves: int):
    """Summed per-move root (visits, wins) across members: (n_moves,) each."""
    v, w = jax.vmap(lambda t: root_move_stats(t, n_moves))(forest)
    return v.sum(axis=0), w.sum(axis=0)


def ensemble_best_move(forest: Tree, n_moves: int) -> jnp.ndarray:
    """Visit-sum merge: argmax of summed root-child visits."""
    visits, _ = merged_root_stats(forest, n_moves)
    return jnp.argmax(visits).astype(jnp.int32)


def majority_vote_move(forest: Tree, n_moves: int) -> jnp.ndarray:
    """Mode of the per-member most-visited moves (ties -> lowest move id)."""
    votes = jax.vmap(best_child)(forest)  # (E,)
    counts = jnp.zeros((n_moves,), jnp.int32).at[
        jnp.clip(votes, 0, n_moves - 1)].add(1)
    return jnp.argmax(counts).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_moves",))
def forest_summary(forest: Tree, n_moves: int) -> dict[str, jnp.ndarray]:
    """All end-of-search reductions in one jitted program (a driver that
    computes them eagerly pays several vmap re-traces per search)."""
    visits, _ = merged_root_stats(forest, n_moves)
    return {
        "member_best_moves": jax.vmap(best_child)(forest),
        "member_root_values": jax.vmap(root_value)(forest),
        "best_move_sum": jnp.argmax(visits).astype(jnp.int32),
        "best_move_vote": majority_vote_move(forest, n_moves),
    }


@functools.partial(jax.jit, static_argnames=("n_moves",))
def forest_retire_summary(forest: Tree, n_moves: int) -> dict:
    """Device-side merged root snapshot of a forest in ONE jitted program.

    The forest twin of ``tree.root_summary_device``: the pipelined serving
    engine dispatches this at retirement detection (async) and materializes
    the result a tick later, so the readback overlaps the next tick's
    quanta (DESIGN.md §18). Merged ``best_move`` follows the single-tree
    contract: ``-1`` when no member has expanded a root child yet.
    """
    visits, wins = merged_root_stats(forest, n_moves)
    rv = forest.visits[:, 0].sum()
    rw = forest.wins[:, 0].sum()
    return {
        "root_visits": visits,
        "root_wins": wins,
        "best_move": jnp.where(visits.sum() > 0, jnp.argmax(visits),
                               -1).astype(jnp.int32),
        "best_move_vote": majority_vote_move(forest, n_moves),
        "member_best_moves": jax.vmap(best_child)(forest),
        "root_value": jnp.where(rv > 0, rw / jnp.maximum(rv, 1.0), 0.0),
        "tree_nodes": forest.n_nodes.sum(),
    }


def forest_root_summary(forest: Tree, n_moves: int,
                        n_real: int | None = None) -> dict:
    """Host-side merged root snapshot — the retire currency of forest
    tenants (``repro.serve.games``), shaped like ``core/tree.root_summary``
    so the result guard and clients read both identically, plus ensemble
    extras (vote move, per-member best moves). ``n_real`` slices off
    sharding pad members first."""
    if n_real is not None and n_real < forest_size(forest):
        forest = jax.tree.map(lambda x: x[:n_real], forest)
    dev = jax.device_get(forest_retire_summary(forest, n_moves))
    return materialize_forest_summary(dev, forest_size(forest))


def materialize_forest_summary(dev: dict, n_trees: int) -> dict:
    """Pull a ``forest_retire_summary`` device dict to plain host types
    (split out so the pipelined engine can defer exactly this step)."""
    return {
        "root_visits": np.asarray(dev["root_visits"]),
        "root_wins": np.asarray(dev["root_wins"]),
        "best_move": int(dev["best_move"]),
        "root_value": float(dev["root_value"]),
        "tree_nodes": int(dev["tree_nodes"]),
        "n_trees": n_trees,
        "best_move_vote": int(dev["best_move_vote"]),
        "member_best_moves": np.asarray(dev["member_best_moves"]).tolist(),
    }


# ---------------------------------------------------------- periodic sync ----
class RootSyncState(NamedTuple):
    """Foreign (other-member) statistics already injected into each tree.

    Tracking what was injected lets ``sync_root_stats`` recover each member's
    OWN contribution exactly (own = in-tree − injected), so the merge never
    double-counts across repeated syncs.
    """

    visits: jnp.ndarray       # (E, n_moves) f32 injected per-move visits
    wins: jnp.ndarray         # (E, n_moves) f32 injected per-move wins
    root_visits: jnp.ndarray  # (E,) f32 injected root-node visits
    root_wins: jnp.ndarray    # (E,) f32 injected root-node wins


def init_sync_state(n_trees: int, n_moves: int) -> RootSyncState:
    z = jnp.zeros((n_trees, n_moves), jnp.float32)
    z1 = jnp.zeros((n_trees,), jnp.float32)
    return RootSyncState(visits=z, wins=z, root_visits=z1, root_wins=z1)


@functools.partial(jax.jit, static_argnames=("n_moves",))
@jax.named_scope("merge")
def sync_root_stats(forest: Tree, state: RootSyncState, n_moves: int
                    ) -> tuple[Tree, RootSyncState]:
    """Refresh every member's root stats with the other members' own work.

    After the call, member e's root child for move a holds
    ``own_e(a) + Σ_{e'≠e} own_e'(a)`` — for the moves e has expanded; moves a
    member has not discovered receive nothing (it cannot host a child row
    for them), which is the standard root-parallel partial-merge semantics.
    """
    dense_v, dense_w = jax.vmap(lambda t: root_move_stats(t, n_moves))(forest)
    own_v = dense_v - state.visits            # (E, M) each member's own work
    own_w = dense_w - state.wins
    new_f_v = own_v.sum(axis=0)[None, :] - own_v   # Σ others' own
    new_f_w = own_w.sum(axis=0)[None, :] - own_w
    own_rv = forest.visits[:, 0] - state.root_visits
    own_rw = forest.wins[:, 0] - state.root_wins
    new_f_rv = own_rv.sum() - own_rv
    new_f_rw = own_rw.sum() - own_rw

    def write(tree, old_fv, old_fw, nfv, nfw, d_rv, d_rw):
        cap = tree.cap
        slots = tree.children[0]
        valid = jnp.arange(slots.shape[0]) < tree.n_children[0]
        safe = jnp.where(valid, slots, cap)
        mv = jnp.clip(jnp.where(valid, tree.move[safe], 0), 0, n_moves - 1)
        visits = tree.visits.at[safe].add(
            jnp.where(valid, nfv[mv] - old_fv[mv], 0.0))
        wins = tree.wins.at[safe].add(
            jnp.where(valid, nfw[mv] - old_fw[mv], 0.0))
        visits = visits.at[cap].set(0.0).at[0].add(d_rv)
        wins = wins.at[cap].set(0.0).at[0].add(d_rw)
        # record only what was actually injected (moves with a child row)
        has = jnp.zeros((n_moves + 1,), bool).at[
            jnp.where(valid, mv, n_moves)].set(True)[:n_moves]
        rec_v = jnp.where(has, nfv, 0.0)
        rec_w = jnp.where(has, nfw, 0.0)
        return tree._replace(visits=visits, wins=wins), rec_v, rec_w

    forest, rec_v, rec_w = jax.vmap(write)(
        forest, state.visits, state.wins, new_f_v, new_f_w,
        new_f_rv - state.root_visits, new_f_rw - state.root_wins)
    return forest, RootSyncState(visits=rec_v, wins=rec_w,
                                 root_visits=new_f_rv, root_wins=new_f_rw)


# ------------------------------------------------------------------ driver ----
def gscpm_search_batch(boards: jnp.ndarray, to_move, cfg: GSCPMConfig,
                       key: jax.Array, *, n_trees: int | None = None,
                       merge_every: int = 0, forest: Tree | None = None,
                       shard: str = "auto",
                       tracer=None) -> tuple[Tree, dict[str, Any]]:
    """Root-parallel GSCPM over E trees in one jitted program per round.

    boards: (E, n_cells) — one root position per member (multi-request
    search), or (n_cells,) with ``n_trees=E`` — an E-member ensemble on one
    position. ``to_move`` is scalar or (E,). ``merge_every > 0`` enables
    periodic root synchronization (plus a final sync before move selection).

    ``forest`` warm-starts all E members from an existing forest — typically
    ``reroot_forest``'s output after a move (DESIGN.md §16). The member
    count must match the boards batch; as with the single-tree warm start
    the schedule stays exactly ``cfg``'s and the forest's buffers are
    donated to the first chunk.

    Per-round work is ONE dispatch of ``run_schedule_round_forest`` — no
    per-tree Python loop. ``shard`` controls the multi-device path:
    ``"auto"`` partitions the ensemble axis over the ``shard_map`` forest
    step whenever more than one device is visible (padding E up to the
    device count when it does not divide — pad members are bitwise inert),
    ``"off"`` forces the single-device dispatch, ``"require"`` raises
    unless a real mesh is available (CI uses it to assert the sharded path
    actually ran sharded). The sharded search is bit-identical to the
    unsharded one: per-member RNG and compute never depend on placement,
    and the only cross-shard exchange is ``sync_root_stats``' exact
    delta-tracked merge, whose integer/half-integer float32 sums are
    order-independent. ``cfg.metrics`` adds a whole-ensemble
    ``stats["metrics"]`` summary. The host spans are
    ``gscpm.gscpm_search``'s, under the same names; ``tracer`` also records
    the ``gscpm_round`` spans (blocking per round, a profiling mode).
    """
    boards = jnp.asarray(boards)
    if boards.ndim == 1:
        if n_trees is None and forest is not None:
            n_trees = forest_size(forest)   # warm restart implies E
        boards = jnp.tile(boards[None, :], (n_trees or 1, 1))
    E = boards.shape[0]
    if n_trees is not None and n_trees != E:
        raise ValueError(f"n_trees={n_trees} != boards.shape[0]={E}")
    if shard not in ("auto", "off", "require"):
        raise ValueError(f"shard must be 'auto'|'off'|'require', "
                         f"got {shard!r}")
    n_moves = cfg.game_obj.n_actions  # the Game seam's move-id space

    with span("gscpm_search"):
        with span("search_init"):
            reused_nodes = 0
            if forest is None:
                forest = init_forest(E, cfg.tree_cap, n_moves, to_move)
            else:
                if forest_size(forest) != E:
                    raise ValueError(
                        f"warm forest has {forest_size(forest)} members, "
                        f"boards batch has {E}")
                from repro.core.gscpm import warm_tree_check
                tm = int(np.asarray(to_move).reshape(-1)[0])
                warm_tree_check(forest, tm, cfg)
                reused_nodes = int(np.asarray(forest.n_nodes).sum()) - E
            mesh = ensemble_mesh() if shard != "off" else None
            if shard == "require" and mesh is None:
                raise RuntimeError(
                    f"shard='require' needs two or more devices; JAX sees "
                    f"{len(jax.devices())} (README 'Scaling out')")
            padded_members = 0
            Ep = E
            if mesh is not None:
                sharding, Ep = ensemble_sharding(E, mesh)
                padded_members = Ep - E
                forest, boards = pad_forest_members(forest, boards, Ep, cfg,
                                                    to_move)
                member_keys = fold_task_keys(key,
                                             jnp.arange(Ep, dtype=jnp.int32))
                forest, boards, member_keys = jax.device_put(
                    (forest, boards, member_keys), sharding)
            else:
                member_keys = fold_task_keys(key,
                                             jnp.arange(E, dtype=jnp.int32))
            schedule = sched.make_schedule(
                cfg.n_playouts, cfg.n_tasks, cfg.n_workers, cfg.scheduler)
            state = init_sync_state(Ep, n_moves) if merge_every > 0 else None
            metrics = None
            if cfg.metrics:
                from repro.obsv.search_metrics import (
                    init_search_metrics_forest)
                metrics = init_search_metrics_forest(Ep)
                if reused_nodes:
                    # per-member retention gauge (summed in the ensemble
                    # summary; pad members report 0 — their forests are
                    # fresh inits)
                    metrics = metrics._replace(
                        tree_nodes_reused=(forest.n_nodes - 1).astype(
                            jnp.int32))

        cp = jnp.asarray(cfg.cp, jnp.float32)
        t0 = time.perf_counter()
        playouts_per_tree = 0
        n_syncs = 0
        for r, rnd in enumerate(schedule):
            m, tasks = int(rnd.m), int(rnd.active.sum())
            with span("gscpm_round", tracer, round=r, m=m, tasks=E * tasks,
                      rounds=1, iterations=m, workers=E * cfg.n_workers):
                out = run_schedule_round_forest(forest, boards, cfg,
                                                member_keys, rnd, cp, metrics,
                                                n_real=E, mesh=mesh)
                forest, metrics = out if cfg.metrics else (out, metrics)
                if tracer:
                    jax.block_until_ready(forest.visits)
            playouts_per_tree += tasks * m
            if merge_every > 0 and ((r + 1) % merge_every == 0
                                    or r == len(schedule) - 1):
                forest, state = sync_root_stats(forest, state, n_moves)
                n_syncs += 1
        with span("search_wait"):
            jax.block_until_ready(forest.visits)
        dt = time.perf_counter() - t0

        with span("search_stats"):
            if padded_members:
                forest = jax.tree.map(lambda x: x[:E], forest)
                if cfg.metrics:
                    metrics = jax.tree.map(lambda x: x[:E], metrics)
            playouts = E * playouts_per_tree
            summary = jax.device_get(forest_summary(forest, n_moves))
            stats = {
                "time_s": dt,
                "n_trees": E,
                "playouts": playouts,
                "playouts_per_tree": playouts_per_tree,
                "playouts_per_s": playouts / max(dt, 1e-9),
                "rounds": len(schedule),
                "grain": cfg.grain,
                "n_syncs": n_syncs,
                "sharded": mesh is not None,
                "n_devices": (1 if mesh is None
                              else int(np.prod(mesh.devices.shape))),
                "mesh_shape": (None if mesh is None
                               else dict(zip(mesh.axis_names,
                                             (int(d) for d in
                                              mesh.devices.shape)))),
                "padded_members": padded_members,
                "tree_nodes": [int(n) for n in np.asarray(forest.n_nodes)],
                "member_best_moves": summary["member_best_moves"].tolist(),
                "member_root_values": summary["member_root_values"].tolist(),
                "best_move_sum": int(summary["best_move_sum"]),
                "best_move_vote": int(summary["best_move_vote"]),
            }
            if reused_nodes:
                stats["reused_nodes"] = reused_nodes
            if cfg.metrics:
                from repro.obsv.search_metrics import summarize_metrics
                stats["metrics"] = summarize_metrics(metrics)
    return forest, stats


def check_forest_invariants(forest: Tree, *,
                            discrete_credits: bool = True) -> None:
    """Per-member structural invariants (host-side, used by tests).

    ``discrete_credits=False`` for token-tree forests backed up with
    continuous values (see ``tree.check_invariants``).
    """
    from repro.core.tree import check_invariants

    for e in range(forest_size(forest)):
        check_invariants(forest_member(forest, e),
                         discrete_credits=discrete_credits)
