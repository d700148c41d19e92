"""Smoke run of the main path on a TPU at the paper's widths.

    python chip_smoke.py                # one chip: kernels, search, serving
    python chip_smoke.py --four-chips   # the shard_map forest: 4 chips vs 1

The widths are ``configs/hex_paper.PAPER``: 11x11 Hex (121 children),
244 lanes, ``tree_cap = 2**20``, 4096 tasks, Cp = 1.0. The one-chip run has
three phases, each through the entry points a user calls:

1. ``kernels``: the Pallas ``hex_winner`` against ``hex.winner_flood_batch``
   on seeded random filled 11x11 boards, and the Pallas ``uct_select``
   against ``kernels.ref.uct_select`` at (244, 121). Outputs must be equal.
2. ``search``: first one round at ``PAPER``'s board and lane width with a
   2^16-node tree, on the chip and on the host CPU through the jnp paths;
   the trees must be equal. Then one ``gscpm_search`` at ``PAPER``. The
   full 1,048,576-playout budget runs unless one measured round says it
   cannot finish within ``SEARCH_SECONDS``; the budget is then cut to
   whole rounds and the cut is printed. The tree must pass the structural
   checks below.
3. ``serving``: Hex 11x11 and Gomoku 9x9 requests through
   ``TPFIFOGameEngine`` at the same widths, one warm ``GameSession`` move
   among them. Every request must be answered, and one served answer must
   equal the same search run directly, bit for bit.

``--four-chips`` runs only ``gscpm_search_batch`` at the settings of the
benchmark's ``hex11-forest8`` (8 trees of 124,928 playouts at ``PAPER``'s
widths, tree cap 2^17, root statistics merged after each of the 2 rounds),
sharded over 4 chips and unsharded on one, in this one process; every
tree array and both merged moves must be equal.

Each phase prints its wall time and the device's peak memory. The last
line of standard output is one JSON object naming the device; it is
printed only when JAX sees a TPU and every phase passed, and the exit code
is non-zero otherwise. One process holds the chip throughout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# wall-clock allotment for the paper-width search itself (compilation
# excluded): the whole run must end within 1200 s
SEARCH_SECONDS = 480.0
N_BOARDS = 4096
HOST_GRAIN = 16       # iterations of the search checked against the host
HOST_TREE_CAP = 1 << 16
SERVE_ROUNDS = 2      # schedule rounds per served request
SERVE_GRAIN = 64      # iterations per round (m) of a served request
# the forest of bench/configs/hex11-forest8.json: 488 tasks of grain 256
# (2 rounds) per tree, a merge after every round
FOREST_TREES = 8
FOREST_PLAYOUTS = 124_928
FOREST_TASKS = 488
FOREST_TREE_CAP = 1 << 17
FOREST_MERGE_EVERY = 1


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def random_filled_boards(rng: np.random.Generator, n_boards: int,
                         size: int) -> np.ndarray:
    """Filled Hex boards as a playout leaves them: the cells in a random
    order, stones alternating black, white, black, ..."""
    n = size * size
    rank = rng.permuted(np.tile(np.arange(n), (n_boards, 1)), axis=1)
    return np.where(rank % 2 == 0, 1, 2).astype(np.int8)


def random_uct_tile(rng: np.random.Generator, W: int, C: int) -> dict:
    """A (W, C) child-statistics tile as a search level presents it:
    integer visits, half-integer wins, a few virtual losses, a random
    number of valid children per lane, tie-break noise and idle lanes."""
    visits = rng.integers(0, 64, (W, C)).astype(np.float32)
    wins = np.floor(rng.uniform(size=(W, C)) * (2 * visits + 1)) / 2
    vloss = rng.integers(0, 3, (W, C)).astype(np.float32)
    valid = np.arange(C)[None, :] < rng.integers(1, C + 1, W)[:, None]
    return dict(
        wins=jnp.asarray(wins, jnp.float32), visits=jnp.asarray(visits),
        vloss=jnp.asarray(vloss),
        parent_total=jnp.asarray((visits * valid).sum(1) + 1.0),
        valid=jnp.asarray(valid),
        noise=jnp.asarray(1e-3 * rng.uniform(size=(W, C)), jnp.float32),
        lane_mask=jnp.asarray(rng.uniform(size=W) < 0.9))


# ------------------------------------------------------------------ phases ----
def kernel_phase(cfg, seed: int, n_boards: int = N_BOARDS) -> None:
    """Pallas ``hex_winner`` and ``uct_select`` against their references."""
    from repro.core import hex as hx
    from repro.kernels import ops, ref

    size, W = cfg.board_size, cfg.n_workers
    rng = np.random.default_rng(seed)
    boards = jnp.asarray(random_filled_boards(rng, n_boards, size))
    t = time.perf_counter()
    got = np.asarray(ops.hex_winner(boards, size))
    t_kernel = time.perf_counter() - t
    want = np.asarray(jax.jit(hx.winner_flood_batch, static_argnums=1)(
        boards, hx.HexSpec(size)))
    bad = int((got != want).sum())
    log(f"  hex_winner {n_boards} boards {size}x{size}: {bad} differ from the "
        f"flood fill, black wins {float((want == 1).mean()):.4f}, "
        f"first call {t_kernel:.3f}s (compile included)")
    if bad:
        raise SmokeFailure(f"hex_winner differs on {bad}/{n_boards} boards")

    tile = random_uct_tile(rng, W, cfg.game_obj.n_actions)
    got = np.asarray(ops.uct_select(cp=cfg.cp, **tile))
    want = np.asarray(jax.jit(ref.uct_select)(cp=cfg.cp, **tile))
    bad = int((got != want).sum())
    log(f"  uct_select ({W}, {cfg.game_obj.n_actions}): {bad} of {W} lanes "
        f"differ from the reference")
    for lane in np.flatnonzero(got != want)[:4]:
        log(f"    lane {lane}: live {bool(tile['lane_mask'][lane])}, kernel "
            f"slot {got[lane]}, reference slot {want[lane]}")
    if bad:
        raise SmokeFailure(f"uct_select differs on {bad}/{W} lanes")


def check_tree(tree, playouts: int) -> None:
    """Vectorized structural checks of a cold-searched tree: conservation
    at the root, parents allocated before children, alternating sides,
    half-integer wins within [0, visits], no node whose children hold
    more visits than it does, and each node's child-move row equal to its
    children's moves."""
    from repro.core.tree import NO_NODE, root_summary
    from repro.serve.resilience import validate_result

    n = int(tree.n_nodes)
    if not 1 <= n <= tree.cap:
        raise SmokeFailure(f"n_nodes {n} outside [1, {tree.cap}]")
    parent, to_move, visits, wins = (np.asarray(a[:n]) for a in (
        tree.parent, tree.to_move, tree.visits, tree.wins))
    bad = validate_result(root_summary(tree, tree.max_children), playouts)
    if visits[0] != playouts:
        bad.append(f"root visits {visits[0]} != playouts {playouts}")
    kids = np.arange(1, n)
    if not ((parent[1:] >= 0) & (parent[1:] < kids)).all():
        bad.append("a parent is not allocated before its child")
    elif not (to_move[1:] == 3 - to_move[parent[1:]]).all():
        bad.append("sides to move do not alternate")
    if not (np.isfinite(wins).all() and (wins >= 0).all()
            and (wins <= visits).all() and (2 * wins == np.round(2 * wins)).all()):
        bad.append("wins not half-integers within [0, visits]")
    if n > 1 and (np.bincount(parent[1:], weights=visits[1:], minlength=n)
                  > visits).any():
        bad.append("children hold more visits than their parent")
    children, child_move = (np.asarray(a[:n]) for a in (tree.children,
                                                        tree.child_move))
    held = (np.arange(children.shape[1])[None, :]
            < np.asarray(tree.n_children[:n])[:, None])
    moves = np.asarray(tree.move)[np.where(held, children, 0)]
    if not (child_move == np.where(held, moves, NO_NODE)).all():
        bad.append("child_move rows differ from the children's moves")
    if bad:
        raise SmokeFailure("tree checks failed: " + "; ".join(bad))


def host_reference_check(cfg, seed: int) -> None:
    """One schedule round at ``cfg``'s board and lane width, with a smaller
    tree, on the device and on the host CPU: the trees must be equal.

    The host run takes the jnp twins of both kernels, so this checks the
    whole compiled search program, not only the Pallas bodies. It is the
    check that catches a miscompiled XLA op on the chip."""
    from repro.core.gscpm import gscpm_search

    small = dataclasses.replace(cfg, n_playouts=cfg.n_workers * HOST_GRAIN,
                                n_tasks=cfg.n_workers,
                                tree_cap=min(cfg.tree_cap, HOST_TREE_CAP))

    def tree_arrays():
        tree, _ = gscpm_search(small.game_obj.init_board(), 1, small,
                               jax.random.key(seed))
        return {k: np.asarray(getattr(tree, k)) for k in (
            "n_nodes", "visits", "wins", "parent", "move", "children",
            "child_move", "n_children")}

    on_device = tree_arrays()
    real_backend = jax.default_backend
    # kernels.ops picks the Pallas or the jnp path from the default backend
    jax.default_backend = lambda: "cpu"
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            on_host = tree_arrays()
    finally:
        jax.default_backend = real_backend
    bad = [k for k in on_device if not np.array_equal(on_device[k],
                                                      on_host[k])]
    log(f"  one round of {small.n_workers}x{HOST_GRAIN} playouts, cap "
        f"{small.tree_cap}, device vs host CPU: "
        f"{'bit-identical' if not bad else 'DIFFERENT in ' + str(bad)} "
        f"({int(on_device['n_nodes'])} nodes)")
    if bad:
        raise SmokeFailure(f"device search differs from the host CPU in {bad}")


def search_phase(cfg, seed: int, seconds: float = SEARCH_SECONDS) -> None:
    """One ``gscpm_search`` at ``cfg``'s widths and (if time allows) its
    full budget, after a small search checked against the host CPU."""
    from repro.core import scheduler as sched
    from repro.core.gscpm import gscpm_search

    host_reference_check(cfg, seed)
    board = cfg.game_obj.init_board()
    key = jax.random.key(seed)
    W, m = cfg.n_workers, cfg.grain
    # the one-round probe shares the full search's compiled program:
    # n_playouts and n_tasks are not part of the config's hash
    probe = dataclasses.replace(cfg, n_playouts=W * m, n_tasks=W)
    t = time.perf_counter()
    tree, _ = gscpm_search(board, 1, probe, key)
    first_s = time.perf_counter() - t
    del tree
    tree, st = gscpm_search(board, 1, probe, key)
    round_s = st["time_s"]
    del tree
    log(f"  compile + first round {first_s:.2f}s; one round of {W}x{m} "
        f"playouts {round_s:.3f}s ({st['playouts_per_s']:.0f} playouts/s)")

    n_rounds = len(sched.make_schedule(cfg.n_playouts, cfg.n_tasks, W,
                                       cfg.scheduler))
    fit = int(seconds // max(round_s, 1e-9))
    run = cfg
    if fit < n_rounds:
        rounds = max(1, fit)
        run = dataclasses.replace(cfg, n_playouts=rounds * W * m,
                                  n_tasks=rounds * W)
        log(f"  BUDGET CUT: {cfg.n_playouts} -> {run.n_playouts} playouts "
            f"({rounds} of {n_rounds} rounds fit in {seconds:.0f}s)")
    tree, st = gscpm_search(board, 1, run, key)
    check_tree(tree, st["playouts"])
    log(f"  search {cfg.board_size}x{cfg.board_size} W={W} "
        f"cap={cfg.tree_cap}: {st['playouts']} playouts in "
        f"{st['time_s']:.2f}s = {st['playouts_per_s']:.0f} playouts/s, "
        f"{st['rounds']} rounds, {st['tree_nodes']} nodes, best move "
        f"{st['best_move']}, root value {st['root_value']:.4f}, masked lane "
        f"fraction {st['masked_lane_fraction']:.4f}")


def serving_phase(cfg, seed: int, rounds: int = SERVE_ROUNDS,
                  grain: int = SERVE_GRAIN, gomoku_size: int = 9) -> None:
    """Hex and Gomoku requests through ``TPFIFOGameEngine`` at ``cfg``'s
    lane width and tree capacity; one served answer is compared with the
    direct search."""
    from repro.core.gscpm import gscpm_search
    from repro.core.tree import root_summary
    from repro.serve.games import GameRequest, GameSession, TPFIFOGameEngine

    W, size = cfg.n_workers, cfg.board_size
    budget = dict(n_playouts=rounds * W * grain, n_tasks=rounds * W)
    eng = TPFIFOGameEngine(n_slots=2, grain=1, n_workers=W,
                           tree_cap=cfg.tree_cap)
    game = cfg.game_obj
    opening = np.asarray(game.place(game.init_board(),
                                    jnp.int32(game.n_cells // 2), jnp.int8(1)))
    reqs = [
        GameRequest(rid="hex-empty", board_size=size, seed=seed, **budget),
        GameRequest(rid="hex-cp0.5", board_size=size, seed=seed + 1, cp=0.5,
                    **budget),
        GameRequest(rid="hex-reply", board_size=size, board=opening,
                    to_move=2, seed=seed + 2, **budget),
        GameRequest(rid="gomoku", game="gomoku", board_size=gomoku_size,
                    seed=seed + 3, **budget),
    ]
    sess = GameSession(eng, "hex", size, base_seed=seed + 10)
    cold = sess.make_request(**budget)
    t = time.perf_counter()
    for r in reqs + [cold]:
        eng.submit(r)
    eng.run()
    sess.play(cold.result["best_move"])
    warm = sess.make_request(**budget)
    eng.submit(warm)
    eng.run()
    wall = time.perf_counter() - t
    served = reqs + [cold, warm]
    for r in served:
        res = r.result
        log(f"  {r.rid:>14}: {res['status']}, best move {res['best_move']}, "
            f"{res['playouts']} playouts, {res['tree_nodes']} nodes, "
            f"latency {res['latency_s']:.2f}s, retries {res['retries']}"
            + (f", reused {res['reused_nodes']} nodes / "
               f"{res['reused_visits']} visits"
               if "reused_visits" in res else ""))
    log(f"  served {len(served)} requests in {wall:.2f}s "
        f"(compiles included)")
    unanswered = [r.rid for r in served if r.result["status"] != "answered"]
    if unanswered:
        raise SmokeFailure(f"requests not answered: {unanswered}")
    if not warm.result.get("reused_visits"):
        raise SmokeFailure("the session's second move did not start warm")

    # the engine's contract: a served search equals the direct search
    r = reqs[2]
    rcfg = eng.request_cfg(r)
    tree, _ = gscpm_search(jnp.asarray(r.board), r.to_move, rcfg,
                           jax.random.key(r.seed))
    ref = root_summary(tree, rcfg.game_obj.n_actions)
    same = (np.array_equal(r.result["root_visits"], ref["root_visits"])
            and np.array_equal(r.result["root_wins"], ref["root_wins"])
            and r.result["best_move"] == ref["best_move"]
            and r.result["root_value"] == ref["root_value"]
            and r.result["tree_nodes"] == ref["tree_nodes"])
    log(f"  served {r.rid} vs direct gscpm_search: "
        f"{'bit-identical' if same else 'DIFFERENT'}")
    if not same:
        raise SmokeFailure(f"served {r.rid} differs from the direct search")


def forest_phase(cfg, seed: int, n_trees: int = FOREST_TREES,
                 n_playouts: int = FOREST_PLAYOUTS,
                 n_tasks: int = FOREST_TASKS,
                 tree_cap: int = FOREST_TREE_CAP,
                 merge_every: int = FOREST_MERGE_EVERY,
                 n_devices: int = 4) -> None:
    """``gscpm_search_batch`` sharded over ``n_devices`` chips against the
    same forest on one chip, at ``cfg``'s widths with the forest's budget,
    tree cap and merges: every tree array must be equal."""
    from repro.core.root_parallel import gscpm_search_batch

    if len(jax.devices()) != n_devices:
        raise SmokeFailure(f"--four-chips needs {n_devices} devices, JAX "
                           f"sees {len(jax.devices())}")
    run = dataclasses.replace(cfg, n_playouts=n_playouts, n_tasks=n_tasks,
                              tree_cap=tree_cap)
    board = cfg.game_obj.init_board()
    key = jax.random.key(seed)

    out = {}
    for shard in ("require", "off"):
        t = time.perf_counter()
        forest, st = gscpm_search_batch(board, 1, run, key, n_trees=n_trees,
                                        merge_every=merge_every, shard=shard)
        wall = time.perf_counter() - t
        homes = {s.device for s in forest.visits.addressable_shards}
        out[shard] = (jax.device_get(forest), st)
        del forest
        log(f"  shard={shard}: {n_trees} trees x {st['playouts_per_tree']} "
            f"playouts, {st['n_syncs']} merges, on {len(homes)} device(s) "
            f"{sorted(d.id for d in homes)}, {wall:.2f}s wall (compile "
            f"included), best move sum {st['best_move_sum']} vote "
            f"{st['best_move_vote']}")
        if shard == "require" and (len(homes) != n_devices
                                   or st["n_devices"] != n_devices):
            raise SmokeFailure(f"sharded forest sits on {len(homes)} "
                               f"device(s), not {n_devices}")
    f_s, st_s = out["require"]
    f_1, st_1 = out["off"]
    differ = [k for k, a, b in zip(f_s._fields, f_s, f_1)
              if not np.array_equal(a, b)]
    differ += [k for k in ("n_syncs", "best_move_sum", "best_move_vote",
                           "member_best_moves") if st_s[k] != st_1[k]]
    log(f"  sharded vs one chip: "
        f"{'bit-identical' if not differ else f'DIFFERENT in {differ}'}")
    if differ or (merge_every and not st_s["n_syncs"]):
        raise SmokeFailure(f"the sharded forest differs from the one-chip "
                           f"forest ({differ}) or never merged")


# -------------------------------------------------------------------- main ----
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the shard_map forest on 4 chips against "
                        "the same forest on one chip")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of every board, tile and search key")
    args = p.parse_args(argv)

    from repro.configs.hex_paper import PAPER
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")

    if args.four_chips:
        phases = [("forest", forest_phase)]
    else:
        phases = [("kernels", kernel_phase), ("search", search_phase),
                  ("serving", serving_phase)]
    failed = []
    t_all = time.perf_counter()
    for name, phase in phases:
        log(f"[{name}]")
        t = time.perf_counter()
        try:
            phase(PAPER, args.seed)
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            failed.append(name)
        log(f"[{name}] {'FAILED' if name in failed else 'ok'} in "
            f"{time.perf_counter() - t:.2f}s, device peak "
            f"{peak_bytes()} bytes")
    log(f"total {time.perf_counter() - t_all:.2f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
