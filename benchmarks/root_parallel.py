"""Root-parallel scaling — aggregate playouts/s vs ensemble size E.

The §3 claim measured: E independent trees advanced by ONE jitted program
per round (no per-tree Python loop) amortize dispatch and fill idle vector
lanes, so aggregate throughput grows far faster than the cost of batching.
The acceptance bar for this repo: E=8 aggregate playouts/s >= 3x the
single-tree rate at an identical per-tree configuration.

The default per-tree config is the classic root-parallel regime — each
member is a narrow (W=1) searcher, the setting of the paper's companion
study (arXiv:1409.4297) where an ensemble of sequential searchers is merged
at the root. Wide per-tree configs (W >= 8) shift the parallelism budget to
the shared-tree axis of §2 and saturate a small host by themselves; the
ensemble dial and the lane dial trade against each other on fixed hardware.

    PYTHONPATH=src python benchmarks/root_parallel.py
"""

from __future__ import annotations

import os
import sys

import jax

if __package__ in (None, ""):   # `python benchmarks/root_parallel.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from repro.core.gscpm import GSCPMConfig, gscpm_search
from repro.core.root_parallel import gscpm_search_batch


def run(n_playouts: int = 4096, n_workers: int = 1, board_size: int = 5,
        n_tasks: int = 8, ensemble_sweep=(1, 2, 4, 8),
        merge_every: int = 0, seed: int = 0,
        tree_cap: int | None = None, repeats: int = 5) -> dict:
    cfg = GSCPMConfig(board_size=board_size, n_playouts=n_playouts,
                      n_tasks=n_tasks, n_workers=n_workers,
                      tree_cap=tree_cap or max(512, n_playouts // 8))
    board = cfg.game_obj.init_board()
    key = jax.random.key(seed)

    def one_single():
        _, st = gscpm_search(board, 1, cfg, key)
        return st

    def one_batch(e):
        _, st = gscpm_search_batch(board, 1, cfg, key, n_trees=e,
                                   merge_every=merge_every)
        return st

    # warm-up/compile every program before any timing
    one_single()
    for e in ensemble_sweep:
        one_batch(e)

    # paired repeats: shared hosts drift (contention, frequency scaling), so
    # each rep measures the single baseline and every ensemble size back to
    # back and the speedup is the median of PAIRED ratios — drift then hits
    # both sides of each ratio equally instead of whichever ran first
    single_rates = []
    batch_stats = {e: [] for e in ensemble_sweep}
    ratios = {e: [] for e in ensemble_sweep}
    for _ in range(repeats):
        s = one_single()
        single_rates.append(s["playouts_per_s"])
        for e in ensemble_sweep:
            st = one_batch(e)
            batch_stats[e].append(st)
            ratios[e].append(st["playouts_per_s"] / s["playouts_per_s"])

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    base_rate = median(single_rates)
    points = {}
    for e in ensemble_sweep:
        st = batch_stats[e][-1]
        speedup = median(ratios[e])
        points[str(e)] = {
            "playouts_per_s": median(
                [b["playouts_per_s"] for b in batch_stats[e]]),
            "aggregate_speedup": speedup,
            "batching_efficiency": speedup / e,
            "best_move_sum": st["best_move_sum"],
            "best_move_vote": st["best_move_vote"],
            "sharded": st["sharded"],
        }
    out = {
        "config": {"n_playouts": n_playouts, "n_workers": n_workers,
                   "board_size": board_size, "n_tasks": n_tasks,
                   "merge_every": merge_every, "repeats": repeats,
                   "n_devices": len(jax.devices())},
        "single_tree_playouts_per_s": base_rate,
        "single_tree_rates": single_rates,
        "ensemble": points,
    }
    out["sharded_forest"] = sharded_forest(
        n_playouts=min(n_playouts, 1024), repeats=2)
    return out


def sharded_forest(n_playouts: int = 1024, n_trees: int = 8,
                   board_size: int = 5, n_tasks: int = 8,
                   n_workers: int = 1, tree_cap: int | None = None,
                   seed: int = 0, repeats: int = 3) -> dict | None:
    """shard_map forest scale-out vs the single-device vmap path.

    Both points run in THIS process on the devices JAX already sees: one
    process holds a chip, so a child started after the parent touched JAX
    could not reach the device. ``shard="off"`` runs the forest on one
    device, ``shard="require"`` over all of them. Returns None when only
    one device is visible; on a CPU host the multi-device rehearsal is
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before the
    process starts (README "Scaling out"). The merged best move must agree
    across the two points — the bit-identity contract of
    tests/test_forest_sharding.py, smoked here on every benchmark run.
    """
    n_devices = len(jax.devices())
    if n_devices < 2:
        return None
    kw = dict(n_playouts=n_playouts, board_size=board_size, n_tasks=n_tasks,
              n_workers=n_workers,
              tree_cap=tree_cap or max(512, n_playouts // 8))
    cfg = GSCPMConfig(**kw)
    board = cfg.game_obj.init_board()
    key = jax.random.key(seed)

    def point(shard: str) -> dict:
        def one():
            _, st = gscpm_search_batch(board, 1, cfg, key, n_trees=n_trees,
                                       shard=shard)
            return st

        one()                                    # compile off the clock
        stats = [one() for _ in range(repeats)]
        rates = sorted(s["playouts_per_s"] for s in stats)
        st = stats[-1]
        return {
            "n_devices": st["n_devices"],
            "sharded": st["sharded"],
            "mesh_shape": st["mesh_shape"],
            "padded_members": st["padded_members"],
            "playouts": st["playouts"],
            "playouts_per_s": rates[len(rates) // 2],
            "best_move_sum": st["best_move_sum"],
            "best_move_vote": st["best_move_vote"],
        }

    single = point("off")
    sharded = point("require")
    if (sharded["best_move_sum"], sharded["playouts"]) != (
            single["best_move_sum"], single["playouts"]):
        raise RuntimeError(f"sharded forest {sharded} disagrees with the "
                           f"single-device forest {single}")
    return {
        "config": dict(kw, n_trees=n_trees, seed=seed, repeats=repeats,
                       n_devices=n_devices),
        "single_device": single,
        "sharded": sharded,
        "speedup_vs_single_device": (sharded["playouts_per_s"]
                                     / max(single["playouts_per_s"], 1e-9)),
    }


def main():
    import argparse

    from benchmarks.common import save_result

    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny budgets (CI rot-guard, <1 min)")
    args = p.parse_args()

    out = run(n_playouts=512, repeats=2) if args.smoke else run()
    base = out["single_tree_playouts_per_s"]
    print(f"single tree: {base:9.0f} playouts/s   (baseline)")
    for e, pt in out["ensemble"].items():
        print(f"E={e:>2} trees:  {pt['playouts_per_s']:9.0f} playouts/s   "
              f"aggregate {pt['aggregate_speedup']:5.2f}x   "
              f"batching efficiency {pt['batching_efficiency']:5.1%}")
    sf = out["sharded_forest"]
    if sf is None:
        print("sharded forest: not run (one device visible)")
    else:
        print(f"sharded forest: E={sf['config']['n_trees']} over "
              f"{sf['sharded']['n_devices']} devices   "
              f"{sf['sharded']['playouts_per_s']:9.0f} playouts/s   "
              f"{sf['speedup_vs_single_device']:5.2f}x vs 1 device   "
              f"mesh {sf['sharded']['mesh_shape']}")
    path = save_result("root_parallel", out)
    print("->", path)
    e8 = out["ensemble"].get("8")
    if e8 is not None:
        ok = e8["aggregate_speedup"] >= 3.0
        print(f"acceptance (E=8 aggregate >= 3x single tree): "
              f"{'PASS' if ok else 'FAIL'} ({e8['aggregate_speedup']:.2f}x)")


if __name__ == "__main__":
    main()
