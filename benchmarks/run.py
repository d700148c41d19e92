"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one benchmark per paper table/figure (CPU-scaled budgets), the kernel
microbenches, and the roofline-table render; writes JSON artifacts to
artifacts/bench/ and prints a summary. Pass --full for the larger budgets.

When the run includes fig7 (and optionally tpfifo / serve_games), it also
writes a root-level ``BENCH_mcts.json`` trajectory summary — search
playouts/s, best serving speedup, and mixed-game move-latency percentiles
for this host/backend — so the perf trajectory accumulates across PRs (CI
uploads it as an artifact per commit).
"""

from __future__ import annotations

import argparse
import json
import os
import time
import traceback


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="larger playout budgets (several minutes)")
    p.add_argument("--only", default=None,
                   help="comma-separated subset, e.g. table2,fig7")
    args = p.parse_args()

    from benchmarks import (ablate_vloss, fig5_cilkview, fig7_speedup,
                            fig9_mapping, kernels_micro, roofline_table,
                            root_parallel, selfplay, serve_chaos,
                            serve_games, table2_sequential, tpfifo)
    from benchmarks.common import save_result

    n_po = 8192 if args.full else 1024
    jobs = {
        "table2_sequential": lambda: table2_sequential.run(n_playouts=n_po),
        "fig5_cilkview": lambda: fig5_cilkview.run(),
        "fig7_speedup": lambda: fig7_speedup.run(
            n_playouts=n_po, n_workers=16,
            task_sweep=(4, 8, 16, 32, 64, 128, 256, 512) if args.full
            else (4, 16, 64, 256)),
        # the same sweep through the Game seam on the second workload
        # (smaller budget: the gomoku smoke guards the seam, the hex run
        # stays the perf headline with stable BENCH_mcts.json keys)
        "fig7_gomoku": lambda: fig7_speedup.run(
            n_playouts=n_po if args.full else n_po // 2, n_workers=16,
            game="gomoku", task_sweep=(4, 16, 64, 256) if args.full
            else (16, 64)),
        "fig9_mapping": lambda: fig9_mapping.run(n_playouts=n_po),
        "kernels_micro": lambda: kernels_micro.run(),
        "ablate_vloss": lambda: ablate_vloss.run(n_playouts=n_po),
        "roofline_table": lambda: roofline_table.run(),
        "root_parallel": lambda: root_parallel.run(n_playouts=n_po),
        "tpfifo": lambda: tpfifo.run(n_requests=48 if args.full else 24),
        "serve_games": lambda: serve_games.run(
            n_requests=32 if args.full else 16),
        # fault-rate sweep: goodput/latency under injected chaos with
        # bit-identical recovery + zero recompiles asserted inside
        "serve_chaos": lambda: serve_chaos.run(
            n_requests=32 if args.full else 16),
        "selfplay": lambda: selfplay.run(
            n_playouts=4096 if args.full else 1024,
            max_moves=20 if args.full else 12),
    }
    if args.only:
        keep = {k.strip() for k in args.only.split(",")}
        jobs = {k: v for k, v in jobs.items() if any(s in k for s in keep)}

    failures = []
    results: dict[str, dict] = {}
    for name, job in jobs.items():
        t0 = time.perf_counter()
        print(f"=== {name} ===", flush=True)
        try:
            res = job()
            results[name] = res
            path = save_result(name, res)
            print(json.dumps(_summ(name, res), indent=1))
            print(f"[{name}] ok in {time.perf_counter()-t0:.1f}s -> {path}\n",
                  flush=True)
        except Exception as e:
            failures.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    traj = write_mcts_trajectory(results)
    if traj:
        print(f"perf trajectory -> {traj}")
    print("benchmarks complete;",
          f"{len(jobs) - len(failures)}/{len(jobs)} ok",
          ("FAILED: " + ", ".join(failures)) if failures else "")
    raise SystemExit(1 if failures else 0)


def write_mcts_trajectory(results: dict) -> str | None:
    """Write root-level BENCH_mcts.json from a run containing fig7.

    The accumulating perf headline of the repo: best search throughput
    (fig7's playouts/s sweep) plus the best TPFIFO serving speedup when
    that benchmark also ran. One file per host/backend snapshot — CI
    uploads it per commit so regressions are visible as a trajectory.
    """
    fig7 = results.get("fig7_speedup")
    if not fig7:
        return None
    import jax

    def best_of(res):
        rate, point = 0.0, {}
        for sched, pts in res["curves"].items():
            for n_tasks, p in pts.items():
                if p["playouts_per_s"] > rate:
                    rate = p["playouts_per_s"]
                    point = {"scheduler": sched, "n_tasks": int(n_tasks)}
        return rate, point

    best_rate, best_point = best_of(fig7)
    seq = fig7["sequential_playouts_per_s"]
    payload = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        "backend": jax.default_backend(),
        # both axes of the host: OS cores (what the paper's thread scaling
        # is against) AND visible JAX devices (what shard_map scales over —
        # 1 unless XLA_FLAGS forces virtual host devices)
        "host_cores": os.cpu_count(),
        "n_devices": len(jax.devices()),
        "board": fig7["board"],
        "n_workers": fig7["n_workers"],
        "n_playouts": fig7["n_playouts"],
        "sequential_playouts_per_s": seq,
        "best_playouts_per_s": best_rate,
        "best_point": best_point,
        "best_speedup_vs_sequential": best_rate / max(seq, 1e-9),
    }
    # per-game search throughput (the existing top-level keys stay the Hex
    # headline so the perf trajectory remains comparable across PRs)
    games = {}
    for name, res in results.items():
        if name.startswith("fig7") and "curves" in res:
            rate, point = best_of(res)
            games[res.get("game", "hex")] = {
                "board": res["board"],
                "sequential_playouts_per_s": res[
                    "sequential_playouts_per_s"],
                "best_playouts_per_s": rate,
                "best_point": point,
            }
    if games:
        payload["games"] = games
    if "tpfifo" in results:
        payload["tpfifo_best_speedup"] = results["tpfifo"]["best_speedup"]
    if "serve_games" in results:
        # mixed hex+gomoku Poisson serving: move-latency percentiles,
        # playouts/s, and the zero-recompile ledger (see serve_games.py)
        payload["serving"] = results["serve_games"]["serving"]
        # async retirement pipelining vs blocking on the same trace, with
        # per-request bit-identity asserted in-run (DESIGN.md §18)
        payload["pipeline"] = results["serve_games"]["pipeline"]
    if "root_parallel" in results:
        # shard_map forest scale-out point, in-process on the visible
        # devices (None on one device; see root_parallel.sharded_forest)
        payload["sharded_forest"] = results["root_parallel"].get(
            "sharded_forest")
    if "selfplay" in results:
        # cross-move tree reuse: warm vs cold move latency and the mean
        # visits-retained fraction over a self-play game (see selfplay.py)
        payload["selfplay"] = results["selfplay"]["selfplay"]
    if "serve_chaos" in results:
        # resilience: goodput/p50/p95 vs injected fault rate, with
        # bit-identical recovery and zero recompiles asserted in-run
        payload["chaos"] = results["serve_chaos"]["chaos"]
    km = results.get("kernels_micro")
    if km and "hex_winner" in km:
        # fused playout-evaluation throughput per (board, W) case + the
        # headline (best batched rate) — the playout-phase twin of
        # best_playouts_per_s
        cases = {k: v["playout_eval_per_s"]
                 for k, v in km["hex_winner"].items()}
        payload["playout_eval_per_s"] = max(cases.values())
        payload["playout_eval_per_s_by_case"] = cases
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_mcts.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return os.path.abspath(path)


def _git_sha() -> str | None:
    """Commit the trajectory point describes (None outside a git checkout —
    the artifact must never make the benchmark run fail)."""
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
            check=True).stdout.strip()
    except Exception:
        return None


def _summ(name: str, res: dict) -> dict:
    """Console-sized digest per benchmark."""
    if name == "table2_sequential":
        return {k: res[k] for k in ("n_playouts", "time_s", "per_playout_us",
                                    "extrapolated_paper_budget_s")}
    if name == "fig5_cilkview":
        b = res["speedup_bounds"]
        i61 = res["core_counts"].index(61)
        return {"bound_61c_16384t": b["16384"][i61],
                "bound_61c_64t": b["64"][i61]}
    if name.startswith("fig7"):
        return {s: {t: round(p["speedup"], 2) for t, p in pts.items()}
                for s, pts in res["curves"].items()}
    if name == "root_parallel":
        out = {f"E={e}": round(p["aggregate_speedup"], 2)
               for e, p in res["ensemble"].items()}
        sf = res.get("sharded_forest") or {}
        if "speedup_vs_single_device" in sf:
            out["sharded_vs_1dev"] = round(sf["speedup_vs_single_device"], 2)
        return out
    if name == "fig9_mapping":
        return {t: {k: round(v, 2) for k, v in o.items()}
                for t, o in res["overlay"].items()}
    if name == "kernels_micro":
        return {k: list(v) for k, v in res.items()}
    if name == "ablate_vloss":
        return {r: {"tree_nodes": v["tree_nodes"],
                    "playouts_per_s": round(v["playouts_per_s"])}
                for r, v in res["results"].items()}
    if name == "tpfifo":
        return {"lockstep_tok_s": round(res["lockstep"]["throughput_tok_s"]),
                "speedups": {m: round(r["speedup_vs_lockstep"], 2)
                             for m, r in res["tpfifo"].items()},
                "best": round(res["best_speedup"], 2),
                "pass": res["acceptance"]["pass"]}
    if name == "serve_games":
        s = res["serving"]
        return {"playouts_per_s": round(s["playouts_per_s"]),
                "move_latency_ms": {"p50": round(
                    s["move_latency_p50_s"] * 1e3),
                    "p95": round(s["move_latency_p95_s"] * 1e3)},
                "p50_vs_one_per_core": round(s["p50_vs_one_per_core"], 2),
                "p95_vs_one_per_core": round(s["p95_vs_one_per_core"], 2),
                "preemptions": s["preemptions"],
                "recompiles": s["recompiles"],
                "pipeline_speedup": round(res["pipeline"]["speedup"], 2)}
    if name == "serve_chaos":
        c = res["chaos"]
        return {"fault_rates": c["fault_rates"],
                "goodput_playouts_per_s": [round(g) for g in
                                           c["goodput_playouts_per_s"]],
                "latency_p95_ms": [round(v * 1e3) for v in
                                   c["latency_p95_s"]],
                "retries": c["retries"],
                "quarantined": c["quarantined"],
                "goodput_at_max_rate_vs_clean": round(
                    c["goodput_at_max_rate_vs_clean"], 2),
                "recompiles": c["recompiles"]}
    if name == "selfplay":
        s = res["selfplay"]
        return {"warm_p50_ms": round(s["warm_move_p50_s"] * 1e3),
                "cold_p50_ms": round(s["cold_move_p50_s"] * 1e3),
                "p50_speedup": round(s["p50_speedup_warm_vs_cold"], 2),
                "mean_retained_fraction": round(
                    s["mean_retained_fraction"], 3),
                "recompiles": s["recompiles"]}
    if name == "roofline_table":
        return {"n_ok": res["n_ok"], "n_cells": res["n_cells"]}
    return {}


if __name__ == "__main__":
    main()
