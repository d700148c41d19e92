"""Closed-loop search: one client, back-to-back ``gscpm_search`` moves.

Set-up warms the one program the moves run (a one-round search at the
cell's configuration). In the window each move is a fresh tree on the next
position of the mix; a move starts only while the mean move time so far
says it ends inside the window, and the first move always runs.

The check keeps the finished trees of move 0 and of up to ``keep - 1`` moves
drawn from the seed, and holds each against the plain reference
(``harness.reference``): the whole-tree audit, the reported move, and a
replay of the search's first sync iterations node by node.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness.spans import span

TREE_FIELDS = ("parent", "move", "to_move", "children", "n_children",
               "visits", "wins", "n_nodes")


def gscpm_config(cfg: dict):
    from repro.core.gscpm import GSCPMConfig

    return GSCPMConfig(
        game=cfg["game"], board_size=cfg["board_size"],
        n_playouts=cfg["n_playouts"], n_tasks=cfg["n_tasks"],
        n_workers=cfg["n_workers"], vl_rounds=cfg["vl_rounds"],
        virtual_loss=cfg["virtual_loss"], cp=cfg["cp"],
        select_noise=cfg["select_noise"], tree_cap=cfg["tree_cap"],
        scheduler=cfg["scheduler"])


def host_tree(tree) -> dict:
    return {k: np.asarray(getattr(tree, k)) for k in TREE_FIELDS}


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, log):
        from harness import traffic

        self.cfg, self.mix, self.seed, self.log = cfg, mix, seed, log
        self.gcfg = gscpm_config(cfg)
        self.stream = traffic.closed_loop(mix, seed, cfg["board_size"])
        self.keep = int(mix.get("check_moves", 2))
        self.rng = np.random.default_rng([seed, 1])
        self.kept: list[tuple[dict, object, dict]] = []

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core.gscpm import gscpm_search

        W, m = self.gcfg.n_workers, self.gcfg.grain
        probe = dataclasses.replace(self.gcfg, n_playouts=W * m, n_tasks=W)
        board = jnp.zeros(self.gcfg.game_obj.n_cells, jnp.int8)
        tree, _ = gscpm_search(board, 1, probe, jax.random.key(0))
        jax.block_until_ready(tree.visits)
        del tree

    def _keep(self, i: int, move: dict, tree, stats: dict) -> None:
        """Move 0, and a seeded reservoir of the later moves."""
        item = (move, tree, stats)
        if i == 0 or len(self.kept) < self.keep:
            self.kept.append(item)
            return
        j = int(self.rng.integers(0, i))      # reservoir over moves 1..i
        if j < self.keep - 1:
            self.kept[1 + j] = item

    def window(self, seconds: float) -> dict:
        import jax
        import jax.numpy as jnp

        from repro.core.gscpm import gscpm_search

        t0 = time.perf_counter()
        times, playouts = [], 0
        while True:
            elapsed = time.perf_counter() - t0
            if times and elapsed + float(np.mean(times)) > seconds:
                break
            mv = next(self.stream)
            with span("move"):
                ts = time.perf_counter()
                tree, st = gscpm_search(jnp.asarray(mv["board"]),
                                        mv["to_move"], self.gcfg,
                                        jax.random.key(mv["key_seed"]))
                times.append(time.perf_counter() - ts)
            playouts += st["playouts"]
            self._keep(mv["index"], mv, tree, st)
        wall = time.perf_counter() - t0
        self.log(f"moves {len(times)}: " + ", ".join(
            f"{t:.3f}s" for t in times) + f"; window {wall:.3f}s")
        return {"attempted": len(times), "failed": 0, "wall_s": wall,
                "playouts": playouts, "move_s": times}

    def end_to_end(self, rec: dict) -> dict:
        return {"search_playouts_per_s": rec["playouts"] / rec["wall_s"]}

    def after_trace(self, log) -> None:
        """One extra one-round search with the device counters on (a program
        of its own, bit-identical results); its time is not read."""
        import jax
        import jax.numpy as jnp

        from repro.core.gscpm import gscpm_search

        W, m = self.gcfg.n_workers, self.gcfg.grain
        cfg = dataclasses.replace(self.gcfg, metrics=True, n_playouts=W * m,
                                  n_tasks=W)
        tree, st = gscpm_search(jnp.zeros(cfg.game_obj.n_cells, jnp.int8), 1,
                                cfg, jax.random.key(self.seed % (2**31)))
        del tree
        mx = st["metrics"]
        log(f"search counters (one round, empty board): depth mean "
            f"{mx['depth_mean']:.4f}, expansion collisions per proposal "
            f"{mx['expand_collision_rate']:.4f}, leaf collisions per playout "
            f"{mx['leaf_collision_rate']:.4f}, masked-lane fraction "
            f"{st['masked_lane_fraction']:.4f}")

    def check(self, rec: dict, log) -> dict:
        from harness import reference as ref

        iters = int(self.mix.get("replay_iterations", 32))
        kept = [(mv, host_tree(tree), st) for mv, tree, st in self.kept]
        self.kept = []            # the device trees are freed here
        audit = best = replay = 0
        t = time.perf_counter()
        for mv, tree, st in kept:
            a = ref.audit_tree(tree, mv["board"], mv["to_move"], st["playouts"])
            audit += sum(a.values())
            best += int(ref.best_child_move(tree) != st["best_move"])
            rep = ref.replay_prefix(self.cfg, mv["board"], mv["to_move"],
                                    mv["key_seed"], iters, guide=tree)
            replay += ref.replay_mismatch(rep, tree)
            log(f"move {mv['index']}: audit {a}, replayed {iters} iterations "
                f"({rep.tree.n_nodes} nodes, {rep.near_ties} near ties, "
                f"{rep.taken_runner_up} to the runner-up)")
        log(f"reference: {len(kept)} moves in {time.perf_counter() - t:.1f}s")
        return {"tree_audit_violations": {"value": audit, "limit": 0},
                "reported_move_mismatch": {"value": best, "limit": 0},
                "replay_node_mismatch": {"value": replay, "limit": 0}}
