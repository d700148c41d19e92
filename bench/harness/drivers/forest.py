"""Closed-loop root-parallel search: back-to-back ``gscpm_search_batch``
moves of an E-tree forest sharded over the chips, with root statistics
merged across trees every ``merge_every`` rounds.

The moves follow the same position stream and start rule as the
single-tree search driver. The check keeps move 0's forest and a seeded
reservoir of later ones, and holds each against the plain reference: every
member's tree audit, the merge (after the last merge every member that
holds a root move holds the ensemble's total for it, and every root holds
the ensemble's playouts), the reported moves, and a replay of one seeded
member's first sync iterations node by node.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness.spans import span

from harness.drivers.search import TREE_FIELDS, gscpm_config


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, log):
        from harness import traffic

        self.cfg, self.mix, self.seed, self.log = cfg, mix, seed, log
        self.chips = chips
        self.gcfg = gscpm_config(cfg)
        self.E = int(cfg["n_trees"])
        self.stream = traffic.closed_loop(mix, seed, cfg["board_size"])
        self.keep = int(mix.get("check_moves", 2))
        self.rng = np.random.default_rng([seed, 3])
        self.kept: list = []

    def _search(self, gcfg, board, to_move, key_seed):
        import jax
        import jax.numpy as jnp

        from repro.core.root_parallel import gscpm_search_batch

        return gscpm_search_batch(
            jnp.asarray(board), to_move, gcfg, jax.random.key(key_seed),
            n_trees=self.E, merge_every=int(self.cfg["merge_every"]),
            shard=self.cfg["shard"])

    def setup(self) -> None:
        """Warms the cell's programs with a search of up to two rounds of
        one iteration each (the grain is traced, not compiled in): the
        second round and merge take their inputs from the first merge,
        whose output placement differs from ``device_put``'s, so they are
        programs of their own."""
        import jax

        W = self.gcfg.n_workers
        rounds = min(2, -(-self.gcfg.n_tasks // W))
        probe = dataclasses.replace(self.gcfg, n_playouts=rounds * W,
                                    n_tasks=rounds * W)
        forest, st = self._search(probe, np.zeros(self.gcfg.game_obj.n_cells,
                                                  np.int8), 1, 0)
        jax.block_until_ready(forest.visits)
        if st["n_devices"] != self.chips:
            raise RuntimeError(f"the forest ran on {st['n_devices']} devices, "
                               f"the cell asks for {self.chips}")
        del forest

    def _keep(self, i: int, item) -> None:
        if i == 0 or len(self.kept) < self.keep:
            self.kept.append(item)
            return
        j = int(self.rng.integers(0, i))
        if j < self.keep - 1:
            self.kept[1 + j] = item

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        times, playouts = [], 0
        while True:
            elapsed = time.perf_counter() - t0
            if times and elapsed + float(np.mean(times)) > seconds:
                break
            mv = next(self.stream)
            with span("move"):
                ts = time.perf_counter()
                forest, st = self._search(self.gcfg, mv["board"],
                                          mv["to_move"], mv["key_seed"])
                times.append(time.perf_counter() - ts)
            playouts += st["playouts"]
            self._keep(mv["index"], (mv, forest, st))
        wall = time.perf_counter() - t0
        self.log(f"moves {len(times)}: " + ", ".join(
            f"{t:.3f}s" for t in times) + f"; window {wall:.3f}s")
        return {"attempted": len(times), "failed": 0, "wall_s": wall,
                "playouts": playouts}

    def end_to_end(self, rec: dict) -> dict:
        return {"search_playouts_per_s": rec["playouts"] / rec["wall_s"]}

    def after_trace(self, log) -> None:
        pass

    def check(self, rec: dict, log) -> dict:
        from harness import reference as ref

        iters = int(self.mix.get("replay_iterations", 32))
        kept = [(mv, {k: np.asarray(getattr(f, k)) for k in TREE_FIELDS}, st)
                for mv, f, st in self.kept]
        self.kept = []                   # the device forests are freed here
        n_moves = self.cfg["board_size"] ** 2
        audit = merge = reported = replay = 0
        t = time.perf_counter()
        for mv, forest, st in kept:
            total = st["playouts"]
            members = [{k: v[e] for k, v in forest.items()}
                       for e in range(self.E)]
            dense = [ref.dense_root(m, n_moves) for m in members]
            for m in members:
                audit += sum(ref.audit_tree(m, mv["board"], mv["to_move"],
                                            total, injected_root=True).values())
            held = np.array([d[2] for d in dense])
            anyone = held.any(axis=0)
            for a in np.flatnonzero(anyone):
                vs = {float(d[0][a]) for d, h in zip(dense, held[:, a]) if h}
                ws = {float(d[1][a]) for d, h in zip(dense, held[:, a]) if h}
                merge += int(len(vs) > 1 or len(ws) > 1)
            for m, d, h in zip(members, dense, held):
                if (h == anyone).all():
                    merge += int(d[0].sum() != m["visits"][0])
            summed = sum(d[0] for d in dense)
            best = [ref.best_child_move(m) for m in members]
            votes = np.bincount(np.clip(best, 0, n_moves - 1), minlength=n_moves)
            reported += int(int(np.argmax(summed)) != st["best_move_sum"])
            reported += int(int(np.argmax(votes)) != st["best_move_vote"])
            reported += int(best != list(st["member_best_moves"]))
            e = int(self.rng.integers(0, self.E))
            # the first round, before the first merge changes the root
            rep = ref.replay_prefix(self.cfg, mv["board"], mv["to_move"],
                                    mv["key_seed"], iters, member=e,
                                    rounds=1, guide=members[e])
            replay += ref.replay_mismatch(rep, members[e])
            log(f"move {mv['index']}: {self.E} trees audited, member {e} "
                f"replayed {iters} iterations ({rep.tree.n_nodes} nodes, "
                f"{rep.near_ties} near ties, {rep.taken_runner_up} to the "
                f"runner-up)")
        log(f"reference: {len(kept)} moves in {time.perf_counter() - t:.1f}s")
        return {"tree_audit_violations": {"value": audit, "limit": 0},
                "root_merge_violations": {"value": merge, "limit": 0},
                "reported_move_mismatch": {"value": reported, "limit": 0},
                "replay_node_mismatch": {"value": replay, "limit": 0}}
