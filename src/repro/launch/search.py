"""Search launcher: GSCPM over any registered game (DESIGN.md §13).

``python -m repro.launch.search --game gomoku --size 9 --playouts 2048``
runs a Grain-Size Controlled Parallel MCTS from the empty position and
prints the chosen move and throughput; ``--trees E`` switches to the
root-parallel forest (E trees advanced by one jitted program per round,
visit-sum + majority-vote merges). The ``--game`` flag resolves through the
``Game`` registry (``repro.core.game``) — Hex and Gomoku ship; new games
only need to register a protocol implementation.

``--moves N`` plays N moves of self-play from the empty board: after each
search the best move is committed and the tree is RE-ROOTED onto the played
child (``core.tree.reroot_tree``, DESIGN.md §16) so the next search starts
warm — the single-CLI demonstration of cross-move tree reuse. ``--cold``
ablates it (fresh tree every move); ``--reuse-tree`` is the default,
spelled out for symmetry.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.core import game as game_mod
from repro.core.gscpm import GSCPMConfig, gscpm_search
from repro.core.root_parallel import gscpm_search_batch
from repro.core.tree import reroot_forest, reroot_tree
from repro.launch.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--game", default="hex",
                   choices=list(game_mod.available_games()),
                   help="registered Game to search (core/game.py registry)")
    p.add_argument("--size", type=int, default=9, help="board side length")
    p.add_argument("--playouts", type=int, default=2048)
    p.add_argument("--tasks", type=int, default=64,
                   help="grain dial: m = playouts / tasks")
    p.add_argument("--workers", type=int, default=16, help="parallel lanes")
    p.add_argument("--trees", type=int, default=1,
                   help=">1: root-parallel ensemble of this many trees")
    p.add_argument("--scheduler", default="fifo",
                   choices=["fifo", "rebalance", "one_per_core",
                            "sequential"])
    p.add_argument("--cp", type=float, default=1.0)
    p.add_argument("--to-move", type=int, default=1, choices=[1, 2])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--moves", type=int, default=1,
                   help="play this many self-play moves (search, commit the "
                        "best move, re-root, repeat)")
    reuse = p.add_mutually_exclusive_group()
    reuse.add_argument("--reuse-tree", dest="reuse", action="store_true",
                       default=True,
                       help="warm-start each move from the re-rooted tree "
                            "(default)")
    reuse.add_argument("--cold", dest="reuse", action="store_false",
                       help="ablation: fresh tree every move")
    p.add_argument("--metrics", action="store_true",
                   help="thread the device-plane SearchMetrics accumulator "
                        "through the search and print its summary "
                        "(bit-identical results, one extra compiled "
                        "program)")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="record per-round spans as Chrome/Perfetto trace-"
                        "event JSON (blocks per round while tracing)")
    args = p.parse_args()
    enable_compile_cache()

    cfg = GSCPMConfig(game=args.game, board_size=args.size,
                      n_playouts=args.playouts, n_tasks=args.tasks,
                      n_workers=args.workers, cp=args.cp,
                      scheduler=args.scheduler,
                      tree_cap=max(1 << 14, 4 * args.playouts),
                      metrics=args.metrics)
    board = cfg.game_obj.init_board()
    key = jax.random.key(args.seed)
    tracer = None
    if args.trace:
        from repro.obsv import TraceRecorder
        tracer = TraceRecorder(process_name="repro-search")
        from repro.core import gscpm as gscpm_mod
        tracer.watch_compiles("run_chunk", gscpm_mod.run_chunk)

    game = cfg.game_obj
    to_move = args.to_move
    carry = None    # the re-rooted tree/forest warm-starting the next move
    for mvno in range(args.moves):
        key_mv = key if args.moves == 1 else jax.random.fold_in(key, mvno)
        reused = ""
        if args.trees > 1:
            forest, st = gscpm_search_batch(
                board, to_move, cfg, key_mv, n_trees=args.trees,
                forest=carry, tracer=tracer)
            mv = st["best_move_sum"]
            if "reused_nodes" in st:
                reused = f", reused {st['reused_nodes']} nodes"
            print(f"[{args.game} {args.size}x{args.size}] {st['n_trees']} "
                  f"trees, {st['playouts']} playouts in {st['time_s']:.2f}s "
                  f"({st['playouts_per_s']:.0f}/s, grain m={st['grain']}"
                  f"{reused})")
            print(f"  best move (visit-sum) {st['best_move_sum']}, "
                  f"(majority vote) {st['best_move_vote']}; member values "
                  f"{['%.3f' % v for v in st['member_root_values']]}")
        else:
            tree, st = gscpm_search(board, to_move, cfg, key_mv,
                                    tree=carry, tracer=tracer)
            mv = st["best_move"]
            if "reused_visits" in st:
                reused = (f", reused {st['reused_nodes']} nodes / "
                          f"{st['reused_visits']:.0f} visits")
            print(f"[{args.game} {args.size}x{args.size}] {st['playouts']} "
                  f"playouts in {st['time_s']:.2f}s "
                  f"({st['playouts_per_s']:.0f}/s, grain m={st['grain']}, "
                  f"{st['tree_nodes']} nodes{reused})")
            print(f"  best move {st['best_move']}, "
                  f"root value {st['root_value']:.3f}")
        if args.metrics:
            dm = st["metrics"]
            print(f"  device metrics: depth mean/max {dm['depth_mean']:.2f}/"
                  f"{dm['depth_max']}, {dm['expansions']} expansions "
                  f"({dm['expand_collision_rate']:.2f} collision rate), "
                  f"playout len mean/max {dm['playout_len_mean']:.1f}/"
                  f"{dm['playout_len_max']}, held levels {dm['held_levels']}, "
                  f"peak {dm['tree_nodes_peak']} nodes, "
                  f"reused {dm['tree_nodes_reused']}")
        if mvno == args.moves - 1 or mv < 0:
            break
        if args.reuse:
            carry = (reroot_forest(forest, mv) if args.trees > 1
                     else reroot_tree(tree, mv))
        board = game.place(board, jnp.int32(mv), jnp.int8(to_move))
        to_move = 3 - to_move
    if tracer is not None:
        from repro.obsv import validate_trace
        path = tracer.save(args.trace)
        print(f"  trace: {validate_trace(path)} events -> {path}")


if __name__ == "__main__":
    main()
