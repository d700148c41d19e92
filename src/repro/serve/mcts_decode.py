"""GSCPM-guided LM decoding — the paper's technique as a serving feature.

A search job over token continuations is exactly the paper's "logical task
of fungible iterations" (DESIGN.md §4): ``n_playouts`` UCT iterations are
split into ``n_tasks`` grains of ``m`` iterations, scheduled onto
``n_workers`` vmapped lanes against ONE shared token tree, by the same
``repro.core.scheduler`` disciplines the Hex engine uses.

Mapping of MCTS steps onto the LM:

- *state* of a node at depth k = prompt ⊕ k tree tokens; the root holds the
  prefilled prompt KV cache (computed once, broadcast to the worker lanes).
- *selection*: level-synchronous UCT descent over up-to-``branch`` children
  per node — all W lanes step down the token tree in lockstep, one
  ``kernels.ops.uct_select`` (W, C) tile per level, the same batched descent
  as the Hex engine (single-agent: a node's value is its mean rollout score).
- *expansion*: an untried token among the leaf's top-``branch`` logits;
  batch-deduped via the same prefix-sum allocator as Hex (token ids are
  legal `move`s since expand_batch orders (leaf, move) lexicographically).
- *playout*: ``rollout_len`` sampled continuation tokens; the score is
  exp(mean logprob) ∈ (0,1] — the model's own confidence in the line
  (a likelihood-based stand-in for the game result Δ).
- *backup*: scatter-add of the score along the path (atomics → .at[].add).

Every playout replays its path through the decode step (positions after the
prompt are rewritten each iteration, so one (W, S_max) cache serves all
iterations without copying).

Multi-request root parallelism (DESIGN.md §3): ``mcts_decode_search_batch``
stacks B independent token trees (one per concurrent request) into a forest
and advances ALL of them through one shared jitted step — ``jax.vmap`` over
the single-request chunk, with the KV cache's per-leaf batch axis split into
(requests, lanes). ``prompt_len`` is a *traced* per-request scalar, so a
batch may mix prompt lengths (shorter prompts are left-aligned in a padded
token matrix; decode masks positions beyond each request's own cursor) and
token commits never recompile.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.core import uct as uct_mod
from repro.core.gscpm import (advance_paths, expand_batch, fold_task_keys,
                              level_noise)
from repro.core.root_parallel import fold_member_task_keys
from repro.core.tree import (NO_NODE, Tree, best_child, child_stat_tile,
                             init_forest, init_tree)
from repro.kernels import ops
from repro.models import api
from repro.models.common import ModelConfig


@dataclasses.dataclass(frozen=True)
class MCTSDecodeConfig:
    """Fields marked compare=False are excluded from hash/eq: ``cp`` reaches
    the jitted chunks as a traced operand and the playout/task/scheduler
    knobs only shape the host-side schedule (grain arrives as traced ``m``),
    so sweeping them shares one compiled program. Traced code must never
    read a compare=False field directly."""

    n_playouts: int = dataclasses.field(default=128, compare=False)
    # the grain dial: m = n_playouts / n_tasks
    n_tasks: int = dataclasses.field(default=16, compare=False)
    n_workers: int = 8           # vmapped lanes through the LM
    cp: float = dataclasses.field(default=1.0, compare=False)
    branch: int = 8              # children per node = top-k tokens
    max_depth: int = 6           # tree horizon in tokens
    rollout_len: int = 8
    temperature: float = 1.0
    select_noise: float = 1e-3
    tree_cap: int = 2048
    scheduler: str = dataclasses.field(default="fifo", compare=False)
    descent: str = "batched"     # batched (level-synchronous) | scalar (oracle)

    @property
    def grain(self) -> int:
        return max(1, self.n_playouts // max(1, self.n_tasks))


# ------------------------------------------------------------- selection ----
def select_token_path(tree: Tree, cfg: MCTSDecodeConfig, noise_key: jax.Array,
                      cp=None):
    """UCT descent to a not-fully-expanded node (single-agent values).

    Per-lane scalar oracle for ``select_token_batch`` (``cp`` defaults to
    cfg.cp for standalone use; the jitted chunks pass the traced operand).
    """
    cap = tree.cap
    C = tree.max_children
    max_path = cfg.max_depth + 2
    cp = cfg.cp if cp is None else cp
    path0 = jnp.full((max_path,), cap, dtype=jnp.int32).at[0].set(0)

    def cond(st):
        node, depth, path, done = st
        return ~done

    def body(st):
        node, depth, path, _ = st
        n_kids = tree.n_children[node]
        fully = (n_kids >= cfg.branch) & (depth < cfg.max_depth)
        slots = tree.children[node]
        valid = jnp.arange(C, dtype=jnp.int32) < n_kids
        safe = jnp.where(valid, slots, cap)
        visits = tree.visits[safe]
        scores = uct_mod.uct_scores(
            tree.wins[safe], visits, jnp.zeros_like(visits),
            tree.visits[node], cp, valid)
        noise = cfg.select_noise * jax.random.uniform(
            jax.random.fold_in(noise_key, depth), (C,))
        child = safe[uct_mod.select_child(scores, noise)]
        nxt = (child, depth + 1, path.at[depth + 1].set(child), False)
        stay = (node, depth, path, True)
        return jax.tree.map(lambda a, b: jnp.where(fully, a, b), nxt, stay)

    node, depth, path, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(0), path0, False))
    return path, depth, node


def select_token_batch(tree: Tree, cfg: MCTSDecodeConfig, cp,
                       noise_keys: jax.Array):
    """Level-synchronous batched descent over the token tree.

    The LM twin of ``gscpm.select_batch``: all W lanes step down in
    lockstep, one ``kernels.ops.uct_select`` (W, C) tile per level, with
    finished lanes masked and held. Bit-identical to
    ``jax.vmap(select_token_path)`` under the same RNG schedule.
    """
    cap = tree.cap
    C = tree.max_children
    max_path = cfg.max_depth + 2
    W = noise_keys.shape[0]

    nodes0 = jnp.zeros((W,), jnp.int32)
    depths0 = jnp.zeros((W,), jnp.int32)
    paths0 = jnp.full((W, max_path), cap, dtype=jnp.int32).at[:, 0].set(0)
    done0 = jnp.zeros((W,), bool)

    def cond(st):
        return ~st[-1].all()

    def body(st):
        nodes, depths, paths, done = st
        n_kids = tree.n_children[nodes]
        fully = (n_kids >= cfg.branch) & (depths < cfg.max_depth)
        safe, valid, wins, visits, vloss, ptot = child_stat_tile(tree, nodes)
        noise = level_noise(noise_keys, depths, C, cfg.select_noise)
        picks = ops.uct_select(wins, visits, vloss, ptot, valid, cp,
                               noise=noise, lane_mask=~done)
        child = safe[jnp.arange(W), picks]
        step = fully & ~done
        nodes = jnp.where(step, child, nodes)
        paths = advance_paths(paths, depths, child, step)
        depths = jnp.where(step, depths + 1, depths)
        return nodes, depths, paths, done | ~step

    nodes, depths, paths, _ = jax.lax.while_loop(
        cond, body, (nodes0, depths0, paths0, done0))
    return paths, depths, nodes


def path_tokens(tree: Tree, path: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Tokens along the path (token of path[t+1]), 0-padded."""
    toks = tree.move[path[1:max_depth + 1]]
    return jnp.maximum(toks, 0).astype(jnp.int32)


def propose_token(tree: Tree, leaf: jnp.ndarray, leaf_logits: jnp.ndarray,
                  cfg: MCTSDecodeConfig, depth: jnp.ndarray,
                  key: jax.Array) -> jnp.ndarray:
    """Random untried token among the leaf's top-`branch` logits (-1: none)."""
    _, top_tok = jax.lax.top_k(leaf_logits, cfg.branch)   # (branch,)
    tried = tree.child_move[leaf]          # (C,), NO_NODE past its children
    is_tried = (top_tok[:, None] == tried[None, :]).any(axis=1)  # (branch,)
    can = ~is_tried & (depth < cfg.max_depth)
    g = jax.random.gumbel(key, (cfg.branch,))
    pick = jnp.argmax(jnp.where(can, g, -jnp.inf))
    return jnp.where(can.any(), top_tok[pick], NO_NODE).astype(jnp.int32)


# ----------------------------------------------------------------- backup ----
def backup_values(tree: Tree, paths: jnp.ndarray, values: jnp.ndarray,
                  weights: jnp.ndarray) -> Tree:
    """Single-agent scatter-add backup: every node on the path gains value."""
    W, D = paths.shape
    flat = paths.reshape(-1)
    w = jnp.repeat(weights, D) * (flat != tree.cap)
    visits = tree.visits.at[flat].add(w).at[tree.cap].set(0.0)
    wins = tree.wins.at[flat].add(
        w * jnp.repeat(values, D)).at[tree.cap].set(0.0)
    return tree._replace(visits=visits, wins=wins)


# ---------------------------------------------------------- one iteration ----
def _iteration(tree: Tree, params, mcfg: ModelConfig, cfg: MCTSDecodeConfig,
               cache, root_logits: jnp.ndarray, prompt_len, cp,
               iter_keys: jnp.ndarray, active: jnp.ndarray):
    """One batched GSCPM iteration of width W against the shared token tree.

    ``prompt_len`` is a traced i32 scalar (per-request under vmap), not a
    static python int — decode positions are computed from it, so one
    compiled program serves every prompt length up to the cache size.
    ``cp`` is the traced exploration constant (never read from cfg here).
    """
    W = cfg.n_workers
    V = root_logits.shape[-1]
    prompt_len = jnp.asarray(prompt_len, jnp.int32)

    noise_keys = jax.vmap(lambda k: jax.random.fold_in(k, 0))(iter_keys)
    if cfg.descent == "scalar":
        sel = jax.vmap(lambda k: select_token_path(tree, cfg, k, cp)
                       )(noise_keys)
    else:
        sel = select_token_batch(tree, cfg, cp, noise_keys)
    paths, depths, leaves = sel                                # (W, D), (W,), (W,)
    toks = jax.vmap(lambda p: path_tokens(tree, p, cfg.max_depth))(paths)

    # --- replay the paths through the decode step (lockstep positions) ----
    def replay_step(t, carry):
        cache, leaf_logits = carry
        tok_t = toks[:, t][:, None]                            # (W,1)
        logits, cache = api.decode(params, mcfg, tok_t,
                                   prompt_len + t, cache)
        leaf_logits = jnp.where((depths == t + 1)[:, None],
                                logits[:, 0, :], leaf_logits)
        return cache, leaf_logits

    leaf_logits0 = jnp.broadcast_to(root_logits, (W, V))
    cache, leaf_logits = jax.lax.fori_loop(
        0, cfg.max_depth, replay_step, (cache, leaf_logits0))

    # --- expansion (dedup batch insert, same allocator as Hex) ------------
    k_prop = jax.vmap(lambda k: jax.random.fold_in(k, 1))(iter_keys)
    moves = jax.vmap(
        lambda l, ll, d, k: propose_token(tree, l, ll, cfg, d, k)
    )(leaves, leaf_logits, depths, k_prop)
    tree, new_ids = expand_batch(tree, leaves, moves, active)
    expanded = new_ids < tree.cap
    paths = jnp.where(
        jnp.arange(paths.shape[1])[None, :] == (depths + 1)[:, None],
        jnp.where(expanded[:, None], new_ids[:, None], tree.cap), paths)

    # --- rollout: expanded token first, then sampled continuation --------
    start_pos = prompt_len + cfg.max_depth   # parked replay ends here

    def rollout(cache):
        tok0 = jnp.where(expanded, jnp.maximum(moves, 0),
                         jnp.argmax(leaf_logits, -1).astype(jnp.int32))

        def body(t, carry):
            cache, tok, logp_sum = carry
            logits, cache = api.decode(params, mcfg, tok[:, None],
                                       start_pos + t, cache)
            logits = logits[:, 0, :].astype(jnp.float32)
            logits_t = logits / max(cfg.temperature, 1e-6)
            keys = jax.vmap(
                lambda k: jax.random.fold_in(jax.random.fold_in(k, 2), t)
            )(iter_keys)
            nxt = jax.vmap(jax.random.categorical)(keys, logits_t)
            logp = jax.nn.log_softmax(logits, axis=-1)
            lp = jnp.take_along_axis(logp, nxt[:, None], axis=1)[:, 0]
            return cache, nxt.astype(jnp.int32), logp_sum + lp

        cache, _, logp_sum = jax.lax.fori_loop(
            0, cfg.rollout_len, body,
            (cache, tok0, jnp.zeros((W,), jnp.float32)))
        return cache, logp_sum

    cache, logp_sum = rollout(cache)
    values = jnp.exp(logp_sum / cfg.rollout_len)               # (0,1]
    tree = backup_values(tree, paths, values, active.astype(jnp.float32))
    return tree, cache


@functools.partial(jax.jit,
                   static_argnames=("mcfg", "cfg"),
                   donate_argnums=(0, 4))
def run_chunk(tree: Tree, params, mcfg: ModelConfig, cfg: MCTSDecodeConfig,
              cache, root_logits, prompt_len, task_keys, active,
              m, cp) -> tuple[Tree, Any]:
    """m sync iterations — one task grain per lane (jitted once per config;
    ``prompt_len``, ``m`` and ``cp`` are traced, so prompt length, grain and
    Cp changes do not recompile)."""

    def body(i, carry):
        tree, cache = carry
        iter_keys = jax.vmap(lambda tk: jax.random.fold_in(tk, i))(task_keys)
        return _iteration(tree, params, mcfg, cfg, cache, root_logits,
                          prompt_len, cp, iter_keys, active)

    return jax.lax.fori_loop(0, m, body, (tree, cache))


@functools.partial(jax.jit,
                   static_argnames=("mcfg", "cfg", "cache_axes_def"),
                   donate_argnums=(0, 4))
def run_chunk_batch(forest: Tree, params, mcfg: ModelConfig,
                    cfg: MCTSDecodeConfig, cache, root_logits, prompt_lens,
                    task_keys, active, m, cp, cache_axes_def
                    ) -> tuple[Tree, Any]:
    """`run_chunk` vmapped over B concurrent requests — one jitted program.

    forest: B stacked trees; cache leaves carry a (B, W) split batch axis at
    each leaf's own position (``cache_axes_def``, hashable static arg);
    root_logits (B, V); prompt_lens (B,); task_keys/active (B, W); ``cp`` a
    traced scalar shared by all requests.
    """
    cache_axes = jax.tree.unflatten(
        jax.tree.structure(cache), list(cache_axes_def))

    def one(tree, cache_b, rl, pl, keys, act):
        def body(i, carry):
            tr, ch = carry
            iter_keys = jax.vmap(
                lambda tk: jax.random.fold_in(tk, i))(keys)
            return _iteration(tr, params, mcfg, cfg, ch, rl, pl, cp,
                              iter_keys, act)

        return jax.lax.fori_loop(0, m, body, (tree, cache_b))

    return jax.vmap(one, in_axes=(0, cache_axes, 0, 0, 0, 0),
                    out_axes=(0, cache_axes))(
        forest, cache, root_logits, prompt_lens, task_keys, active)


# ------------------------------------------------------------------ driver ----
def mcts_decode_search(params, mcfg: ModelConfig, prompt: jnp.ndarray,
                       cfg: MCTSDecodeConfig, key: jax.Array,
                       batch_extras: dict | None = None
                       ) -> tuple[Tree, dict[str, Any]]:
    """One GSCPM search for the best next token after `prompt` (1D i32)."""
    prompt_len = int(prompt.shape[0])
    max_len = prompt_len + cfg.max_depth + cfg.rollout_len + 1
    # prefill with the prompt tiled across the worker lanes: every lane gets
    # its own copy of the prompt KV (cache leaves are layer-stacked, so this
    # is simpler and shape-agnostic vs broadcasting a batch axis mid-tree)
    W = cfg.n_workers
    tiled = jnp.tile(prompt[None, :], (W, 1))
    extras = {k: jnp.tile(v, (W,) + (1,) * (v.ndim - 1))
              for k, v in (batch_extras or {}).items()}
    root_logits, cache = api.prefill(params, mcfg,
                                     {"tokens": tiled, **extras}, max_len)
    root_logits = root_logits[0, 0].astype(jnp.float32)

    tree = init_tree(cfg.tree_cap, cfg.branch, 1)
    schedule = sched.make_schedule(
        cfg.n_playouts, cfg.n_tasks, cfg.n_workers, cfg.scheduler)

    cp = jnp.asarray(cfg.cp, jnp.float32)
    t0 = time.perf_counter()
    playouts = 0
    for rnd in schedule:
        task_keys = fold_task_keys(key, jnp.asarray(rnd.task_ids, dtype=jnp.int32))
        active = jnp.asarray(rnd.active)
        tree, cache = run_chunk(tree, params, mcfg, cfg, cache, root_logits,
                                jnp.asarray(prompt_len, jnp.int32),
                                task_keys, active,
                                jnp.asarray(rnd.m, jnp.int32), cp)
        playouts += int(rnd.active.sum()) * rnd.m
    jax.block_until_ready(tree.visits)
    dt = time.perf_counter() - t0

    best = best_child(tree)
    stats = {
        "time_s": dt,
        "playouts": playouts,
        "playouts_per_s": playouts / max(dt, 1e-9),
        "tree_nodes": int(tree.n_nodes),
        "best_token": int(best),
        "grain": cfg.grain,
        "root_children": int(tree.n_children[0]),
    }
    return tree, stats


# --------------------------------------------- multi-request root parallel ----
def mcts_decode_search_batch(params, mcfg: ModelConfig, prompts: jnp.ndarray,
                             cfg: MCTSDecodeConfig, key: jax.Array, *,
                             prompt_lens: jnp.ndarray | None = None,
                             request_mask: jnp.ndarray | None = None,
                             batch_extras: dict | None = None
                             ) -> tuple[Tree, dict[str, Any]]:
    """Root-parallel GSCPM decode: B requests, B trees, ONE jitted step.

    prompts: (B, P) i32, left-aligned; rows shorter than P declare their true
    length in ``prompt_lens`` (pad tail tokens are never attended: root
    logits come from a decode at each request's own last real position, and
    every later decode masks positions beyond its cursor). ``request_mask``
    (B,) bool masks whole requests (their lanes run dead and their trees stay
    empty) — the slot-engine's empty-slot mechanism.

    Per round, ALL B trees advance through one ``run_chunk_batch`` dispatch;
    there is no per-request Python loop (DESIGN.md §3).
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    if prompts.ndim == 1:
        prompts = prompts[None, :]
    B, P = prompts.shape
    W = cfg.n_workers
    lens = (jnp.full((B,), P, jnp.int32) if prompt_lens is None
            else jnp.asarray(prompt_lens, jnp.int32))
    mask = (jnp.ones((B,), bool) if request_mask is None
            else jnp.asarray(request_mask, bool))
    max_len = P + cfg.max_depth + cfg.rollout_len + 1

    # request-major tiling: lane w of request b sits at row b*W + w
    tiled = jnp.repeat(prompts, W, axis=0)                       # (B*W, P)
    extras = {k: jnp.repeat(jnp.asarray(v), W, axis=0)
              for k, v in (batch_extras or {}).items()}
    _, cache = api.prefill(params, mcfg, {"tokens": tiled, **extras}, max_len)
    # root logits at each request's true last position (prefill's last-column
    # logits would read a pad token for short rows); the rewrite of the last
    # real token's KV is idempotent
    last_tok = prompts[jnp.arange(B), lens - 1]
    logits, cache = api.decode(params, mcfg,
                               jnp.repeat(last_tok, W)[:, None],
                               jnp.repeat(lens - 1, W), cache)
    root_logits = logits.reshape(B, W, -1)[:, 0, :].astype(jnp.float32)

    # split every cache leaf's (B*W) batch axis into (B, W) at its own index
    axes_tree = api.cache_batch_axes(mcfg, B * W, max_len)
    cache = jax.tree.map(
        lambda x, bi: x.reshape(x.shape[:bi] + (B, W) + x.shape[bi + 1:]),
        cache, axes_tree)
    cache_axes_def = tuple(jax.tree.leaves(axes_tree))

    forest = init_forest(B, cfg.tree_cap, cfg.branch, 1)
    member_keys = fold_task_keys(key, jnp.arange(B, dtype=jnp.int32))
    schedule = sched.make_schedule(
        cfg.n_playouts, cfg.n_tasks, cfg.n_workers, cfg.scheduler)

    cp = jnp.asarray(cfg.cp, jnp.float32)
    t0 = time.perf_counter()
    playouts_per_req = 0
    for rnd in schedule:
        task_keys = fold_member_task_keys(
            member_keys, jnp.asarray(rnd.task_ids, dtype=jnp.int32))
        active = jnp.asarray(rnd.active)[None, :] & mask[:, None]   # (B, W)
        forest, cache = run_chunk_batch(
            forest, params, mcfg, cfg, cache, root_logits, lens,
            task_keys, active, jnp.asarray(rnd.m, jnp.int32), cp,
            cache_axes_def)
        playouts_per_req += int(rnd.active.sum()) * rnd.m
    jax.block_until_ready(forest.visits)
    dt = time.perf_counter() - t0

    n_req = int(np.asarray(mask).sum())
    # best_child returns the most-visited root child's move (token); a
    # masked request's empty tree yields NO_NODE (-1)
    best = np.asarray(jax.vmap(best_child)(forest))
    playouts = n_req * playouts_per_req
    stats = {
        "time_s": dt,
        "n_requests": B,
        "n_active_requests": n_req,
        "playouts": playouts,
        "playouts_per_request": playouts_per_req,
        "playouts_per_s": playouts / max(dt, 1e-9),
        "grain": cfg.grain,
        "tree_nodes": [int(n) for n in np.asarray(forest.n_nodes)],
        "best_tokens": best.tolist(),
        "root_children": [int(n) for n in np.asarray(forest.n_children[:, 0])],
    }
    return forest, stats


def mcts_generate_batch(params, mcfg: ModelConfig, prompts, prompt_lens,
                        n_tokens: int, cfg: MCTSDecodeConfig, key: jax.Array
                        ) -> tuple[np.ndarray, np.ndarray, list]:
    """Lockstep multi-request generation: one batched search per emitted
    token, all requests committing together. The token matrix keeps a fixed
    width of ``P0 + n_tokens``, so the whole generation reuses one compiled
    search program (prompt lengths are traced)."""
    prompts = np.asarray(prompts, np.int32)
    B, P0 = prompts.shape
    lens = np.asarray(prompt_lens, np.int32).copy()
    buf = np.zeros((B, P0 + n_tokens), np.int32)
    buf[:, :P0] = prompts
    all_stats = []
    for i in range(n_tokens):
        _, stats = mcts_decode_search_batch(
            params, mcfg, jnp.asarray(buf), cfg, jax.random.fold_in(key, i),
            prompt_lens=jnp.asarray(lens))
        toks = np.asarray(stats["best_tokens"], np.int32)
        buf[np.arange(B), lens] = toks
        lens += 1
        all_stats.append(stats)
    return buf, lens, all_stats


def mcts_generate(params, mcfg: ModelConfig, prompt: jnp.ndarray,
                  n_tokens: int, cfg: MCTSDecodeConfig, key: jax.Array,
                  batch_extras: dict | None = None) -> tuple[jnp.ndarray, list]:
    """Emit n_tokens, one GSCPM search per token (search-then-commit)."""
    toks = jnp.asarray(prompt, jnp.int32)
    all_stats = []
    for i in range(n_tokens):
        _, stats = mcts_decode_search(
            params, mcfg, toks, cfg, jax.random.fold_in(key, i), batch_extras)
        toks = jnp.concatenate(
            [toks, jnp.asarray([stats["best_token"]], jnp.int32)])
        all_stats.append(stats)
    return toks, all_stats
