"""Training step: loss + grad + AdamW, microbatched, optionally compressed.

Two jit-able step functions:

- ``train_step`` — the GSPMD path: batch sharded over ("pod","data"),
  gradient all-reduce inserted automatically by the partitioner.
- ``train_step_compressed`` — identical math, but the step runs inside a
  ``shard_map`` that is *manual over the pod axis only* (data/model stay on
  the GSPMD auto path); the cross-pod gradient reduction goes through
  int8 block-quantized all-gather (``repro.optim.compression``) — the
  DCN-friendly distributed-optimization trick from DESIGN.md §6.

**Grain size control, the training-side analogue** (DESIGN.md §4): the
global batch is split into ``n_microbatches`` grains accumulated under
``lax.scan``. Exactly like the paper's nTasks dial, more grains trade
parallel width (per-step live activation memory) against loop overhead;
§Perf hillclimbs it.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import api
from repro.models.common import ModelConfig
from repro.optim import adamw
from repro.optim.compression import compressed_psum_tree
from repro.sharding import rules as shr

BATCH_KEYS = ("tokens", "labels", "mask", "patches", "frames")


def make_state(cfg: ModelConfig, key: jax.Array,
               moment_dtype: str = "float32") -> dict:
    params = api.init_params(cfg, key)
    return {"params": params,
            "opt": adamw.init_opt_state(params, moment_dtype)}


def abstract_state(cfg: ModelConfig, moment_dtype: str = "float32") -> dict:
    """ShapeDtypeStruct state tree (dry-run stand-in, no allocation)."""
    params = api.abstract_params(cfg)
    mdt = jnp.dtype(moment_dtype)
    mom = lambda p: jax.ShapeDtypeStruct(p.shape, mdt)
    return {"params": params,
            "opt": {"m": jax.tree.map(mom, params),
                    "v": jax.tree.map(mom, params),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}


def state_axes(cfg: ModelConfig) -> dict:
    """Logical-axis tree parallel to the state (moments shard like params)."""
    axes = api.param_axes(cfg)
    is_ax = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)
    return {"params": axes,
            "opt": {"m": jax.tree.map(lambda a: a, axes, is_leaf=is_ax),
                    "v": jax.tree.map(lambda a: a, axes, is_leaf=is_ax),
                    "step": ()}}


def _split_micro(batch: dict, n: int) -> dict:
    """(B, ...) -> (n, B/n, ...) for every present batch leaf."""
    def sp(x):
        B = x.shape[0]
        assert B % n == 0, (B, n)
        return x.reshape(n, B // n, *x.shape[1:])
    return {k: sp(v) for k, v in batch.items() if k in BATCH_KEYS}


def _mean_grads(cfg: ModelConfig, params, batch: dict, n_micro: int,
                accum_dtype=jnp.float32):
    """Microbatch-accumulated (loss, grads) — the grain-size scan."""
    loss_fn = lambda p, b: api.loss(p, cfg, b)
    if n_micro <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads
    micro = _split_micro(batch, n_micro)

    def body(carry, mb):
        acc_loss, acc_g = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        return (acc_loss + loss,
                jax.tree.map(lambda a, gg: a + gg.astype(a.dtype),
                             acc_g, g)), None

    from repro.models.common import maybe_scan
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
    (loss_sum, grad_sum), _ = maybe_scan(cfg, body, (jnp.float32(0.0), zeros),
                                         micro)
    inv = 1.0 / n_micro
    return loss_sum * inv, jax.tree.map(lambda g: (g * inv).astype(g.dtype),
                                        grad_sum)


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig,
                    n_microbatches: int = 1, accum_dtype=jnp.float32):
    """The GSPMD train step: state, batch -> state, metrics."""

    def step(state: dict, batch: dict):
        loss, grads = _mean_grads(cfg, state["params"], batch, n_microbatches,
                                  accum_dtype)
        params, opt, metrics = adamw.adamw_update(
            state["params"], grads, state["opt"], opt_cfg)
        metrics["loss"] = loss
        return {"params": params, "opt": opt}, metrics

    return step


def make_train_step_compressed(cfg: ModelConfig, opt_cfg: adamw.OptConfig,
                               mesh, n_microbatches: int = 1,
                               pod_axis: str = "pod"):
    """Manual-over-pod step with the int8-compressed cross-pod reduce."""
    from jax.sharding import PartitionSpec as P
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape))[pod_axis]

    def inner(state: dict, batch: dict):
        loss, grads = _mean_grads(cfg, state["params"], batch, n_microbatches)
        # cross-pod mean with int8 on the wire (exact path: lax.pmean)
        grads = jax.tree.map(lambda g: g / n_pods,
                             compressed_psum_tree(grads, pod_axis))
        loss = jax.lax.pmean(loss, pod_axis)
        params, opt, metrics = adamw.adamw_update(
            state["params"], grads, state["opt"], opt_cfg)
        metrics["loss"] = loss
        return {"params": params, "opt": opt}, metrics

    def step(state: dict, batch: dict):
        batch_specs = {k: P(pod_axis) for k in batch}
        f = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state), batch_specs),
            out_specs=(jax.tree.map(lambda _: P(), state),
                       {"loss": P(), "grad_norm": P(), "lr": P(),
                        "skipped": P()}),
            axis_names={pod_axis}, check_vma=False)
        return f(state, batch)

    return step


# --------------------------------------------------------------- shardings ----
def state_shardings(mesh, cfg: ModelConfig, rules=None):
    axes = state_axes(cfg)
    is_ax = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)
    return jax.tree.map(
        lambda a: shr.named_sharding(mesh, a, rules), axes, is_leaf=is_ax)


def batch_shardings(mesh, batch_tree, rules=None):
    def leading_batch(x):
        ndim = len(x.shape) if hasattr(x, "shape") else x.ndim
        return shr.named_sharding_for(
            mesh, ("batch",) + (None,) * (ndim - 1), tuple(x.shape), rules)
    return jax.tree.map(leading_batch, batch_tree)


def jit_train_step(step_fn, mesh, cfg: ModelConfig, batch_tree, rules=None,
                   donate: bool = True):
    """jit with explicit in/out shardings for the production mesh."""
    ss = state_shardings(mesh, cfg, rules)
    bs = batch_shardings(mesh, batch_tree, rules)
    ms = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(
        step_fn,
        in_shardings=(ss, bs),
        out_shardings=(ss, {"loss": ms, "grad_norm": ms, "lr": ms,
                            "skipped": ms}),
        donate_argnums=(0,) if donate else ())
