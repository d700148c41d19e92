"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

On a TPU, ``kernels.ops`` routes every search through two compiled Pallas
kernels: ``uct_select`` (one (W, C) tile per descent level) and
``hex_winner`` (every Hex playout). Interpret mode cannot see what Mosaic
refuses (unsupported shape casts, tiling, VMEM), so these tests compile
both kernels, and the whole paper-width search program, for a ``v5e:2x2``
topology that is described here and not attached. Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under several test workers the one given this file takes it. The
persistent compilation cache is off around these compiles, since an entry
written for a chip that is not attached cannot be read back here.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hex_winner as hw
from repro.kernels import uct_select as us


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _uct_args(lead, W, C, sharding):
    f = lambda *s: _spec(lead + s, jnp.float32, sharding)  # noqa: E731
    return (f(W, C), f(W, C), f(W, C), f(W), _spec(lead + (W, C), jnp.bool_,
                                                   sharding),
            _spec((), jnp.float32, sharding), f(W, C),
            _spec(lead + (W,), jnp.bool_, sharding))


def _select(wins, visits, vloss, ptot, valid, cp, noise, lane_mask):
    return us.uct_select(wins, visits, vloss, ptot, valid, cp, noise=noise,
                         lane_mask=lane_mask)


@pytest.mark.parametrize("W,C", [(244, 121), (16, 81)])
def test_uct_select_compiles_for_tpu(one_chip, W, C):
    compiled = jax.jit(_select).lower(*_uct_args((), W, C, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_uct_select_compiles_under_forest_vmap(one_chip):
    """The forest path: one (W, C) tile per tree of an 8-tree ensemble."""
    E, W, C = 8, 244, 121
    args = _uct_args((E,), W, C, one_chip)
    forest = jax.vmap(_select, in_axes=(0, 0, 0, 0, 0, None, 0, 0))
    compiled = jax.jit(forest).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the Pallas call as the compiled program names it: the device op's name,
# which the benchmark's roofline and phase readers look for, comes from the
# jitted function ``hex_winner`` around it
HEX_WINNER_CALL = re.compile(
    r'^\s*%hex_winner(\.\d+)? = .* custom-call\(.*'
    r'custom_call_target="tpu_custom_call"', re.M)


@pytest.mark.parametrize("size,W", [(11, 244), (9, 16), (13, 64),
                                    (11, 1024)])
def test_hex_winner_compiles_for_tpu(one_chip, size, W):
    """One block up to 512 rows; W = 1024 runs a grid of two."""
    boards = _spec((W, size * size), jnp.int8, one_chip)
    compiled = jax.jit(lambda b: hw.hex_winner(b, size)).lower(
        boards).compile()
    assert len(HEX_WINNER_CALL.findall(compiled.as_text())) == 1


def test_paper_search_program_compiles_for_tpu(one_chip, monkeypatch):
    """``run_chunk`` at ``configs/hex_paper.PAPER`` (11x11, 244 lanes,
    tree_cap 2^20) with both kernels inside, fitting one v5e chip."""
    from repro.configs.hex_paper import PAPER
    from repro.core import gscpm
    from repro.core.tree import init_tree

    # kernels.ops picks the Pallas path by asking for the default backend,
    # which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, game = PAPER, PAPER.game_obj
    place = lambda x: _spec(x.shape, x.dtype, one_chip)  # noqa: E731
    tree = jax.tree.map(place, jax.eval_shape(
        lambda: init_tree(cfg.tree_cap, game.n_actions, 1)))
    keys = place(jax.eval_shape(lambda: gscpm.fold_task_keys(
        jax.random.key(0), jnp.arange(cfg.n_workers, dtype=jnp.int32))))
    compiled = gscpm.run_chunk.lower(
        tree, _spec((game.n_cells,), jnp.int8, one_chip), cfg, keys,
        _spec((cfg.n_workers,), jnp.bool_, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.float32, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert len(HEX_WINNER_CALL.findall(compiled.as_text())) == 1
    mem = compiled.memory_analysis()
    # the donated tree is updated in place; scratch stays far below HBM
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < 1 << 30
