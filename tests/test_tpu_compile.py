"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

On a TPU, ``kernels.ops`` routes every search through two compiled Pallas
kernels: ``uct_select`` (one (W, C) tile per descent level) and
``hex_winner`` (every Hex playout). Interpret mode cannot see what Mosaic
refuses (unsupported shape casts, tiling, VMEM), so these tests compile
both kernels, the whole paper-width search program, and the root-parallel
forest's programs over the four chips, for a ``v5e:2x2`` topology that is
described here and not attached. Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under several test workers the one given this file takes it. The
persistent compilation cache is off around these compiles, since an entry
written for a chip that is not attached cannot be read back here.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import hex_winner as hw
from repro.kernels import uct_select as us


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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
COLLECTIVE = re.compile(r"all-reduce|all-gather|all-to-all|collective-permute|"
                        r"reduce-scatter")
HEX_WINNER_CALL = re.compile(
    r'^\s*%hex_winner(\.\d+)? = .* custom-call\(.*'
    r'custom_call_target="tpu_custom_call"', re.M)


HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)(.*)$")


def backup_index_entries(text):
    """Entries each gather and scatter of the backup phase moves (a
    gather's result, a scatter's updates). A fused scatter may carry no
    ``op_name`` of its own; its combiner, made for the backup's
    scatter-add, does."""
    size, body, comp = {}, {}, None
    for line in text.splitlines():
        if m := HLO_COMPUTATION.match(line):
            comp = body.setdefault(m.group(1), [])
        elif m := HLO_INSTRUCTION.match(line):
            name, _, shape, opcode, args, rest = m.groups()
            size[name] = math.prod(int(d) for d in shape.split(",") if d)
            comp.append((name, opcode, args.split(", "), rest))

    def combiner_in_backup(rest):
        region = re.search(r"to_apply=%([\w.-]+)", rest)
        return bool(region) and any("/backup/" in r
                                    for *_, r in body[region.group(1)])

    found = {}
    for ops in body.values():
        for name, opcode, args, rest in ops:
            if opcode == "gather" and "/backup/" in rest:
                found[name] = size[name]
            elif opcode == "scatter" and ("/backup/" in rest
                                          or combiner_in_backup(rest)):
                found[name] = size[args[2].lstrip("%")]
    return sorted(found.values())


def move_row_reads(text, cap, lanes, n_cells, members=1):
    """The two ops by which a proposal once read a leaf's tried moves: a
    descent gather of ``lanes`` x ``n_cells`` single elements of the
    per-node (cap+1,) ``move`` array, and a scatter of them into a
    (lanes, n_cells + 1) bool mask; ``members`` trees per chip multiply
    both. Returns the names of such ops."""
    typed, found = {}, []
    for line in text.splitlines():
        if m := HLO_INSTRUCTION.match(line):
            name, dtype, shape, opcode, args, rest = m.groups()
            dims = tuple(int(d) for d in shape.split(",") if d)
            typed[name] = (dtype, dims)
            operand = typed.get(args.split(", ")[0].lstrip("%"))
            if (opcode == "gather" and "/descent/" in rest
                    and math.prod(dims) == members * lanes * n_cells
                    and operand is not None and operand[0] == "s32"
                    and operand[1][-1:] == (cap + 1,)
                    and len(operand[1]) <= 2):
                found.append(name)
            elif (opcode == "scatter" and dtype == "pred"
                  and math.prod(dims) == members * lanes * (n_cells + 1)):
                found.append(name)
    return found


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
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert len(HEX_WINNER_CALL.findall(text)) == 1
    # one level's (W, C) child statistics: wins and visits, and no virtual
    # loss, which one selection round per iteration never writes
    tiles = re.findall(rf"= f32\[{cfg.n_workers * game.n_actions}\]\S* "
                       r"fusion\(.*descent/.*gather", text)
    assert len(tiles) == 2, tiles
    # the backup scatters a few path levels per trip, never all W x
    # (max_moves + 1) = 29,768 entries of the padded path array
    entries = backup_index_entries(text)
    assert len(entries) >= 3, entries             # to_move gather, 2 scatters
    assert max(entries) < cfg.n_workers * (game.max_moves + 1), entries
    # a proposal reads its leaf's tried moves as one child_move row: no
    # W x C = 29,524-element gather of `move`, no 29,768-entry tried scatter
    reads = move_row_reads(text, cfg.tree_cap, cfg.n_workers, game.n_cells)
    assert not reads, reads
    mem = compiled.memory_analysis()
    # the donated tree (child_move included) is updated in place; scratch
    # stays far below HBM
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < 1 << 30


def test_forest_programs_compile_for_four_chips(topo, monkeypatch):
    """``hex11-forest8`` over the described 2x2 host (8 trees at PAPER's
    widths, tree cap 2^17, 2 per chip): the placement builds each chip's
    own members, the round runs both kernels with no collective and
    updates the donated forest in place, and the merge exchanges one
    all-reduce of the root totals, (121,) and scalars, in place too."""
    from repro.configs.hex_paper import PAPER
    from repro.core import root_parallel as rp
    from repro.core.gscpm import fold_task_keys
    from repro.core.tree import init_forest

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(PAPER, n_playouts=124_928, n_tasks=488,
                              tree_cap=1 << 17)
    E, W, M = 8, cfg.n_workers, cfg.game_obj.n_actions
    mesh = rp.ensemble_mesh(list(topo.devices))
    ens = NamedSharding(mesh, rp.ENSEMBLE_SPEC)
    rep = NamedSharding(mesh, PartitionSpec())
    place = lambda x: _spec(x.shape, x.dtype, ens)  # noqa: E731
    forest = jax.tree.map(place, jax.eval_shape(
        lambda: init_forest(E, cfg.tree_cap, M, 1)))
    member_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(forest)) // E

    init = rp._init_forest_placed.lower(
        _spec((E,), jnp.int32, rep), n_trees=E, cap=cfg.tree_cap,
        max_children=M, sharding=ens).compile()
    assert init.memory_analysis().output_size_in_bytes < 3 * member_bytes
    assert not COLLECTIVE.search(init.as_text())

    keys = place(jax.eval_shape(lambda: rp.fold_member_task_keys(
        fold_task_keys(jax.random.key(0), jnp.arange(E)), jnp.arange(W))))
    chunk = rp._sharded_chunk.lower(
        forest, _spec((E, cfg.game_obj.n_cells), jnp.int8, ens), keys,
        _spec((E, W), jnp.bool_, ens), _spec((), jnp.int32, rep),
        _spec((), jnp.float32, rep), cfg=cfg, mesh=mesh).compile()
    text = chunk.as_text()
    assert len(HEX_WINNER_CALL.findall(text)) == 1
    assert text.count("tpu_custom_call") >= 2
    assert not COLLECTIVE.search(text)
    # two members per chip, and no backup op at even one's full width
    entries = backup_index_entries(text)
    assert len(entries) >= 3, entries
    assert max(entries) < W * (cfg.game_obj.max_moves + 1), entries
    # nor a move gather or tried scatter for either member
    reads = move_row_reads(text, cfg.tree_cap, W, cfg.game_obj.n_cells,
                           members=E // len(topo.devices))
    assert not reads, reads
    mem = chunk.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * 2 * member_bytes
    assert mem.temp_size_in_bytes < 1 << 30

    state = jax.tree.map(place, jax.eval_shape(
        lambda: rp.init_sync_state(E, M)))
    merge = rp.sync_root_stats.lower(forest, state, M).compile()
    text = merge.as_text()
    reduces = re.findall(r"= \(?([^=]*?)\)? all-reduce(?:-start)?\(", text)
    assert len(reduces) == 1, reduces
    assert set(re.findall(r"[a-z0-9]+\[([0-9,]*)\]", reduces[0])) <= {
        "", str(M)}, reduces
    assert not re.search(r"all-gather|all-to-all|collective-permute|"
                         r"reduce-scatter", text)
    assert merge.memory_analysis().alias_size_in_bytes >= 0.99 * 2 * \
        member_bytes
    for s in jax.tree.leaves(merge.output_shardings):
        assert s.is_equivalent_to(ens, 1)
