"""Batched Hex winner Pallas kernel by roll-dilation flood fill (the
playout endgame).

The paper evaluates a playout by one union-find connectivity pass; on the
Phi that pass is the serial tail of every playout. The TPU equivalent
decides ALL W lanes' filled boards at once over a (W, C) tile: black's
reach set grows from the top row by one hop per step, and black wins iff
it touches the bottom row (the Hex theorem: a filled board has exactly one
winner). One step is six static lane rotations of the reach plane, six
ANDs with per-direction masks and six ORs, over int32 0/1 planes; no
gather, no one-hot tile, no convergence check (DESIGN.md §12).

Why the step count is exact: after k steps the reach set is every black
cell whose shortest black path from a top-row black cell has at most k
edges. A shortest path visits each cell once, so it has at most
n_cells - 1 edges, and n_cells - 1 fixed steps reach the fixpoint on any
filled board.

The kernel used to label components by FastSV pointer doubling (9 rounds
of hook and pointer jump as one-hot compare-and-reduce over (8, C, C)
tiles); on a TPU v5e that took 1,991 us per (244, 121) call, against 36
ns to move its bytes (PERF.md §6). The pointer-doubling solve stays in
``repro.core.hex.cc_labels_batch`` and ``kernels.ref.hex_winner`` as an
independent test oracle.

TPU mapping choices:

- a neighbor read is ``pltpu.roll`` by ``-(dr*size + dc)`` along lanes,
  with in-bounds masks from a 2D iota; an in-bounds neighbor's flat index
  lies in [0, n_cells), so the rotation's wrap never feeds a valid cell;
- the block is all W rows, padded to a multiple of 8, up to ``block_w``
  rows; above that, a grid of equal blocks of at most ``block_w`` rows;
  C is ``max(128, ceil(n/128)*128)``.

Like ``uct_select``, the auto dispatch in ``kernels.ops`` compiles this on
TPU and uses the jitted jnp flood fill elsewhere; interpret mode is a
validation tool only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (r, c) offsets of the six Hex neighbors on the rhombus board
DELTAS = ((-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0))


def _winner_kernel(board_ref, out_ref, *, size: int, n: int):
    brd = board_ref[...]                                   # (bw, C) int32
    bw, C = brd.shape
    cell = jax.lax.broadcasted_iota(jnp.int32, (bw, C), 1)
    r = cell // size
    c = cell % size
    black = (brd == 1) & (cell < n)

    # per direction: "this cell is black and that neighbor is on the
    # board", as an int32 0/1 plane (rotations are lane ops on int32)
    shifts, ok = [], []
    for dr, dc in DELTAS:
        rr, cc = r + dr, c + dc
        inb = (rr >= 0) & (rr < size) & (cc >= 0) & (cc < size)
        shifts.append((-(dr * size + dc)) % C)
        ok.append((black & inb).astype(jnp.int32))

    def step(_, reach):
        acc = reach
        for s, m in zip(shifts, ok):
            acc = acc | (pltpu.roll(reach, s, 1) & m)
        return acc

    reach0 = (black & (r == 0)).astype(jnp.int32)
    reach = jax.lax.fori_loop(0, n - 1, step, reach0)
    bottom = (r == size - 1).astype(jnp.int32)
    conn = jnp.max(reach & bottom, axis=1, keepdims=True)   # (bw, 1)
    out_ref[...] = jnp.where(conn > 0, 1, 2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("size", "block_w", "interpret"))
def hex_winner(boards: jnp.ndarray, size: int, block_w: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """boards: (W, size*size) int8 FILLED boards. Returns (W,) int8 winners.

    Same contract as ``repro.core.hex.winner``: boards must be completely
    filled (the Hex-theorem single connectivity check is only a winner
    check on terminal boards). ``block_w`` caps the rows of one block
    (rounded down to a multiple of 8).
    """
    W, n = boards.shape
    if n != size * size:
        raise ValueError(f"boards last dim {n} != size*size {size * size}")
    C = max(128, -(-n // 128) * 128)
    rows = -(-W // 8) * 8
    n_blocks = -(-rows // max(8, block_w // 8 * 8))
    bw = -(-rows // (8 * n_blocks)) * 8
    Wp = bw * n_blocks
    brd = jnp.pad(boards.astype(jnp.int32), ((0, Wp - W), (0, C - n)))

    out = pl.pallas_call(
        functools.partial(_winner_kernel, size=size, n=n),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((bw, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Wp, 1), jnp.int32),
        interpret=interpret,
    )(brd)
    return out[:W, 0].astype(jnp.int8)
