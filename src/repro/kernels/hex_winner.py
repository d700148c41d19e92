"""Batched pointer-doubling Hex winner Pallas kernel (the playout endgame).

The paper evaluates a playout by one union-find connectivity pass; on the
Phi that pass is the serial tail of every playout. The TPU-native
equivalent solves ALL W lanes' connectivity at once over a (W, n_cells)
tile with the PRAM pointer-jumping scheme (Shiloach–Vishkin / FastSV):
each round hooks every cell to the minimum label in its closed same-color
neighborhood, lets roots adopt the best label their subtree saw, and then
pointer-jumps — O(log n_cells) fixed rounds, no per-lane convergence
loop (DESIGN.md §12).

TPU mapping choices:

- the hook's six Hex-neighbor reads are *static* shifts of the flat board
  (``pltpu.roll`` by ``dr*size + dc``) with in-bounds masks computed from a
  2D iota — no gather;
- the pointer jump ``P[i] = P[P[i]]`` and the scatter-min hook are dynamic
  by nature; both are expressed as one-hot compare-and-reduce over a
  (bw, C, C) tile — the classic gather/scatter-as-matmul trick that keeps
  the kernel in pure VPU/MXU-friendly ops (C = padded cell count, 128 for
  boards up to 11x11, so the tile is small);
- the round count is FIXED at ``ceil(log2(n_cells)) + 2`` (converges
  <= 7 rounds empirically on random and adversarial snake/comb boards up
  to 25x25, against caps of 9-12; the jnp reference's fixpoint loop and
  the fixed-round kernel agree bit-for-bit — tests/test_hex_batch.py,
  tests/test_kernels.py).

Like ``uct_select``, the auto dispatch in ``kernels.ops`` compiles this on
TPU and uses the jitted jnp reference elsewhere; interpret mode is a
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


def _winner_kernel(board_ref, out_ref, *, size: int, n: int, rounds: int):
    brd = board_ref[...]                                   # (bw, C) int32
    bw, C = brd.shape
    cell = jax.lax.broadcasted_iota(jnp.int32, (bw, C), 1)
    r = cell // size
    c = cell % size
    black = (brd == 1) & (cell < n)

    # per-direction "neighbor exists and both endpoints are black" masks;
    # static across rounds, so hoisted out of the loop (rolls carry int32:
    # TPU vector shifts are lane ops, booleans are cast around them)
    blacki = black.astype(jnp.int32)
    edge_ok = []
    for dr, dc in DELTAS:
        rr, cc = r + dr, c + dc
        inb = (rr >= 0) & (rr < size) & (cc >= 0) & (cc < size)
        off = dr * size + dc
        nbr_black = pltpu.roll(blacki, (-off) % C, 1) == 1
        edge_ok.append(black & inb & nbr_black)

    col = jax.lax.broadcasted_iota(jnp.int32, (bw, C, C), 2)

    def one_round(_, P):
        # gather hook: min label over the closed same-color neighborhood
        m = P
        for (dr, dc), ok in zip(DELTAS, edge_ok):
            off = dr * size + dc
            m = jnp.minimum(m, jnp.where(ok, pltpu.roll(P, (-off) % C, 1), C))
        # scatter hook (roots adopt their subtree's best label) as a
        # one-hot segmented min: scat[j] = min{m[i] : P[i] == j}
        oh = P[:, :, None] == col
        scat = jnp.min(jnp.where(oh, m[:, :, None], C), axis=1)
        Q = jnp.minimum(jnp.minimum(P, scat), m)
        # pointer jump Q[i] = Q[Q[i]] as a one-hot gather
        ohq = Q[:, :, None] == col
        return jnp.min(jnp.where(ohq, Q[:, None, :], C), axis=2)

    P0 = cell  # non-black cells stay inert self-loops (no hookable edges)
    P = jax.lax.fori_loop(0, rounds, one_round, P0)

    # black connects top<->bottom iff a bottom black cell's component root
    # is also some top black cell's root. The marks are int32 0/1 planes:
    # Mosaic cannot broadcast a bool (bw, C) vector to (bw, C, 1), so the
    # one-hot reductions use the same int32 where/max forms as the rounds.
    top = (black & (r == 0)).astype(jnp.int32)
    bottom = (black & (r == size - 1)).astype(jnp.int32)
    oh = P[:, :, None] == col
    mark = jnp.max(jnp.where(oh, top[:, :, None], 0), axis=1)    # roots@top
    reach = jnp.max(jnp.where(oh, mark[:, None, :], 0), axis=2)  # mark[P[i]]
    conn = jnp.max(reach * bottom, axis=1, keepdims=True)        # (bw, 1)
    out_ref[...] = jnp.where(conn > 0, 1, 2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("size", "block_w", "interpret"))
def hex_winner(boards: jnp.ndarray, size: int, block_w: int = 8,
               interpret: bool = False) -> jnp.ndarray:
    """boards: (W, size*size) int8 FILLED boards. Returns (W,) int8 winners.

    Same contract as ``repro.core.hex.winner``: boards must be completely
    filled (the Hex-theorem single connectivity check is only a winner
    check on terminal boards).
    """
    # the round budget is owned by repro.core.hex (function-level import:
    # kernels must not depend on core at module scope) so kernel and jnp
    # paths can never drift apart
    from repro.core.hex import doubling_rounds

    W, n = boards.shape
    if n != size * size:
        raise ValueError(f"boards last dim {n} != size*size {size * size}")
    C = max(128, -(-n // 128) * 128)
    bw = min(block_w, W)
    Wp = -(-W // bw) * bw
    brd = jnp.pad(boards.astype(jnp.int32), ((0, Wp - W), (0, C - n)))

    out = pl.pallas_call(
        functools.partial(_winner_kernel, size=size, n=n,
                          rounds=doubling_rounds(n)),
        grid=(Wp // bw,),
        in_specs=[pl.BlockSpec((bw, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Wp, 1), jnp.int32),
        interpret=interpret,
    )(brd)
    return out[:W, 0].astype(jnp.int8)
