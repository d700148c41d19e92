"""Batched UCT child-selection Pallas kernel (the paper's hot inner loop).

On the Xeon Phi the paper leans on 512-bit VPUs to vectorize UCT scoring;
the TPU-native equivalent is a VPU (8x128 vector unit) tile kernel: W worker
lanes x C child slots per tile, fused score computation + masked argmax,
one pass over VMEM-resident stats.

    UCT(j) = w_j/n_j + Cp * sqrt(ln(n_parent)/n_j)        (paper eq. 1)

with virtual loss folded into n_j, unvisited-first semantics (score 1e30),
invalid-slot masking (-1e30), done-lane masking (a finished lane's row is
all-invalid, so its pick is slot 0 and the caller holds it in place), and
bounded tie-break noise — bit-for-bit the same selection as
``repro.core.uct`` (tests sweep W/C/dtype and compare the chosen indices
against the oracle).

``cp`` is a *traced* scalar operand (a (1, 1) tile broadcast to every grid
step), not a static argument: sweeping Cp across an ablation grid reuses one
compiled kernel (the repo's "knobs traced ⇒ zero recompiles" rule).

Tiling: grid over W blocks; child axis padded to the 128-lane boundary and
kept whole per tile (C <= a few hundred for Hex/LM decode — one tile row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30


def _uct_kernel(cp_ref, wins_ref, visits_ref, vloss_ref, ptot_ref, valid_ref,
                noise_ref, out_ref):
    wins = wins_ref[...].astype(jnp.float32)
    n_j = visits_ref[...].astype(jnp.float32) + vloss_ref[...].astype(jnp.float32)
    valid = valid_ref[...] > 0.5
    noise = noise_ref[...].astype(jnp.float32)
    cp = cp_ref[0, 0]

    x_j = wins / jnp.maximum(n_j, 1.0)
    n_p = jnp.maximum(ptot_ref[...].astype(jnp.float32), 1.0)   # (bw, 1)
    explore = cp * jnp.sqrt(jnp.log(n_p) / jnp.maximum(n_j, 1.0))
    score = x_j + explore + noise
    score = jnp.where(n_j <= 0.0, BIG + noise, score)   # unvisited first
    score = jnp.where(valid, score, -BIG)               # masked slots last
    # the lowest slot among the maxima, as jnp.argmax breaks ties. Mosaic's
    # argmax picks another slot among equal scores (seen on a v5e chip on
    # all-masked rows), so the tie-break is spelled out
    best = jnp.max(score, axis=1, keepdims=True)
    slot = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    out_ref[...] = jnp.min(jnp.where(score == best, slot, score.shape[1]),
                           axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def uct_select(wins: jnp.ndarray, visits: jnp.ndarray, vloss: jnp.ndarray,
               parent_total: jnp.ndarray, valid: jnp.ndarray, cp,
               noise: jnp.ndarray | None = None,
               lane_mask: jnp.ndarray | None = None, block_w: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """wins/visits/vloss/valid: (W, C); parent_total: (W,). Returns (W,) i32.

    ``cp`` is traced (python float or 0-d array both hit one compile).
    ``lane_mask`` (W,) bool marks live lanes; a False row is fully invalid
    and deterministically selects slot 0 (its caller holds the lane anyway).
    """
    W, C = wins.shape
    if noise is None:
        noise = jnp.zeros((W, C), jnp.float32)
    if lane_mask is not None:
        valid = valid & lane_mask[:, None]

    bw = min(block_w, W)
    Wp = -(-W // bw) * bw
    Cp_ = -(-C // 128) * 128
    padWC = lambda x, fill=0.0: jnp.pad(
        x.astype(jnp.float32), ((0, Wp - W), (0, Cp_ - C)),
        constant_values=fill)
    wins_p = padWC(wins)
    visits_p = padWC(visits, 1.0)   # pad slots "visited" so no BIG scores
    vloss_p = padWC(vloss)
    valid_p = padWC(valid.astype(jnp.float32))          # pads invalid
    noise_p = padWC(noise)
    ptot_p = jnp.pad(parent_total.astype(jnp.float32), (0, Wp - W),
                     constant_values=1.0).reshape(Wp, 1)
    cp_arr = jnp.asarray(cp, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _uct_kernel,
        grid=(Wp // bw,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((bw, Cp_), lambda i: (i, 0)),
            pl.BlockSpec((bw, Cp_), lambda i: (i, 0)),
            pl.BlockSpec((bw, Cp_), lambda i: (i, 0)),
            pl.BlockSpec((bw, 1), lambda i: (i, 0)),
            pl.BlockSpec((bw, Cp_), lambda i: (i, 0)),
            pl.BlockSpec((bw, Cp_), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Wp, 1), jnp.int32),
        interpret=interpret,
    )(cp_arr, wins_p, visits_p, vloss_p, ptot_p, valid_p, noise_p)
    return out[:W, 0]
