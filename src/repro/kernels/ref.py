"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's public signature exactly; tests sweep
shapes/dtypes and assert_allclose(kernel(interpret=True), ref).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True,
                    scale: float | None = None) -> jnp.ndarray:
    """q: (B, H, S, d); k/v: (B, Hkv, S, d) with GQA broadcast. fp32 math."""
    B, H, S, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, S, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf * scale, kf)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return o.reshape(B, H, S, d).astype(q.dtype)


def uct_select(wins: jnp.ndarray, visits: jnp.ndarray, vloss: jnp.ndarray,
               parent_total: jnp.ndarray, valid: jnp.ndarray,
               cp, noise: jnp.ndarray | None = None,
               lane_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """(W, C) child stats -> (W,) best child slot (paper eq. 1 + tie-break).

    ``cp`` may be a traced scalar; ``lane_mask`` (W,) bool marks live lanes
    (a masked row is all-invalid and deterministically yields slot 0).
    """
    from repro.core.uct import select_child, uct_scores
    if lane_mask is not None:
        valid = valid & lane_mask[..., None]
    scores = uct_scores(wins, visits, vloss, parent_total, cp, valid)
    return select_child(scores, noise).astype(jnp.int32)


def hex_winner(boards: jnp.ndarray, size: int) -> jnp.ndarray:
    """(W, size*size) FILLED boards -> (W,) int8 winners in {1, 2}.

    Same filled-board contract as the kernel (`repro.core.hex.winner`).
    The batched pointer-doubling solve in `repro.core.hex`, a different
    algorithm from the kernel's flood fill, so an independent oracle: one
    connectivity check for BLACK decides every lane (the Hex theorem).
    """
    from repro.core import hex as hx
    black = hx.connected_batch(boards, hx.BLACK, hx.HexSpec(size))
    return jnp.where(black, jnp.int8(1), jnp.int8(2))


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """x: (..., D); w: (D,). fp32 statistics, input-dtype output."""
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * w.astype(jnp.float32)).astype(x.dtype)
