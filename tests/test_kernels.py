"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

KEY = jax.random.key(7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,d", [
    (2, 4, 2, 256, 64),    # GQA
    (1, 8, 1, 128, 128),   # MQA
    (2, 2, 2, 256, 80),    # odd head dim (pad path)
    (1, 4, 4, 512, 64),    # MHA longer seq
])
def test_flash_attention(B, H, Hkv, S, d, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S + d + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    got = ops.flash_attention(q, k, v, causal=True, layout="bhsd")
    want = ref.flash_attention(q, k, v, causal=True)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    got = ops.flash_attention(q, k, v, causal=False, layout="bhsd")
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_matches_model_sdpa():
    """Kernel agrees with the model's attention path (bshd layout)."""
    from repro.models.attention import causal_mask, sdpa
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    got = ops.flash_attention(q, k, v, causal=True)        # bshd
    want = sdpa(q, k, v, causal_mask(128))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("W,C", [(7, 11), (64, 121), (200, 121), (16, 300),
                                 (128, 128), (1, 5)])
@pytest.mark.parametrize("noise", [False, True])
def test_uct_select_kernel_vs_oracle(W, C, noise):
    """Interpret-mode Pallas kernel (validation-only path) == jnp oracle."""
    ks = jax.random.split(jax.random.fold_in(KEY, W * C + noise), 5)
    visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 10)
    wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
    vloss = jnp.round(jax.random.uniform(ks[2], (W, C)) * 2)
    valid = jax.random.uniform(ks[3], (W, C)) > 0.3
    ptot = jnp.maximum(visits.sum(-1), 1.0)
    nz = 1e-3 * jax.random.uniform(ks[4], (W, C)) if noise else None
    got = ops.uct_select(wins, visits, vloss, ptot, valid, 1.0, noise=nz,
                         interpret=True)
    want = ref.uct_select(wins, visits, vloss, ptot, valid, 1.0, noise=nz)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_uct_select_dispatch_agrees_with_kernel():
    """The auto dispatch the search hot path hits (compiled Pallas on TPU,
    jitted jnp reference elsewhere) selects the same children as the
    interpret-mode Pallas kernel — an independent implementation on every
    backend, so this is non-vacuous on the CPU CI host too — with cp
    traced and a lane mask applied."""
    ks = jax.random.split(KEY, 4)
    W, C = 32, 24
    visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 10)
    wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
    valid = jax.random.uniform(ks[2], (W, C)) > 0.3
    ptot = jnp.maximum(visits.sum(-1), 1.0)
    mask = jax.random.uniform(ks[3], (W,)) > 0.25
    for cp in (jnp.float32(0.5), jnp.float32(1.7)):
        got = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid,
                             cp, lane_mask=mask)
        kernel = ops.uct_select(wins, visits, jnp.zeros((W, C)), ptot, valid,
                                cp, lane_mask=mask, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(kernel))


@pytest.mark.parametrize("size,W", [(5, 1), (5, 7), (9, 16), (11, 16)])
def test_hex_winner_kernel_vs_oracle(size, W):
    """Interpret-mode pointer-doubling Pallas kernel (validation-only path)
    == the jnp pointer-doubling reference == the scalar flood-fill winner,
    on filled boards (the kernel's contract domain)."""
    from repro.core import hex as hx
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.fold_in(KEY, size * W), W)
    boards = jnp.tile(hx.empty_board(spec)[None], (W, 1))
    filled = hx.random_fill_batch(boards, 1, keys, spec)
    got = ops.hex_winner(filled, size, interpret=True)
    want = ref.hex_winner(filled, size)
    flood = jax.vmap(lambda b: hx.winner(b, spec))(filled)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flood))
    assert got.dtype == jnp.int8


def test_hex_winner_dispatch_agrees_with_kernel():
    """The auto dispatch the playout phase hits (compiled Pallas on TPU,
    jitted batched flood fill elsewhere) returns the same winners as the
    interpret-mode pointer-doubling kernel — independent implementations
    on every backend, so non-vacuous on the CPU CI host too."""
    from repro.core import hex as hx
    size, W = 9, 12
    spec = hx.HexSpec(size)
    keys = jax.random.split(jax.random.fold_in(KEY, 99), W)
    filled = hx.random_fill_batch(
        jnp.tile(hx.empty_board(spec)[None], (W, 1)), 2, keys, spec)
    got = ops.hex_winner(filled, size)
    kernel = ops.hex_winner(filled, size, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kernel))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 64), d=st.integers(1, 300),
       dt=st.sampled_from(["float32", "bfloat16"]))
def test_rmsnorm_property(n, d, dt):
    dtype = jnp.dtype(dt)
    x = jax.random.normal(jax.random.fold_in(KEY, n * d), (n, d), dtype)
    w = 1 + 0.1 * jax.random.normal(jax.random.fold_in(KEY, d), (d,),
                                    jnp.float32)
    got = ops.rmsnorm(x, w, 1e-5)
    want = ref.rmsnorm(x, w, 1e-5)
    tol = 3e-2 if dt == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    assert got.dtype == x.dtype


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rmsnorm as model_rmsnorm
    x = jax.random.normal(KEY, (4, 32, 256), jnp.float32)
    w = jnp.ones((256,))
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, w, 1e-5)),
                               np.asarray(model_rmsnorm(x, w, 1e-5)),
                               atol=1e-5, rtol=1e-5)
