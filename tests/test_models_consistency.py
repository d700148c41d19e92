"""Cross-implementation model consistency + hypothesis property tests."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import ssm, xlstm
from repro.models.common import ModelConfig, init_tree, spec_with_dtype


def test_mlstm_chunked_equals_quadratic():
    cfg = ModelConfig(family="xlstm", d_model=64, n_heads=4, vocab=64,
                      mlstm_chunk=16)
    p = init_tree(spec_with_dtype(xlstm.mlstm_specs(cfg), jnp.float32),
                  jax.random.key(0))
    x = 0.5 * jax.random.normal(jax.random.key(1), (2, 64, 64))
    y_c, cache_c = xlstm._mlstm_chunked(p, cfg, x)
    full = cfg.replace(mlstm_chunk=0)
    y_f = xlstm.mlstm_forward(p, full, x)
    _, cache_f = xlstm.mlstm_prefill(p, full, x)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_f), atol=3e-5,
                               rtol=3e-4)
    for k in ("C", "n", "m"):
        np.testing.assert_allclose(np.asarray(cache_c[k]),
                                   np.asarray(cache_f[k]), atol=5e-5,
                                   rtol=5e-4)


def test_mlstm_chunked_then_decode():
    """Chunked prefill state continues correctly through decode steps."""
    cfg = ModelConfig(family="xlstm", d_model=32, n_heads=2, vocab=64,
                      mlstm_chunk=8)
    p = init_tree(spec_with_dtype(xlstm.mlstm_specs(cfg), jnp.float32),
                  jax.random.key(2))
    x = 0.5 * jax.random.normal(jax.random.key(3), (1, 40, 32))
    full = cfg.replace(mlstm_chunk=0)
    # ground truth: full quadratic over 40 tokens
    y_full = xlstm.mlstm_forward(p, full, x)
    # chunked prefill over 32, decode the last 8 recurrently
    y_pre, cache = xlstm.mlstm_prefill(p, cfg, x[:, :32])
    outs = []
    for t in range(32, 40):
        yt, cache = xlstm.mlstm_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(yt)
    y_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_full[:, 32:]),
                               atol=2e-4, rtol=2e-3)


def test_mamba2_forward_equals_decode():
    cfg = ModelConfig(family="ssm", d_model=64, n_heads=4, vocab=64,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8)
    p = init_tree(spec_with_dtype(ssm.mamba2_specs(cfg), jnp.float32),
                  jax.random.key(4))
    x = 0.5 * jax.random.normal(jax.random.key(5), (2, 32, 64))
    y, cache = ssm.mamba2_forward(p, cfg, x, return_cache=True)
    cache_d = ssm.mamba2_init_cache(cfg, 2, jnp.float32)
    outs = []
    for t in range(32):
        yt, cache_d = ssm.mamba2_decode(p, cfg, x[:, t:t + 1], cache_d)
        outs.append(yt)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq), atol=5e-4,
                               rtol=5e-3)
    np.testing.assert_allclose(np.asarray(cache["state"]),
                               np.asarray(cache_d["state"]), atol=5e-4,
                               rtol=5e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), chunk=st.sampled_from([4, 8, 16]),
       L=st.sampled_from([16, 32, 48]))
def test_mamba2_chunk_invariance(seed, chunk, L):
    """SSD output independent of the chunking grain (property)."""
    cfg = ModelConfig(family="ssm", d_model=32, n_heads=2, vocab=64,
                      ssm_state=8, ssm_headdim=16, ssm_chunk=chunk)
    p = init_tree(spec_with_dtype(ssm.mamba2_specs(cfg), jnp.float32),
                  jax.random.key(7))
    x = 0.3 * jax.random.normal(jax.random.key(seed), (1, L, 32))
    y1 = ssm.mamba2_forward(p, cfg, x)
    y2 = ssm.mamba2_forward(p, cfg.replace(ssm_chunk=L), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-4,
                               rtol=3e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_moe_fullcapacity_matches_dense(seed):
    """With ample capacity the grouped MoE equals the per-token dense mix."""
    from repro.models import moe
    cfg = ModelConfig(family="moe", d_model=16, n_experts=4,
                      n_experts_per_tok=2, moe_d_ff=8, capacity_factor=8.0,
                      vocab=32, norm_topk_prob=True, moe_group_size=8)
    p = init_tree(spec_with_dtype(moe.moe_specs(cfg), jnp.float32),
                  jax.random.key(11))
    x = jax.random.normal(jax.random.key(seed), (2, 8, 16))
    y = moe.moe_ffn(p, cfg, x)
    xf = x.reshape(-1, 16)
    topi, topw = moe.router_topk(xf @ p["router"], 2, True)
    yref = np.zeros((16, 16), np.float32)
    for t in range(16):
        for j in range(2):
            e, w = int(topi[t, j]), float(topw[t, j])
            h = jax.nn.silu(xf[t] @ p["wg"][e]) * (xf[t] @ p["wu"][e])
            yref[t] += w * np.asarray(h @ p["wd"][e])
    np.testing.assert_allclose(np.asarray(y.reshape(16, 16)), yref,
                               atol=1e-4, rtol=1e-3)


def test_sdpa_chunked_matches_full():
    from repro.models.attention import causal_mask, sdpa, sdpa_chunked
    q = jax.random.normal(jax.random.key(0), (2, 64, 4, 16))
    k = jax.random.normal(jax.random.key(1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.key(2), (2, 64, 2, 16))
    full = sdpa(q, k, v, causal_mask(64))
    for chunk in (8, 16, 32):
        ch = sdpa_chunked(q, k, v, chunk)
        np.testing.assert_allclose(np.asarray(ch), np.asarray(full),
                                   atol=2e-5, rtol=2e-5)
    # prefix-LM variant
    from repro.models.attention import prefix_lm_mask
    pre = sdpa(q, k, v, prefix_lm_mask(64, 10))
    ch = sdpa_chunked(q, k, v, 16, prefix_len=10)
    np.testing.assert_allclose(np.asarray(ch), np.asarray(pre), atol=2e-5,
                               rtol=2e-5)
