"""Roofline machinery: HLO cost parser vs known modules, collective parsing."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import collectives as coll
from repro.roofline import hlo_costs
from repro.roofline.terms import RooflineTerms, active_params, model_flops


def test_scan_trip_scaling():
    """Parser flops for a scanned matmul chain ~= n x single-matmul flops."""
    n, m = 12, 128

    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    x = jax.ShapeDtypeStruct((m, m), jnp.float32)
    ws = jax.ShapeDtypeStruct((n, m, m), jnp.float32)
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    got = hlo_costs.rollup(hlo)
    want = n * 2 * m**3
    assert want * 0.9 < got.flops < want * 1.6, (got.flops, want)
    assert got.while_trips and got.while_trips[0]["trip"] == n


def test_unrolled_matches_xla():
    """On a loop-free module the parser tracks XLA's own flops closely."""
    def f(a, b):
        return jnp.tanh(a @ b).sum()
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    got = hlo_costs.rollup(compiled.as_text())
    xla = compiled.cost_analysis()["flops"]
    assert 0.5 * xla <= got.flops <= 2.0 * xla, (got.flops, xla)


def test_collective_parse_synthetic():
    text = """
ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %ag = f32[16,128]{1,0} all-gather(%p), replica_groups=[2,4]<=[8], dimensions={0}
  %ar = bf16[32,32]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[8,8]{1,0} collective-permute(%y), source_target_pairs={{0,1}}
}
"""
    cs = coll.parse_collectives(text)
    assert [c.op for c in cs] == ["all-gather", "all-reduce",
                                  "collective-permute"]
    ag, ar, cp = cs
    assert ag.group_size == 4 and ag.result_bytes == 16 * 128 * 4
    assert ag.operand_bytes == ag.result_bytes // 4
    assert ar.group_size == 4 and ar.result_bytes == 32 * 32 * 2
    assert ar.wire_bytes == pytest.approx(2 * 3 / 4 * 32 * 32 * 2)
    assert cp.wire_bytes == 8 * 8 * 4


def test_active_params_moe():
    from repro import configs
    cfg = configs.get_config("qwen3-moe-235b-a22b")
    from repro.models import api
    n = api.n_params(cfg)
    a = active_params(cfg, n)
    # a22b: ~22B active of ~235B total
    assert 15e9 < a < 30e9, a
    dense = configs.get_config("llama3-8b")
    assert active_params(dense, api.n_params(dense)) == api.n_params(dense)


def test_roofline_terms():
    t = RooflineTerms(flops_per_chip=197e12, hbm_bytes_per_chip=819e9,
                      wire_bytes_per_chip=0.0, chips=256,
                      model_flops_global=197e12 * 256 / 2,
                      attn_flops_global=0.0)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.bottleneck in ("compute", "memory")
    assert t.mfu == pytest.approx(0.5)
    assert t.useful_ratio == pytest.approx(0.5)


def test_model_flops_kinds():
    from repro import configs
    from repro.models import api
    cfg = configs.get_config("llama3-8b")
    n = api.n_params(cfg)
    tr = model_flops(cfg, n, "train", 4096, 256)
    pf = model_flops(cfg, n, "prefill", 4096, 256)
    de = model_flops(cfg, n, "decode", 4096, 256)
    assert tr == pytest.approx(3 * pf)
    assert de == pytest.approx(pf / 4096)
