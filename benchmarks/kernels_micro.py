"""Kernel microbenches: correctness sweeps + timing of the real dispatch path.

Interpret-mode Pallas timings are meaningless (Python-interpreted kernel
bodies), so interpret runs are reported as validation only — never timed.
For ``uct_select`` the timed path is the ``ops.uct_select`` dispatch users
actually hit on this backend (compiled Pallas on TPU, the jitted jnp
reference elsewhere); attention/rmsnorm have no jnp fallback in ``ops``, so
off-TPU their interpret run is validation-only and the jitted oracle is
timed as the reference throughput. Each entry records which path ran
(``dispatch``) so TPU and CPU artifacts are not comparable by accident; TPU
wall-clock numbers belong to the §Perf iteration on real hardware.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hex as hx
from repro.kernels import ops, ref

from benchmarks.common import timed

ON_TPU = jax.default_backend() == "tpu"


def run(seed: int = 0) -> dict:
    key = jax.random.key(seed)
    out: dict[str, dict] = {}

    # flash attention
    fa = {}
    for (B, H, Hkv, S, d) in [(1, 4, 2, 256, 64), (1, 8, 8, 512, 64)]:
        ks = jax.random.split(jax.random.fold_in(key, S), 3)
        q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, d), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, d), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, layout="bhsd")
        want = ref.flash_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(got - want)))
        oracle = jax.jit(lambda q, k, v: ref.flash_attention(q, k, v, True))
        jax.block_until_ready(oracle(q, k, v))
        t, _ = timed(lambda: jax.block_until_ready(oracle(q, k, v)),
                     repeats=3)
        flops = 4 * B * H * S * S * d
        fa[f"B{B}H{H}S{S}d{d}"] = {
            "max_err_vs_oracle": err,
            "checked_path": ("pallas_compiled" if ON_TPU
                             else "pallas_interpret_validation_only"),
            "timed_path": "jnp_oracle",
            "oracle_s": t, "oracle_gflops": flops / t / 1e9}
    out["flash_attention"] = fa

    # uct_select — validate the Pallas kernel in interpret mode (never
    # timed), then time the backend dispatch path the search actually hits
    us = {}
    for (W, C) in [(128, 128), (1024, 128)]:
        ks = jax.random.split(jax.random.fold_in(key, W + C), 4)
        visits = jnp.round(jax.random.uniform(ks[0], (W, C)) * 50)
        wins = jnp.round(jax.random.uniform(ks[1], (W, C)) * visits)
        vloss = jnp.zeros((W, C))
        valid = jax.random.uniform(ks[2], (W, C)) > 0.2
        ptot = jnp.maximum(visits.sum(-1), 1.0)
        cp = jnp.float32(1.0)
        got = ops.uct_select(wins, visits, vloss, ptot, valid, cp,
                             interpret=True)
        want = ref.uct_select(wins, visits, vloss, ptot, valid, cp)
        agree = float((got == want).mean())
        jax.block_until_ready(
            ops.uct_select(wins, visits, vloss, ptot, valid, cp))
        t, _ = timed(lambda: jax.block_until_ready(
            ops.uct_select(wins, visits, vloss, ptot, valid, cp)), repeats=3)
        us[f"W{W}C{C}"] = {
            "interpret_agreement_validation_only": agree,
            "dispatch": "pallas_compiled" if ON_TPU else "jnp_ref",
            "dispatch_s": t,
            "selections_per_s": W / t,
        }
    out["uct_select"] = us

    # hex winner / playout — the playout phase's two formulations (O(diam)
    # flood fill vs O(log n) pointer doubling), scalar-vmap vs batched, and
    # the fused playout stage. The interpret-mode Pallas kernel run is
    # validation-only; the timed paths are the real dispatch
    # (flood-fill Pallas on TPU, batched flood fill elsewhere) and
    # the jitted alternatives it was chosen against.
    hw = {}
    for (size, W) in [(9, 16), (11, 16), (11, 128)]:
        spec = hx.HexSpec(size)
        ks = jax.random.split(jax.random.fold_in(key, 7000 + size * W), W)
        empty = jnp.tile(hx.empty_board(spec)[None], (W, 1))
        fill_j = jax.jit(lambda b, k: hx.random_fill_batch(b, 1, k, spec))
        filled = jax.block_until_ready(fill_j(empty, ks))

        entry = {"dispatch": "pallas_compiled" if ON_TPU
                 else "jnp_flood_batch"}
        if W <= 16:  # interpret-mode Pallas is pure Python — keep it small
            kern = ops.hex_winner(filled, size, interpret=True)
            pj = ref.hex_winner(filled, size)
            entry["kernel_interpret_agreement_validation_only"] = float(
                (np.asarray(kern) == np.asarray(pj)).mean())

        disp = lambda b: ops.hex_winner(b, size)
        pj_j = jax.jit(lambda b: ref.hex_winner(b, size))
        flood_v = jax.jit(jax.vmap(lambda b: hx.winner(b, spec)))
        po_b = jax.jit(lambda b, k: hx.playout_batch(b, 1, k, spec))
        # explicit per-lane formulation (`hx.playout` itself is now a
        # width-1 wrapper over the batched path): fill + scalar flood winner
        po_v = jax.jit(jax.vmap(lambda b, k: hx.winner(
            hx.random_fill(b, jnp.int32(1), k, spec), spec)))
        for f, args in ((disp, (filled,)), (pj_j, (filled,)),
                        (flood_v, (filled,)), (po_b, (empty, ks)),
                        (po_v, (empty, ks))):
            jax.block_until_ready(f(*args))
        t_disp, _ = timed(lambda: jax.block_until_ready(disp(filled)),
                          repeats=5)
        t_pj, _ = timed(lambda: jax.block_until_ready(pj_j(filled)),
                        repeats=5)
        t_flood, _ = timed(lambda: jax.block_until_ready(flood_v(filled)),
                           repeats=5)
        t_pob, _ = timed(lambda: jax.block_until_ready(po_b(empty, ks)),
                         repeats=5)
        t_pov, _ = timed(lambda: jax.block_until_ready(po_v(empty, ks)),
                         repeats=5)
        entry.update({
            "winner_dispatch_s": t_disp,
            "winner_pointer_doubling_jnp_s": t_pj,
            "winner_floodfill_vmap_s": t_flood,
            "winner_eval_per_s": W / t_disp,
            "playout_batched_s": t_pob,
            "playout_vmap_s": t_pov,
            "playout_eval_per_s": W / t_pob,
            "playout_batched_speedup_vs_vmap": t_pov / t_pob,
        })
        hw[f"{size}x{size}W{W}"] = entry
    out["hex_winner"] = hw

    # gomoku eval — the second Game workload's fused playout stage
    # (completion-time resolution over a random fill) vs the sequential
    # per-lane move-loop oracle. One jitted jnp path on every backend
    # (no Pallas body yet — ROADMAP), so `dispatch` is backend-invariant.
    from repro.core import game as game_mod

    gk = {}
    for (size, W) in [(9, 16), (11, 64)]:
        g = game_mod.make_game("gomoku", size)
        ks = jax.random.split(jax.random.fold_in(key, 9000 + size * W), W)
        empty = jnp.tile(g.init_board()[None], (W, 1))
        po_b = jax.jit(lambda b, k, g=g: g.playout_batch(b, 1, k))
        po_v = jax.jit(jax.vmap(
            lambda b, k, g=g: g.playout_scalar(b, jnp.int32(1), k)))
        vals_b = jax.block_until_ready(po_b(empty, ks))
        vals_v = jax.block_until_ready(po_v(empty, ks))
        t_b, _ = timed(lambda: jax.block_until_ready(po_b(empty, ks)),
                       repeats=5)
        t_v, _ = timed(lambda: jax.block_until_ready(po_v(empty, ks)),
                       repeats=5)
        gk[f"{size}x{size}W{W}"] = {
            "dispatch": "jnp_completion_scan",
            "batched_vs_scalar_agreement": float(
                (np.asarray(vals_b) == np.asarray(vals_v)).mean()),
            "draw_fraction": float((np.asarray(vals_b) == 0).mean()),
            "playout_batched_s": t_b,
            "playout_scalar_vmap_s": t_v,
            "playout_eval_per_s": W / t_b,
            "playout_batched_speedup_vs_scalar": t_v / t_b,
        }
    out["gomoku_eval"] = gk

    # rmsnorm
    rn = {}
    for shape in [(4096, 1024), (256, 8192)]:
        x = jax.random.normal(jax.random.fold_in(key, shape[1]), shape,
                              jnp.float32)
        w = jnp.ones((shape[-1],), jnp.float32)
        got = ops.rmsnorm(x, w)
        want = ref.rmsnorm(x, w)
        err = float(jnp.max(jnp.abs(got - want)))
        oracle = jax.jit(lambda x, w: ref.rmsnorm(x, w))
        jax.block_until_ready(oracle(x, w))
        t, _ = timed(lambda: jax.block_until_ready(oracle(x, w)), repeats=3)
        gb = 2 * x.size * 4 / 1e9
        rn[f"{shape[0]}x{shape[1]}"] = {
            "max_err_vs_oracle": err,
            "checked_path": ("pallas_compiled" if ON_TPU
                             else "pallas_interpret_validation_only"),
            "timed_path": "jnp_oracle",
            "oracle_s": t, "oracle_gbps": gb / t}
    out["rmsnorm"] = rn
    return out


if __name__ == "__main__":
    import json

    from benchmarks.common import save_result
    r = run()
    print(json.dumps(r, indent=1))
    save_result("kernels_micro", r)
