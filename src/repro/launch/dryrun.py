import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 TPU v5e pods; for each
cell the jitted step function must ``.lower().compile()`` under the
production mesh, and we record

- ``compiled.memory_analysis()``  — proves the cell fits 16 GB/chip,
- ``compiled.cost_analysis()``    — per-chip HLO FLOPs / bytes,
- parsed collective ops           — per-chip wire bytes (roofline/collectives),
- the three roofline terms        — EXPERIMENTS.md §Roofline reads these.

Cost accounting: XLA's ``cost_analysis`` visits a while body ONCE (a ~94x
FLOP undercount for scanned layers), and fully unrolling makes XLA:CPU
codegen take ~12 min/cell (measured). So cells compile in their scanned
form (fast) and costs come from ``repro.roofline.hlo_costs`` — a
per-computation cost model over the compiled HLO text that scales while
bodies by their parsed trip counts (validated at 74-100% of the
unrolled-compiled ground truth on smollm; dot FLOPs are exact).
``--crosscheck`` additionally lowers the unroll_loops=True variant and
reports its pre-partitioning global FLOPs.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
        [--skip-existing] [--out artifacts/dryrun]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import inputs as inp
from repro.launch.mesh import make_production_mesh, mesh_dims
from repro.models import api
from repro.optim import adamw
from repro.roofline import collectives as coll
from repro.roofline import hlo_costs
from repro.roofline import terms as rt
from repro.serve import engine as serve_engine
from repro.sharding import rules as shr
from repro.train import step as train_step_mod


def _metrics_shardings(mesh):
    rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return {"loss": rep, "grad_norm": rep, "lr": rep, "skipped": rep}


def lower_cell(arch: str, shape_name: str, mesh, unroll: bool = True,
               cfg_overrides: dict | None = None,
               rules_overrides: dict | None = None):
    """Build and lower one cell; returns (lowered, cfg, spec, rules)."""
    spec = inp.input_specs(arch, shape_name, cfg_overrides)
    cfg = spec["cfg"].replace(unroll_loops=unroll, scan_layers=not unroll)
    rules = dict(spec["rules"])
    rules.update(rules_overrides or {})
    shape = spec["shape"]

    with shr.use_rules(rules, mesh):
        if shape.kind == "train":
            import jax.numpy as jnp
            step = train_step_mod.make_train_step(
                cfg,
                adamw.OptConfig(moment_dtype=spec.get("moment_dtype",
                                                      "float32")),
                n_microbatches=spec.get("n_microbatches", 1),
                accum_dtype=jnp.dtype(spec.get("accum_dtype", "float32")))
            ss = inp.shardings_for(mesh, spec["state"], spec["state_axes"],
                                   rules)
            bs = inp.batch_shardings_for(mesh, spec["batch"], rules)
            jitted = jax.jit(step, in_shardings=(ss, bs),
                             out_shardings=(ss, _metrics_shardings(mesh)),
                             donate_argnums=(0,))
            lowered = jitted.lower(spec["state"], spec["batch"])
        elif shape.kind == "prefill":
            step = serve_engine.make_prefill_step(cfg, shape.seq_len)
            ps = inp.shardings_for(mesh, spec["params"], spec["param_axes"],
                                   rules)
            bs = inp.batch_shardings_for(mesh, spec["batch"], rules)
            jitted = jax.jit(step, in_shardings=(ps, bs))
            lowered = jitted.lower(spec["params"], spec["batch"])
        else:  # decode
            step = serve_engine.make_serve_step(cfg)
            ps = inp.shardings_for(mesh, spec["params"], spec["param_axes"],
                                   rules)
            cs = inp.shardings_for(mesh, spec["cache"], spec["cache_axes"],
                                   rules)
            ts = shr.named_sharding_for(
                mesh, ("batch", None), tuple(spec["tokens"].shape), rules)
            pos_s = shr.named_sharding_for(
                mesh, ("batch",), tuple(spec["pos"].shape), rules)
            jitted = jax.jit(step, in_shardings=(ps, ts, pos_s, cs),
                             donate_argnums=(3,))
            lowered = jitted.lower(spec["params"], spec["tokens"],
                                   spec["pos"], spec["cache"])
    return lowered, cfg, spec, rules


def analyze_cell(arch: str, shape_name: str, mesh, mesh_name: str,
                 crosscheck: bool = False, cfg_overrides: dict | None = None,
                 rules_overrides: dict | None = None) -> dict:
    """lower + compile + extract every §Roofline input for one cell."""
    t0 = time.perf_counter()
    lowered, cfg, spec, rules = lower_cell(
        arch, shape_name, mesh, False, cfg_overrides, rules_overrides)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    parsed = hlo_costs.rollup(hlo)

    crosscheck_flops = None
    if crosscheck:
        lo_u, *_ = lower_cell(arch, shape_name, mesh, True, cfg_overrides,
                              rules_overrides)
        crosscheck_flops = float(
            lo_u.cost_analysis().get("flops", 0.0))

    shape = spec["shape"]
    chips = len(mesh.devices.flatten())
    n_params = api.n_params(cfg)
    mf = rt.model_flops(cfg, n_params, shape.kind, shape.seq_len,
                        shape.global_batch)
    af = rt.attn_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
    terms = rt.RooflineTerms(
        flops_per_chip=parsed.flops,
        hbm_bytes_per_chip=parsed.bytes_major,
        wire_bytes_per_chip=parsed.coll_wire,
        chips=chips,
        model_flops_global=mf,
        attn_flops_global=af,
    )
    peak_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mesh_dims": mesh_dims(mesh),
        "chips": chips,
        "kind": shape.kind,
        "n_params": n_params,
        "n_params_active": rt.active_params(cfg, n_params),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_chip": peak_bytes,
            "fits_16GiB": bool(peak_bytes < 16 * 1024**3),
        },
        "cost": {
            "parsed_flops_per_chip": parsed.flops,
            "parsed_bytes_per_chip": parsed.bytes_major,
            "parsed_bytes_upper_bound": parsed.bytes,
            "parsed_transcendentals": parsed.transcendentals,
            "xla_flat_flops": float(cost.get("flops", 0.0)),
            "xla_flat_bytes": float(cost.get("bytes accessed", 0.0)),
            "crosscheck_unrolled_global_flops": crosscheck_flops,
            "while_trips": parsed.while_trips,
        },
        "collectives": {
            "count": parsed.coll_count,
            "operand_bytes": parsed.coll_operand,
            "wire_bytes": parsed.coll_wire,
            "by_op": parsed.coll_by_op,
            "flat_structure": coll.summarize(coll.parse_collectives(hlo)),
        },
        "roofline": terms.to_dict(),
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
        "overrides": {"cfg": cfg_overrides or {},
                      "rules": rules_overrides or {}},
    }


def run_cells(cells, out_dir: str, skip_existing: bool = False) -> list[dict]:
    os.makedirs(out_dir, exist_ok=True)
    results = []
    meshes = {}
    for arch, shape_name, mesh_name in cells:
        tag = f"{arch}__{shape_name}__{mesh_name}"
        path = os.path.join(out_dir, tag + ".json")
        if skip_existing and os.path.exists(path):
            with open(path) as f:
                results.append(json.load(f))
            print(f"[skip] {tag}")
            continue
        if mesh_name not in meshes:
            meshes[mesh_name] = make_production_mesh(
                multi_pod=(mesh_name == "multipod"))
        try:
            res = analyze_cell(arch, shape_name, meshes[mesh_name], mesh_name)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            r = res["roofline"]
            print(f"[ok] {tag}: flops/chip={r['flops_per_chip']:.3e} "
                  f"wire/chip={r['wire_bytes_per_chip']:.3e} "
                  f"peak={res['memory']['peak_bytes_per_chip']/2**30:.2f}GiB "
                  f"bottleneck={r['bottleneck']} "
                  f"(compile {res['timing']['compile_s']:.1f}s)")
            results.append(res)
        except Exception as e:
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape_name,
                            "mesh": mesh_name, "error": str(e)})
    return results


def all_cells(mesh_names=("single", "multipod")):
    cells = []
    for arch in configs.ARCHS:
        cfg = configs.get_config(arch)
        for shape_name, shape in configs.SHAPES.items():
            ok, _ = configs.applicable(cfg, shape)
            if not ok:
                continue
            for mesh_name in mesh_names:
                cells.append((arch, shape_name, mesh_name))
    return cells


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, choices=list(configs.ARCHS))
    p.add_argument("--shape", default=None, choices=list(configs.SHAPES))
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true",
                   help="use the 2x16x16 multi-pod mesh for --arch/--shape")
    p.add_argument("--single-pod-only", action="store_true")
    p.add_argument("--multi-pod-only", action="store_true")
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    if args.all:
        names = ("single", "multipod")
        if args.single_pod_only:
            names = ("single",)
        if args.multi_pod_only:
            names = ("multipod",)
        cells = all_cells(names)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape,
                  "multipod" if args.multi_pod else "single")]
    results = run_cells(cells, args.out, args.skip_existing)
    n_fail = sum(1 for r in results if "error" in r)
    print(f"\n{len(results) - n_fail}/{len(results)} cells OK")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
