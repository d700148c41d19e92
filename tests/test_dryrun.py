"""Dry-run machinery smoke: one small cell on an 8-device subprocess.

The full 40-cell x 2-mesh sweep runs via ``python -m repro.launch.dryrun
--all`` (artifacts/dryrun); this test proves the plumbing (input specs,
shardings, lower+compile, cost extraction) on a reduced mesh quickly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.mesh import make_auto_mesh
from repro.launch import inputs as inp
from repro.launch import dryrun
from repro.roofline import hlo_costs
mesh = make_auto_mesh((4, 2), ("data", "model"))
arch, shape = sys.argv[1], sys.argv[2]
ov = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
      "d_ff": 512, "vocab": 4096}
lowered, cfg, spec, rules = dryrun.lower_cell(arch, shape, mesh,
                                              cfg_overrides=ov, unroll=False)
compiled = lowered.compile()
mem = compiled.memory_analysis()
costs = hlo_costs.rollup(compiled.as_text())
assert costs.flops > 0, "parser found no flops"
assert mem.temp_size_in_bytes > 0
print("OK", costs.flops, costs.coll_count)
"""


@pytest.mark.parametrize("arch,shape", [
    ("smollm-135m", "train_4k"),
    ("smollm-135m", "decode_32k"),
    ("qwen1.5-0.5b", "prefill_32k"),
])
def test_dryrun_cell_subprocess(arch, shape):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch, shape],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_input_specs_all_cells():
    """input_specs builds (abstractly, no devices needed) for all 40 cells."""
    from repro import configs
    from repro.launch.inputs import input_specs
    n = 0
    for arch in configs.ARCHS:
        cfg = configs.get_config(arch)
        for sname, sh in configs.SHAPES.items():
            ok, why = configs.applicable(cfg, sh)
            if not ok:
                assert "full-attn" in why
                continue
            spec = input_specs(arch, sname)
            assert spec["cfg"].vocab == cfg.vocab
            n += 1
    assert n == 32  # 40 logical cells - 8 long_500k full-attn skips

def test_cell_count_documented():
    """10 archs x 4 shapes = 40; long_500k runs only for zamba2 + xlstm."""
    from repro import configs
    total = len(configs.ARCHS) * len(configs.SHAPES)
    assert total == 40
    runnable = 0
    for arch in configs.ARCHS:
        cfg = configs.get_config(arch)
        runnable += sum(
            1 for sh in configs.SHAPES.values()
            if configs.applicable(cfg, sh)[0])
    assert runnable == 32
