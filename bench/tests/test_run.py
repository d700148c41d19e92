"""A cell refuses to run without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import runner

REPO = Path(__file__).resolve().parents[2]


def run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hex11-paper.search",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_cell_raises_without_a_tpu():
    with pytest.raises(runner.NoAccelerator):
        runner.run_cell("hex11-paper.search", 1, 1.0, False)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_names_files_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert callable(runner.metric_reader(m["name"]))


def test_programs_built_counts_what_compiles_inside():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    x3, x5, x7 = jnp.ones(3), jnp.ones(5), jnp.ones(7)
    f(x3)
    with runner.ProgramsBuilt() as built:
        f(x3)                              # compiled before: nothing built
        f(x5)                              # a new shape: one program
    f(x7)                                  # after the window: not counted
    assert len(built.built) == 1
    assert built.summary().startswith("1 programs built inside it")
