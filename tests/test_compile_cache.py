"""Placement of the persistent compilation cache (``launch/compile_cache``).

Each case runs in a fresh process, because JAX reads
``JAX_COMPILATION_CACHE_DIR`` once at start-up and the cache is process
state. The fixed in-checkout default is redirected to a temporary
directory so the test writes nothing into the checkout.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

# compiles one small program with the cache on and prints the directory
# and the number of persistent-cache hits
SCRIPT = r"""
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from jax import monitoring
from repro.launch import compile_cache

compile_cache.DEFAULT_CACHE_DIR = Path(sys.argv[1])
hits = []
monitoring.register_event_listener(lambda name, **kw: hits.append(name))
print("DIR", compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print("HITS", hits.count("/jax/compilation_cache/cache_hits"))
"""


def _run(default_dir: Path, env_dir: Path | None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(default_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(line.split(" ", 1) for line in proc.stdout.splitlines())


def test_default_is_a_fixed_path_in_the_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


def test_env_var_wins_and_second_run_hits(tmp_path):
    default, chosen = tmp_path / "default", tmp_path / "chosen"
    first = _run(default, chosen)
    assert first["DIR"] == str(chosen)
    assert any(chosen.iterdir())
    assert not default.exists()
    assert int(_run(default, chosen)["HITS"]) > 0


def test_unset_env_uses_default_and_second_run_hits(tmp_path):
    default = tmp_path / "default"
    first = _run(default, None)
    assert first["DIR"] == str(default)
    assert int(first["HITS"]) == 0
    assert any(default.iterdir())
    assert int(_run(default, None)["HITS"]) > 0
