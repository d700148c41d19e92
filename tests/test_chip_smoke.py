"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny widths, its tree
checks against a corrupted tree, and its refusal to report success
without a TPU.

On the CPU, ``kernels.ops`` dispatches to the jnp paths, so the kernel
phase here checks the plumbing, not the Pallas bodies (those are compiled
for the chip in tests/test_tpu_compile.py). The configs use
``tree_cap=320``, a game class no other test file serves.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.gscpm import GSCPMConfig, gscpm_search

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

# 8 tasks on 4 lanes: two schedule rounds of m = 8 iterations
TINY = GSCPMConfig(board_size=5, n_playouts=64, n_tasks=8, n_workers=4,
                   tree_cap=320)


def test_kernel_phase_tiny():
    smoke.kernel_phase(TINY, seed=0, n_boards=64)


def test_search_phase_runs_full_budget_when_it_fits(capsys):
    smoke.search_phase(TINY, seed=0, seconds=1e9)
    out = capsys.readouterr().out
    assert "BUDGET CUT" not in out
    assert "64 playouts" in out


def test_search_phase_cuts_budget_to_whole_rounds(capsys):
    smoke.search_phase(TINY, seed=0, seconds=0.0)
    out = capsys.readouterr().out
    assert "BUDGET CUT: 64 -> 32 playouts (1 of 2 rounds" in out


def test_host_reference_check_rejects_a_different_tree(monkeypatch):
    """The device-vs-host comparison fails when the two searches differ
    (here the second, host-side search is handed a corrupted tree)."""
    import repro.core.gscpm as gscpm

    real, calls = gscpm.gscpm_search, []

    def search(*a, **kw):
        tree, st = real(*a, **kw)
        calls.append(1)
        if len(calls) == 2:
            tree = tree._replace(visits=tree.visits.at[1].add(1.0))
        return tree, st

    monkeypatch.setattr(gscpm, "gscpm_search", search)
    with pytest.raises(smoke.SmokeFailure, match="visits"):
        smoke.host_reference_check(TINY, seed=0)
    assert len(calls) == 2


def test_serving_phase_tiny(capsys):
    smoke.serving_phase(TINY, seed=0, rounds=2, grain=4, gomoku_size=5)
    out = capsys.readouterr().out
    assert out.count(": answered") == 6
    assert "bit-identical" in out


@pytest.mark.parametrize("corrupt", ["root_visits", "wins_above_visits",
                                     "child_heavier_than_parent",
                                     "stale_child_move"])
def test_check_tree_rejects_a_corrupted_tree(corrupt):
    tree, st = gscpm_search(TINY.game_obj.init_board(), 1, TINY,
                            jax.random.key(1))
    smoke.check_tree(tree, st["playouts"])
    if corrupt == "root_visits":
        tree = tree._replace(visits=tree.visits.at[0].add(1.0))
    elif corrupt == "wins_above_visits":
        tree = tree._replace(wins=tree.wins.at[1].set(tree.visits[1] + 1))
    elif corrupt == "child_heavier_than_parent":
        tree = tree._replace(visits=tree.visits.at[2].add(2 * st["playouts"]))
    else:
        tree = tree._replace(child_move=tree.child_move.at[0, 0].add(1))
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_tree(tree, st["playouts"])


def _run_alone(script: Path, tmp_path: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu(tmp_path):
    proc = _run_alone(ROOT / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_without_the_repository(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone)
    proc = _run_alone(alone / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


FOREST_REHEARSAL = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.core.gscpm import GSCPMConfig
cfg = GSCPMConfig(board_size=5, n_playouts=64, n_tasks=8, n_workers=4,
                  tree_cap=320)
smoke.forest_phase(cfg, seed=0, n_playouts=32, n_tasks=8, tree_cap=320)
"""


def test_forest_phase_on_four_virtual_devices():
    """``--four-chips``' phase at tiny widths on four virtual CPU devices
    (a child process: the device count is fixed when JAX starts): the
    forest, merged after each of its 2 rounds, is the same in every tree
    array sharded and on one device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (str(ROOT / "src"),
                               os.environ.get("PYTHONPATH")) if p),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run(
        [sys.executable, "-c", FOREST_REHEARSAL, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "2 merges, on 4 device(s)" in proc.stdout
    assert "sharded vs one chip: bit-identical" in proc.stdout
