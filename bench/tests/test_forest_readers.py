"""The forest's per-layer readers ``sync_iter_us.forest`` and
``merge_us.forest`` on small synthetic device traces of four chips."""

import pytest

from harness import runner

FOREST = {"n_workers": 244, "board_size": 11, "n_playouts": 124928,
          "n_tasks": 488, "n_trees": 8}          # m = 256


def chip(rounds, merges, last=None):
    """``XLA Modules`` events of one chip: round runs and merge runs, each
    (start_ns, duration_ns), and a last event, which may be cut."""
    mods = ([(s, d, "jit__sharded_chunk(11)") for s, d in rounds]
            + [(s, d, "jit_sync_root_stats(12)") for s, d in merges]
            + [(s, 5, "jit_fold_member_task_keys(13)") for s, _ in rounds])
    if last is not None:
        mods.append(last)
    return {"ops": [], "modules": sorted(mods)}


def ctx_for(devices, t0=0, t1=20_000_000):
    return {"trace": {"devices": devices, "host": []}, "t0": t0, "t1": t1,
            "window_s": (t1 - t0) / 1e9, "device_kind": "TPU v5 lite",
            "config": FOREST}


def read(name, ctx):
    return runner.metric_reader(name)(ctx)


def four_chips():
    """Chip n runs rounds of (2 + n) ms and merges of (10 + n) us; each chip
    ends on a round the profiler cut at 1 ms, which both readers leave out
    (a whole merge or round read as cut would pull both means down)."""
    devs = {}
    for n in range(4):
        r, g = (2 + n) * 1_000_000, (10 + n) * 1_000
        rounds = [(100_000, r), (100_000 + r + 50_000, r)]
        merges = [(100_000 + r + 10_000, g), (100_000 + 2 * r + 60_000, g)]
        cut = (100_000 + 2 * r + 100_000, 1_000_000, "jit__sharded_chunk(11)")
        devs[f"/device:TPU:{n}"] = chip(rounds, merges, last=cut)
    return devs


def test_sync_iter_us_forest_means_whole_rounds_over_chips():
    # rounds of 2, 3, 4, 5 ms over m = 256 iterations, mean over the chips
    want = sum((2 + n) * 1e6 / 256 for n in range(4)) / 4 / 1e3
    assert read("sync_iter_us.forest", ctx_for(four_chips())) == \
        pytest.approx(want, rel=1e-12)


def test_merge_us_forest_means_whole_merges_over_chips():
    assert read("merge_us.forest", ctx_for(four_chips())) == \
        pytest.approx(11.5, rel=1e-12)


def test_a_cut_last_merge_is_left_out():
    devs = {"/device:TPU:0": chip([(0, 2_000_000)], [(2_100_000, 20_000)],
                                  last=(3_000_000, 7_000,
                                        "jit_sync_root_stats(12)"))}
    assert read("merge_us.forest", ctx_for(devs)) == pytest.approx(20.0)


def test_only_runs_wholly_in_the_window_count():
    devs = four_chips()
    # the window opens inside each chip's first round, so its first merge
    # and second round remain (and the second merge, then the cut round)
    ctx = ctx_for(devs, t0=200_000)
    want = sum((2 + n) * 1e6 / 256 for n in range(4)) / 4 / 1e3
    assert read("sync_iter_us.forest", ctx) == pytest.approx(want)
    assert read("merge_us.forest", ctx) == pytest.approx(11.5)


def test_no_run_wholly_in_the_window_reads_none():
    devs = four_chips()
    for name in ("sync_iter_us.forest", "merge_us.forest"):
        assert read(name, ctx_for(devs, t0=9_000_000, t1=9_500_000)) is None
        assert read(name, ctx_for({})) is None
    # a chip whose only round is its last program event reads nothing
    alone = {"/device:TPU:0": chip([], [], last=(0, 2_000_000,
                                                 "jit__sharded_chunk(11)"))}
    assert read("sync_iter_us.forest", ctx_for(alone)) is None


def test_the_single_tree_program_is_not_the_forest_round():
    devs = {"/device:TPU:0": {"ops": [], "modules": [
        (0, 1_000_000, "jit_run_chunk(1)"),
        (2_000_000, 1_000_000, "jit__sharded_chunk_metrics(2)"),
        (4_000_000, 5, "jit_fold_task_keys(3)")]}}
    assert read("sync_iter_us.forest", ctx_for(devs)) is None
    assert read("merge_us.forest", ctx_for(devs)) is None
