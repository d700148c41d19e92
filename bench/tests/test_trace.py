"""The trace reduction on a small synthetic trace."""

import pytest

from harness import trace as tr

# (start_ns, duration_ns, name): two overlapping ops, a gap, a loop that
# holds two ops, a collective, and an op that runs past the window's end
OPS = [
    (0, 10, "fusion.1"),
    (5, 10, "uct_select.6"),
    (30, 40, "while.3"),
    (30, 15, "hex_winner.7"),
    (50, 10, "all-reduce.2"),
    (90, 30, "fusion.1"),
]
HOST = [(0, 200, "move"), (20, 5, "step"), (70, 30, "generator_wait")]


def test_busy_union_merges_overlaps():
    assert tr.busy_intervals(OPS) == [(0, 15), (30, 70), (90, 120)]
    assert tr.busy_ns(OPS, 0, 100) == 15 + 40 + 10


def test_idle_gaps_cover_the_rest_of_the_window():
    assert tr.idle_gaps(OPS, 0, 100) == [(15, 30), (70, 90)]
    assert tr.idle_gaps([], 0, 100) == [(0, 100)]


def test_named_gaps_take_the_innermost_host_span():
    gaps = tr.named_gaps(OPS, HOST, 0, 100)
    assert gaps == [["idle during generator_wait", 20e-9],
                    ["idle during step", 15e-9]]
    assert tr.host_activity(HOST, 500) == "outside"


def test_op_time_skips_containers_and_clips():
    t = tr.op_time(OPS, 0, 100)
    assert "while" not in t
    assert t["fusion"] == pytest.approx(20e-9)      # 10 + 10 inside window
    assert t["hex_winner"] == pytest.approx(15e-9)
    assert tr.top_ops(OPS, 0, 100)[0][0] == "fusion"


def test_calls_and_collectives():
    assert tr.calls(OPS, "uct_select", 0, 100) == [10]
    assert tr.calls(OPS, "fusion", 0, 100) == [10]   # the cut one is left out
    assert tr.collective_ns(OPS, 0, 100) == 10
    assert tr.op_kind("uct_select.6") == "uct_select"
    assert tr.op_name("%all-reduce.2 = f32[8] all-reduce(x)") == "all-reduce.2"


def test_module_runs_by_program_name():
    mods = [(0, 50, "jit_run_chunk(123)"), (60, 50, "jit_run_chunk(123)"),
            (0, 5, "jit_sync_root_stats(9)")]
    assert tr.module_runs(mods, "run_chunk", 0, 100) == [50]
    assert tr.module_runs(mods, "sync_root_stats", 0, 100) == [5]


def test_leaf_ops_leave_out_containers():
    assert [n for _, _, n in tr.leaf_ops(OPS)] == [
        "fusion.1", "uct_select.6", "hex_winner.7", "all-reduce.2", "fusion.1"]
    # the loop's span covers the gap between its two body ops
    assert tr.busy_ns(tr.leaf_ops(OPS), 0, 100) == 15 + 15 + 10 + 10


def test_idle_share_counts_gaps_inside_programs():
    module = [(0, 100, "jit_f(1)")]
    devices = {"/device:TPU:0": {"ops": OPS, "modules": module},
               "/device:TPU:1": {"ops": [(0, 100, "fusion.2")],
                                 "modules": module}}
    # device 0: leaf ops busy 50 of 100 ns although its program runs
    # throughout; device 1 busy throughout
    assert tr.device_busy_ns(devices, 0, 100) == [50, 100]
    assert tr.program_busy_ns(devices, 0, 100) == [100, 100]
    assert tr.idle_pct(devices, 0, 100) == pytest.approx(100 * 50 / 200)


def test_mean_call_time_over_devices():
    devices = {"/device:TPU:0": {"ops": OPS, "modules": []}}
    assert tr.idle_pct({}, 0, 100) is None
    assert tr.mean_call_s(devices, "hex_winner", 0, 100) == pytest.approx(15e-9)
    assert tr.mean_call_s(devices, "sort", 0, 100) is None
