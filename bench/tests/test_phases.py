"""The phase and round-boundary readers on small synthetic traces, and the
existing readers pinned to what they read on the trace of test_trace.py."""

import pytest

from harness import phases, runner
from harness import trace as tr
from test_trace import HOST, OPS

HLO = """HloModule jit_run_chunk

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %negate.1 = f32[4]{0} negate(%param_0), metadata={op_name="jit(run_chunk)/while/body/descent/neg"}
}

%body.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %fusion.3 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_chunk)/while/body/descent/while/body/gather"}
  %uct_select.6 = s32[4]{0} custom-call(%fusion.3), metadata={op_name="jit(run_chunk)/while/body/descent/jit(uct_select)/pallas_call"}
  %add.5 = f32[4]{0} add(%fusion.3, %fusion.3), metadata={op_name="jit(run_chunk)/while/body/descent/while/body/backup/add"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%add.5)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %sort.4 = f32[4]{0} sort(%copy-done.2), metadata={op_name="jit(run_chunk)/while/body/expand/sort"}
  %cumsum.12 = f32[4]{0} fusion(%sort.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="reduce_window_sum"}
  %hex_winner.7 = s8[4]{0} custom-call(%cumsum.12), metadata={op_name="jit(run_chunk)/while/body/leaf_eval/jit(hex_winner)/pallas_call"}
  %scatter.8 = f32[4]{0} scatter(%hex_winner.7), metadata={op_name="jit(run_chunk)/while/body/backup/scatter-add"}
  %add.9 = s32[] add(%arg), metadata={op_name="jit(run_chunk)/while/body/add"}
  %slice-start.10 = ((f32[4]{0}), f32[2]{0}, s32[]) slice-start(%scatter.8)
  ROOT %tuple.11 = (s32[], f32[2]{0}) tuple(%add.9, %slice-start.10)
}

ENTRY %main.3 (p: f32[4]) -> (s32[], f32[2]) {
  %p = f32[4]{0} parameter(0)
  ROOT %while.39 = (s32[], f32[2]{0}) while(%p), body=%body.2, metadata={op_name="jit(run_chunk)/while"}
}
"""

SCOPES = phases.scope_map(HLO)

# two run_chunk rounds of one search (a key fold and an input conversion
# between them), the stats readbacks and the next search's set-up, a round
# of the next search, and one that the window's end cuts
MODULES = [
    (0, 100, "jit_run_chunk(7)"),
    (102, 3, "jit_fold_task_keys(2)"),
    (106, 1, "jit_convert_element_type(5)"),
    (110, 100, "jit_run_chunk(7)"),
    (215, 5, "jit_argmax(4)"),
    (230, 5, "jit_fold_task_keys(2)"),
    (240, 100, "jit_run_chunk(7)"),
    (345, 100, "jit_run_chunk(7)"),
]
T0, T1 = 0, 400
ROUND_OPS = [
    (0, 10, "fusion.3"), (10, 5, "uct_select.6"), (15, 5, "add.5"),
    (20, 4, "copy-done.2"), (30, 10, "sort.4"), (40, 6, "cumsum.12"),
    (50, 30, "hex_winner.7"), (80, 8, "scatter.8"), (90, 2, "add.9"),
    (0, 100, "while.39"),
]


def shifted(ops, dt):
    return [(s + dt, d, n) for s, d, n in ops]


OPS_3 = (ROUND_OPS + shifted(ROUND_OPS, 110) + shifted(ROUND_OPS, 240)
         + shifted(ROUND_OPS, 345) + [(102, 3, "fusion.3")])


def test_scope_map_takes_the_innermost_phase_on_the_path():
    assert phases.phase_of("jit(f)/while/body/descent/while/body/backup/add"
                           ) == "backup"
    assert phases.phase_of("jit(f)/while/body/add") is None
    assert phases.phase_of(None) is None
    assert SCOPES["negate.1"] == "descent"
    assert SCOPES["fusion.3"] == "descent"
    assert SCOPES["uct_select.6"] == "descent"
    assert SCOPES["add.5"] == "backup"
    assert SCOPES["sort.4"] == "expand"
    assert SCOPES["hex_winner.7"] == "leaf_eval"
    assert SCOPES["scatter.8"] == "backup"


def test_ops_the_compiler_made_take_a_neighbour_phase():
    # an async copy takes its consumer's phase, through the copy-done
    assert SCOPES["copy-start.2"] == SCOPES["copy-done.2"] == "expand"
    # a bare op_name (a rewritten scan) is the compiler's too
    assert SCOPES["cumsum.12"] == "leaf_eval"
    # no consumer with a phase: the producer's
    assert SCOPES["slice-start.10"] == "backup"
    # a path that names no phase stays unscoped
    assert SCOPES["add.9"] == "unscoped"


def test_breakdown_reads_whole_run_chunk_runs_per_iteration():
    devices = {"/device:TPU:0": {"ops": OPS_3, "modules": MODULES}}
    got = phases.breakdown(devices, SCOPES, 2, T0, T1)
    # three runs wholly in the window, m = 2: 6 iterations; the key fold's
    # op, the loop container and the cut fourth run are left out
    n = 6 * 1e3
    assert got["descent"] == pytest.approx(3 * (10 + 5) / n)
    assert got["backup"] == pytest.approx(3 * (5 + 8) / n)
    assert got["expand"] == pytest.approx(3 * (4 + 10) / n)
    assert got["leaf_eval"] == pytest.approx(3 * (6 + 30) / n)
    assert got["unscoped"] == pytest.approx(3 * 2 / n)
    assert phases.breakdown(devices, SCOPES, 2, 500, 600) is None


def test_a_run_the_profiler_cut_is_left_out():
    # the last program event may end at the profiler's stop, not its own end
    cut = MODULES[:-1] + [(345, 30, "jit_run_chunk(7)")]
    assert phases.program_runs(cut, "run_chunk", T0, T1) == [
        (0, 100), (110, 210), (240, 340)]
    assert phases.program_runs(MODULES, "run_chunk", T0, 1000) == [
        (0, 100), (110, 210), (240, 340)]


def test_an_op_the_map_does_not_know_is_unscoped():
    got = phases.phase_ns([(0, 7, "mystery.1"), (7, 3, "sort.4")], SCOPES)
    assert got["unscoped"] == 7 and got["expand"] == 3


def test_round_gaps_stay_inside_one_search():
    # 110 - 100 inside the first search; the readbacks and set-up between
    # 210 and 240 end it; the cut last run gives none
    assert phases.round_gaps(MODULES, T0, T1) == [10]
    assert phases.round_gaps(MODULES, T0, 1000) == [10, 5]


def ctx_for(devices, config=None):
    return {"trace": {"devices": devices, "host": []}, "t0": T0, "t1": T1,
            "window_s": (T1 - T0) / 1e9, "device_kind": "TPU v5 lite",
            "config": config or {"n_workers": 244, "board_size": 11,
                                 "n_playouts": 1024, "n_tasks": 512}}


def test_the_phase_readers(monkeypatch, capsys):
    monkeypatch.setattr(phases, "run_chunk_scopes", lambda cfg: SCOPES)
    ctx = ctx_for({"/device:TPU:0": {"ops": OPS_3, "modules": MODULES}})
    read = {p: runner.metric_reader(f"{p}_us.search")(ctx)
            for p in phases.PHASES}
    assert read == pytest.approx({"descent": 15 / 2e3, "expand": 14 / 2e3,
                                  "leaf_eval": 36 / 2e3, "backup": 13 / 2e3})
    log = capsys.readouterr().err
    assert "unscoped 2.50%" in log            # 2 of 80 ns per round
    assert "rounds in the window (ms): 0.000, 0.000, 0.000" in log
    assert runner.metric_reader("round_gap_us.search")(ctx) == 10 / 1e3


def test_a_program_without_phases_reads_nothing(monkeypatch):
    unscoped = {k: "unscoped" for k in SCOPES}
    monkeypatch.setattr(phases, "run_chunk_scopes", lambda cfg: unscoped)
    ctx = ctx_for({"/device:TPU:0": {"ops": OPS_3, "modules": MODULES}})
    for p in phases.PHASES:
        assert runner.metric_reader(f"{p}_us.search")(ctx) is None
    empty = ctx_for({"/device:TPU:0": {"ops": [], "modules": []}})
    assert runner.metric_reader("round_gap_us.search")(empty) is None


def test_the_compiled_round_names_all_four_phases():
    cfg = {"game": "hex", "board_size": 5, "n_workers": 4, "n_playouts": 32,
           "n_tasks": 8, "cp": 1.0, "tree_cap": 256, "vl_rounds": 1,
           "virtual_loss": 1.0, "select_noise": 1e-3, "scheduler": "fifo"}
    assert set(phases.PHASES) <= set(phases.run_chunk_scopes(cfg).values())


def test_the_existing_readers_read_as_before():
    """The readers that were there before the phase readers, pinned to the
    values they give on test_trace.py's trace."""
    modules = [(0, 50, "jit_run_chunk(123)"), (60, 50, "jit_run_chunk(123)"),
               (0, 5, "jit_sync_root_stats(9)")]
    ctx = ctx_for({"/device:TPU:0": {"ops": OPS, "modules": modules}},
                  {"n_workers": 244, "board_size": 11,
                   "n_playouts": 1048576, "n_tasks": 4096})
    ctx["t1"] = 100
    want = {"device_idle.search": 50.0,
            "sync_iter_us.search": 0.0001953125,
            "uct_select_roofline.search": 7233.601953601954,
            "hex_winner_roofline.search": 242.31176231176235}
    for name, value in want.items():
        assert runner.metric_reader(name)(ctx) == pytest.approx(
            value, rel=1e-12), name
    assert tr.named_gaps(tr.leaf_ops(OPS), HOST, 0, 100) == [
        ["idle during generator_wait", 3e-08],
        ["idle during step", 1.5e-08],
        ["idle during move", 5e-09]]
