"""Runs one cell once: set-up, the measured window, the check, the result.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json`` holds
the deployment (its ``system`` names the module in ``harness/drivers`` that
runs it), ``bench/traffic/<mix>.json`` the mix, and
``bench/metrics/<metric>.py`` the reader of each per-layer metric.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parent


class NoAccelerator(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + start / ticks
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def load_benchmark(path: Path | None = None) -> dict:
    return json.loads((path or REPO / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(entry: dict) -> dict:
    return json.loads((REPO / entry["file"]).read_text())


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_class(system: str):
    return importlib.import_module(f"harness.drivers.{system}").Driver


def require_accelerator(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs


def device_block(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class TraceWindow:
    """The profiler over the first ``seconds`` of the window, stopped by a
    timer (a move may hold the main thread longer than that) or at the
    window's end, whichever comes first."""

    def __init__(self, log_dir: Path, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self.lock = threading.Lock()
        self.t_start = self.t_stop = None
        self.timer = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("window_start"):
            self.t_mark = time.perf_counter()
        self.timer = threading.Timer(self.seconds, self.stop)
        self.timer.daemon = True
        self.timer.start()

    def stop(self):
        import jax

        with self.lock:
            if self.t_stop is not None:
                return
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def close(self):
        if self.timer is not None:
            self.timer.cancel()
            self.timer.join()
        self.stop()

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_start


class ProgramsBuilt:
    """The programs JAX compiles, or reads from its compile cache, while
    the ``with`` is open: none should be built inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax

        self.built: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def _record(self, event: str, seconds: float, **kw) -> None:
        if event == self.EVENT:
            self.built.append((str(kw.get("fun_name", "?")), seconds))

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._record)

    def summary(self) -> str:
        names = ", ".join(f"{n} {s:.3f}s" for n, s in self.built)
        return f"{len(self.built)} programs built inside it" + (
            f" ({names})" if names else "")


def trace_window_ns(data: dict, window_s: float):
    """The traced window on the trace's clock: from the ``window_start``
    mark, for the seconds the profiler ran."""
    marks = [s for s, _, n in data["host"] if n == "window_start"]
    if marks:
        t0 = marks[0]
    else:
        starts = [e[0] for dev in data["devices"].values()
                  for e in dev["ops"] + dev["modules"]]
        t0 = min(starts) if starts else 0
    return t0, t0 + window_s * 1e9


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config: dict | None = None,
             traffic: dict | None = None, require_tpu: bool = True,
             out_dir: Path | None = None, t_process: float | None = None,
             log=None) -> dict:
    """Runs the cell once and returns the result object. ``config`` and
    ``traffic`` replace the cell's files (the tests use it to run a small
    copy); ``require_tpu=False`` lets a test drive a run without a chip."""
    from harness import spans
    from harness import trace as tr
    from harness import traffic as traffic_mod

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    t_process = t_process or process_start_time()
    spans.SPANS.clear()                  # a run names gaps by its own spans
    bench = bench or load_benchmark()
    cell = find(bench["workloads"], cell_name, "workload")
    cfg = config or load_config(find(bench["configs"], cell["config"],
                                     "configuration"))
    mix = traffic or traffic_mod.load(cell["traffic"])
    chips = int(cell["chips"])

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program, however quick to compile, is read from the cache after
    # a cell's first run, so set-up repeats the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_tpu:
        require_accelerator(chips)
    log(f"cell {cell_name}: seed {seed}, {seconds}s window, trace {int(trace)}"
        f", compile cache {cache}")

    driver = driver_class(cfg["system"])(cfg, mix, seed, chips, log=log)
    with spans.span("init"):
        driver.setup()
    tw = None
    made_dir = trace and out_dir is None
    if trace:
        out_dir = out_dir or Path(tempfile.mkdtemp(
            prefix="trace-", dir=os.environ.get("TMPDIR")))
        tw = TraceWindow(out_dir, float(mix.get("trace_seconds", seconds)))
    t_window = time.time()
    setup_s = t_window - t_process
    if tw:
        tw.start()
    try:
        with ProgramsBuilt() as built:
            rec = driver.window(float(seconds))
    finally:
        if tw:
            tw.close()
    log(f"window: {rec['attempted']} attempted, {rec['failed']} failed, "
        f"{built.summary()}")
    dev = device_block(chips) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices()),
        "memory_peak_bytes": 0}

    wanted = [m for m in (bench["per_layer"] if trace else bench["end_to_end"])
              if cell_name in m.get("workloads", [cell_name])]
    metrics = {}
    breakdown = None
    if trace:
        data = tr.load(out_dir)
        if made_dir:
            shutil.rmtree(out_dir, ignore_errors=True)
        t0, t1 = trace_window_ns(data, tw.window_s)
        host = spans.on_trace_clock(t0, tw.t_mark)
        dev["window_s"] = tw.window_s
        busy = tr.device_busy_ns(data["devices"], t0, t1)
        dev["busy_s"] = (sum(busy) / len(busy) / 1e9) if busy else 0.0
        prog = tr.program_busy_ns(data["devices"], t0, t1)
        log(f"device busy in the {tw.window_s:.3f}s traced window: ops "
            f"{dev['busy_s']:.6f}s, programs "
            f"{(sum(prog) / len(prog) / 1e9) if prog else 0.0:.6f}s")
        first = next(iter(data["devices"].values()), {"ops": [], "modules": []})
        breakdown = {
            "device_ops": tr.top_ops(first["ops"], t0, t1),
            "idle_gaps": tr.named_gaps(tr.leaf_ops(first["ops"]), host, t0,
                                       t1)}
        driver.after_trace(log)
        ctx = {"trace": data, "t0": t0, "t1": t1, "window_s": tw.window_s,
               "config": cfg, "traffic": mix, "driver": driver,
               "device_kind": dev["kind"]}
        # a run without a chip reads no device metric: the peaks table
        # knows only accelerators
        for m in wanted if require_tpu else []:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(driver.end_to_end(rec), setup_s=setup_s)
        for m in wanted:
            if m["name"] in values and values[m["name"]] is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    checks = driver.check(rec, log)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def small_copy(cfg: dict, mix: dict, lanes: int = 16, grain: int = 4,
               replay: int = 4) -> tuple[dict, dict]:
    """The cell's configuration and mix at 16 lanes and a small budget that
    keeps its number of rounds: a size the CPU runs in seconds."""
    cfg, mix = json.loads(json.dumps(cfg)), json.loads(json.dumps(mix))
    W = cfg["n_workers"]

    def shrink(entry):
        rounds = -(-entry["n_tasks"] // W)
        entry["n_tasks"] = lanes * rounds
        entry["n_playouts"] = lanes * rounds * grain

    cfg["n_workers"] = lanes
    shrink(cfg)
    cfg["tree_cap"] = max(1 << 12, 2 * lanes * grain * 16)
    mix["replay_iterations"] = replay
    mix["grace_seconds"] = 5.0
    return cfg, mix


def rehearse(cell_name: str, seed: int, seconds: float, trace: bool) -> dict:
    """A small copy of the cell on whatever JAX finds (the CPU here): the
    harness's control flow and its check, with no metrics in the result."""
    from harness import traffic as traffic_mod

    bench = load_benchmark()
    cell = find(bench["workloads"], cell_name, "workload")
    cfg, mix = small_copy(
        load_config(find(bench["configs"], cell["config"], "configuration")),
        traffic_mod.load(cell["traffic"]))
    result = run_cell(cell_name, seed, seconds, trace, bench=bench,
                      config=cfg, traffic=mix, require_tpu=False)
    result["metrics"] = {}             # a CPU run gives no device metric
    result.pop("breakdown", None)
    result["device"].pop("busy_s", None)
    return {"rehearsal": True, **result}
