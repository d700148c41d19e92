"""Device time of one sync iteration, in microseconds: the device time of
the ``run_chunk`` programs that ran wholly inside the traced window over the
sync iterations they ran (each runs one round of m = n_playouts / n_tasks)."""

from harness import trace as tr


def read(ctx):
    cfg = ctx["config"]
    m = max(1, cfg["n_playouts"] // max(1, cfg["n_tasks"]))
    runs = []
    for d in ctx["trace"]["devices"].values():
        runs += tr.module_runs(d["modules"], "run_chunk", ctx["t0"], ctx["t1"])
    if not runs:
        return None
    return sum(runs) / (len(runs) * m) / 1e3
