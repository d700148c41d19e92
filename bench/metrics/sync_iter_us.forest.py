"""Device time of one sync iteration of the forest, in microseconds: on each
chip, the device time of the ``_sharded_chunk`` programs (one round of its
own members, m = n_playouts / n_tasks iterations) that ran wholly inside
the traced window over the iterations they ran, then the mean over the
chips. A chip's last program event, which the profiler may have cut, is
left out (``harness.phases.program_runs``)."""

from harness import phases


def read(ctx):
    cfg = ctx["config"]
    m = max(1, cfg["n_playouts"] // max(1, cfg["n_tasks"]))
    per_chip = []
    for d in ctx["trace"]["devices"].values():
        runs = phases.program_runs(d["modules"], "_sharded_chunk", ctx["t0"],
                                   ctx["t1"])
        if runs:
            per_chip.append(sum(b - a for a, b in runs) / (len(runs) * m))
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / 1e3
