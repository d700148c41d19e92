"""Device time of one root merge of the forest, in microseconds: the mean
duration of the ``sync_root_stats`` programs that ran wholly inside the
traced window on each chip, then the mean over the chips. It holds the
program's ``merge`` scope whole; ``collective_ms.forest`` is its cross-chip
part. A chip's last program event, which the profiler may have cut, is
left out (``harness.phases.program_runs``)."""

from harness import phases


def read(ctx):
    per_chip = []
    for d in ctx["trace"]["devices"].values():
        runs = phases.program_runs(d["modules"], "sync_root_stats", ctx["t0"],
                                   ctx["t1"])
        if runs:
            per_chip.append(sum(b - a for a, b in runs) / len(runs))
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / 1e3
