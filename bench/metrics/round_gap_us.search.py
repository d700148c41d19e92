"""Device time from the end of one ``run_chunk`` program to the start of the
next within one search, in microseconds, averaged over the round boundaries
wholly inside the traced window (harness.phases.round_gaps): the boundary
between rounds that the grain size sets the number of."""

from harness import phases


def read(ctx):
    gaps = [g for d in ctx["trace"]["devices"].values()
            for g in phases.round_gaps(d["modules"], ctx["t0"], ctx["t1"])]
    return sum(gaps) / len(gaps) / 1e3 if gaps else None
