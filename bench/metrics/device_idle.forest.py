"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the cell's chips: 1 - (union of the device's op
intervals, container ops left out) / window. A gap between two ops inside
one program counts as idle."""

from harness import trace as tr


def read(ctx):
    return tr.idle_pct(ctx["trace"]["devices"], ctx["t0"], ctx["t1"])
