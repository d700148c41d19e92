"""Device time of the ``leaf_eval`` phase of one sync iteration, in
microseconds: placing the proposed move and the playout, ``hex_winner``
included. The leaf ops under that scope in the ``run_chunk`` programs wholly
inside the traced window, over the sync iterations those programs ran
(harness.phases); None where the program names no phases."""

from harness import phases


def read(ctx):
    return phases.phase_us(ctx, "leaf_eval")
