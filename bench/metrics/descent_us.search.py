"""Device time of the ``descent`` phase of one sync iteration, in microseconds:
the batched UCT descent and the move proposal. The leaf ops under that scope
in the ``run_chunk`` programs wholly inside the traced window, over the sync
iterations those programs ran (harness.phases); None where the program names
no phases."""

from harness import phases


def read(ctx):
    return phases.phase_us(ctx, "descent")
