"""Device time of the ``backup`` phase of one sync iteration, in microseconds:
the scatter-adds of the results along the paths into the tree. The leaf ops
under that scope in the ``run_chunk`` programs wholly inside the traced
window, over the sync iterations those programs ran (harness.phases); None
where the program names no phases."""

from harness import phases


def read(ctx):
    return phases.phase_us(ctx, "backup")
