"""Device time of collective operations per root merge, in milliseconds,
averaged over the chips: each chip's collective op time in the traced window
over the ``sync_root_stats`` programs it ran there."""

from harness import trace as tr


def read(ctx):
    per_device = []
    for d in ctx["trace"]["devices"].values():
        syncs = tr.module_runs(d["modules"], "sync_root_stats", ctx["t0"],
                               ctx["t1"])
        coll = tr.collective_ns(d["ops"], ctx["t0"], ctx["t1"])
        if syncs and coll > 0:
            per_device.append(coll / len(syncs) / 1e6)
    if not per_device:
        return None
    return sum(per_device) / len(per_device)
