"""The ``uct_select`` kernel's share of its roofline, in percent: the least
time for the bytes one call must move (harness.work.uct_select_bytes at the
cell's lanes and children) at the chip's HBM peak, over the mean device time
of its calls in the traced window. The bound is memory."""

from harness import peaks, trace as tr, work


def read(ctx):
    cfg = ctx["config"]
    moved = work.uct_select_bytes(cfg["n_workers"], cfg["board_size"] ** 2)
    return work.roofline_pct(
        moved, tr.mean_call_s(ctx["trace"]["devices"], "uct_select", ctx["t0"],
                              ctx["t1"]),
        peaks.peaks(ctx["device_kind"])["hbm_bytes_s"])
