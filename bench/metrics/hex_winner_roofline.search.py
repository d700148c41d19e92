"""The ``hex_winner`` kernel's share of its roofline, in percent: the least
time for W filled boards in and W winners out (harness.work.hex_winner_bytes)
at the chip's HBM peak, over the mean device time of its calls in the traced
window. The work is the leaf evaluation's, whatever implements it."""

from harness import peaks, trace as tr, work


def read(ctx):
    cfg = ctx["config"]
    moved = work.hex_winner_bytes(cfg["n_workers"], cfg["board_size"] ** 2)
    return work.roofline_pct(
        moved, tr.mean_call_s(ctx["trace"]["devices"], "hex_winner", ctx["t0"],
                              ctx["t1"]),
        peaks.peaks(ctx["device_kind"])["hbm_bytes_s"])
