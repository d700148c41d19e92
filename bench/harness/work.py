"""The bytes a kernel call must move, from its shapes alone.

They describe the work the search asks of the call, not how a kernel does
it, so a share of the roofline reads the same whatever implements it.
"""

from __future__ import annotations

F32 = 4
I32 = 4
I8 = 1


def uct_select_bytes(lanes: int, children: int) -> int:
    """One descent level: per lane and child slot the child's wins, visits,
    virtual loss, validity and tie-break noise in (float32 each); per lane
    the parent's visit total in and one child index out."""
    return lanes * children * 5 * F32 + lanes * (F32 + I32)


def hex_winner_bytes(lanes: int, cells: int) -> int:
    """One leaf evaluation: W filled boards in (a byte per cell), W winners
    out (a byte each)."""
    return lanes * cells * I8 + lanes * I8


def roofline_pct(bytes_moved: float, seconds: float, hbm_bytes_s: float):
    """Least time for the bytes at the HBM peak over the measured time, in
    percent; None without a measured time."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * (bytes_moved / hbm_bytes_s) / seconds
