"""The peaks table and the kernels' work functions."""

import pytest

from harness import peaks, work


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flop_s"] == 197e12 and p["hbm_bytes_s"] == 819e9


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_kernel_bytes_at_paper_width():
    # 244 lanes x 121 children x 5 float32 tiles, plus per-lane total and pick
    assert work.uct_select_bytes(244, 121) == 244 * 121 * 20 + 244 * 8
    # 244 filled 11x11 boards in, 244 winners out, a byte each
    assert work.hex_winner_bytes(244, 121) == 244 * 122


def test_roofline_share():
    # 819 bytes at 819 GB/s take 1 ns; measured 2 ns -> 50%
    assert work.roofline_pct(819, 2e-9, 819e9) == pytest.approx(50.0)
    assert work.roofline_pct(819, 0.0, 819e9) is None
