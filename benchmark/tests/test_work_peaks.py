"""The scan's needed bytes on hand-computed shapes; the peaks table."""

import pytest

from harness import peaks, work


def test_scan_bytes_by_hand():
    # 10 live rows of tier 64 and 2 of tier 2048, a 225-word pack, 3 launches
    # rows: 10 * (64 + 900) + 2 * (2048 + 900) = 9640 + 5896 = 15536
    # table: 3 * 256 * 225 * 4 = 691200
    assert work.scan_bytes({64: 10, 2048: 2}, 225, 3) == 15536 + 691200
    assert work.scan_bytes({}, 1, 0) == 0
    with pytest.raises(ValueError):
        work.scan_bytes({64: 1}, 0, 1)


def test_padding_is_not_counted():
    # a tier's padded rows never reach the function: only live rows do
    assert (work.scan_bytes({256: 1}, 141, 1)
            == 256 + 141 * 4 + 256 * 141 * 4)


def test_v5e_peak_and_unknown_device():
    assert peaks.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.hbm_bytes_per_s("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.hbm_bytes_per_s("cpu")
