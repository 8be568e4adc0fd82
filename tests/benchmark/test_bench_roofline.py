"""The cascade's least bytes and the peak table."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import roofline  # noqa: E402

CASES = [(9_500_000, [4_000_000, 3_000_000, 10], False),
         (9_500_000, [4_000_000, 3_000_000, 10], True),
         (1, [1], False), (2**25, [2**25] * 16, True)]


@pytest.mark.parametrize("n_emissions,rows,weighted", CASES)
@pytest.mark.parametrize("slower", [1.0, 1.0001, 3.0, 1e6])
def test_share_at_most_100_when_time_is_at_least_bytes_over_peak(
        n_emissions, rows, weighted, slower):
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    n_bytes = roofline.least_bytes(n_emissions, rows, weighted)
    share = roofline.roofline_pct(n_bytes, slower * n_bytes / peak, peak)
    assert 0 < share <= 100.0 * (1 + 1e-12)


def test_least_bytes_counts_inputs_once_and_rows_once():
    assert roofline.least_bytes(10, [3, 2], False) == 10 * 17 + 5 * 12
    assert roofline.least_bytes(10, [3, 2], True) == 10 * 25 + 5 * 16


def test_no_time_no_share():
    assert roofline.roofline_pct(1000, 0.0, 819e9) is None


def test_peaks_of_v5e_and_unknown_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
