"""The arithmetic behind the end-to-end metrics, on hand-made inputs."""

import numpy as np
import pytest

from benchmark import peaks, stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10], 95, 10.0),
    ([0, 100], 95, 95.0),
    (list(range(1, 101)), 95, 95.05),
    ([5, 1, 3], 0, 1.0),
    ([5, 1, 3], 100, 5.0),
])
def test_percentile_is_exact(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_whole_step_rate_counts_only_steps_completed_in_the_window():
    # steps complete at 1.0, 2.0, 3.1 s after a window that starts at t=10 and lasts 3 s
    rate, n, elapsed = stats.whole_step_rate([11.0, 12.0, 13.1], [100, 100, 100], 10.0, 3.0)
    assert (n, elapsed) == (2, 2.0)
    assert rate == pytest.approx(100.0)  # 200 tokens over the 2.0 s the two steps took


def test_whole_step_rate_does_not_quantise_by_a_step():
    # a fixed 3 s denominator would read 66.7; whole steps over their own time read 100
    rate, _, _ = stats.whole_step_rate([11.0, 12.0, 13.1], [100] * 3, 10.0, 3.0)
    assert rate != pytest.approx(200 / 3.0)


def test_whole_step_rate_needs_a_completed_step():
    with pytest.raises(ValueError):
        stats.whole_step_rate([14.0], [1], 10.0, 3.0)


def test_iqr_share_follows_statistics_quantiles():
    vals = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4) exclusive method: q1 = 100.75, q3 = 104.25
    assert stats.iqr_share(vals) == pytest.approx(3.5 / 102.5)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks on record"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_v5e_peaks_and_roofline_bound():
    p = peaks.peaks("TPU v5 lite")
    assert (p["flops_bf16"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    t, bound = peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.roofline_seconds(1.0, 819e9 * 2, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(2.0), "memory")
