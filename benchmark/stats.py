"""Arithmetic of the end-to-end metrics: exact percentiles, whole-step rates,
quartile spread. Kept with the benchmark so that no change to the program's
own meters (tpukit/obs) can move a metric."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Exact q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule), in plain Python."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def whole_step_rate(done_times, units_per_step, window_start: float, seconds: float):
    """Rate over the steps COMPLETED inside `[window_start, window_start +
    seconds]`: their units over (completion of the last of them - window
    start). `done_times[i]` is the host clock at which step i was known
    complete; `units_per_step[i]` its real tokens. Returns (rate, steps
    counted, elapsed). Whole steps over their own time: with tens of steps to
    a window, a fixed denominator would quantise the rate by one step."""
    end = window_start + seconds
    n = sum(1 for t in done_times if t <= end)
    if n == 0:
        raise ValueError("no step completed inside the window")
    elapsed = done_times[n - 1] - window_start
    return sum(units_per_step[:n]) / elapsed, n, elapsed


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver reckons a spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
