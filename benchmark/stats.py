"""The arithmetic of the end-to-end metrics, kept with the benchmark."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` percent
    of the sample at or below it. A missing sample (``None`` or ``inf``: a
    failed or refused request) counts as worse than any latency."""
    xs = sorted(math.inf if v is None else float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def whole_step_rate(done_s, t0_s: float, units_per_step: float, deadline_s: float):
    """Units per second over whole steps.

    ``done_s`` are the completion times of the timed steps in order, ``t0_s``
    the completion of the last warm-up step. The window closes with the first
    step that completes at or past ``deadline_s``: that step counts whole,
    and the rate divides by the time that really elapsed up to it, never by
    the nominal window. Returns (rate, steps, elapsed)."""
    n = 0
    for t in done_s:
        n += 1
        if t >= deadline_s:
            break
    if n == 0:
        raise ValueError("no timed step completed")
    elapsed = done_s[n - 1] - t0_s
    return n * units_per_step / elapsed, n, elapsed


def spread(values) -> float:
    """Distance between the first and third quartile over the median, as the
    driver reckons it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
