"""Result delay of a paced run: per window close, sink arrival minus the
wall time at which (window end + watermark delay) was due on the schedule.
Window length and the configured watermark delay are excluded; queue wait
and the generator's lateness are included. A close that never arrived has
no sample here; it counts in `failed`."""

from __future__ import annotations

import math


def percentile(samples, q: float):
    """Nearest-rank percentile: the smallest sample with at least q of the
    samples at or below it. None without samples."""
    if not samples:
        return None
    s = sorted(samples)
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def delays_ms(run):
    return [c["delay_ms"] for c in run.closes if "delay_ms" in c]
