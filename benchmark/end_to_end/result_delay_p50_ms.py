"""Median result delay over the closes due in the window (see delay.py)."""

import delay


def read(run):
    return delay.percentile(delay.delays_ms(run), 0.50)
