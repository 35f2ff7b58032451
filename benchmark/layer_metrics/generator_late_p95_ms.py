"""How late the paced generator's batches left, against their schedule:
a starved generator must not be read as a fast system."""

import delay


def read(run):
    p = delay.percentile(run.feed.late_s, 0.95)
    return None if p is None else p * 1e3
