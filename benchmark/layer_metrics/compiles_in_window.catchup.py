"""Shape signatures first seen inside the timed window (should be 0)."""

import window_counts


def read(run):
    return window_counts.compiles_in_window(run)
