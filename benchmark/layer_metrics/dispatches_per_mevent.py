"""Device program calls (all programs, compiles' first calls included) per
million events accepted in the window."""

import window_counts


def read(run):
    calls = (sum(window_counts.program_delta(run, "dispatches").values())
             + window_counts.compiles_in_window(run))
    return calls / (run.events_in_window / 1e6)
