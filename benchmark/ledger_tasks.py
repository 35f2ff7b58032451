"""The program's phase ledger over the timed window, one task at a time:
for a count that only one operator's entries carry meaningfully. q7 runs
two window operators over the same bids, the count per (auction, price,
bidder) and the window's max price under no key at all; summed, the second
would halve every share and every mean of the first.

`ledger_window.totals` sums all tasks; `timeline.totals(task=)` answers for
one, and the run knows its tasks from the program's per-task row counters
(`run.flow`). A program without the ledger, and a phase that no task
booked, give None.
"""


def of_largest(run, phase):
    """{phase: {count, total_s, self_s, max_s, n, padded}} over the window
    for the one task whose `phase` entries carry the largest `n`; None
    where no task booked it."""
    try:
        from arroyo_tpu.obs.timeline import totals
    except ImportError:
        return None
    best = None
    for task in getattr(run, "flow", None) or ():
        t = totals(run.start["t_ns"] / 1e3, run.end["t_ns"] / 1e3, task=task)
        if phase in t and (best is None or t[phase]["n"] > best[phase]["n"]):
            best = t
    return best
