"""Thousands of accumulator slots one close of a sliding window merges, in
the window operator that merges the most: the ledger's `close.take` count
(`n` = the slots of the window's live bins, `operators/windows.py`
`_emit_window`) per close in the window. It grows with width / slide: five
bins of q5, thirty of a 60 s hop by 2 s. Read per task, as
`dir_new_slot_pct` is: two window operators over one stream would halve a
mean over both."""

import ledger_tasks


def read(run):
    t = (ledger_tasks.of_largest(run, "close.take") or {}).get("close.take")
    if not t or not t["count"]:
        return None
    return t["n"] / t["count"] / 1e3
