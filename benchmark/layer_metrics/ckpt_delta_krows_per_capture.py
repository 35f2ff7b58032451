"""Thousands of rows in one capture's incremental delta of window state,
in the window operator whose deltas carry the most: the ledger's
`ckpt.delta` count (the dirty slots `_build_delta_batch` hands a barrier,
`operators/windows.py`) per delta built in the window. What a capture's
seconds are to be set against."""

import ledger_tasks


def read(run):
    t = (ledger_tasks.of_largest(run, "ckpt.delta") or {}).get("ckpt.delta")
    if not t or not t["count"]:
        return None
    return t["n"] / t["count"] / 1e3
