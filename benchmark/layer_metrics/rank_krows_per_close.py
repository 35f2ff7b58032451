"""Thousands of rows the ranking operator orders at one close: the
ledger's `rank.sort` count (`n` = rows of the bin it ranked,
`operators/window_fn.py`) per bin ranked in the window. What survives the
filter behind it (`row_num <= 5`) is the sink's to count, not this
operator's. A program that books no `rank.sort` gives None."""

import ledger_window


def read(run):
    t = (ledger_window.totals(run) or {}).get("rank.sort")
    if not t or not t["count"]:
        return None
    return t["n"] / t["count"] / 1e3
