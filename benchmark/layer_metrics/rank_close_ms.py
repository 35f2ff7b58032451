"""Host time of the ranking operator's closes per close due in the window:
the ledger's `rank.sort` (table build, `lexsort`, rank) and `rank.build`
(the output batch with its rank column) by their total seconds, and
`rank.emit` by its self time, because the operators downstream and the out
queue's wait run inside it (`operators/window_fn.py`
`WindowFunctionOperator.handle_watermark`). `rank.buffer` is per batch and
is not a close's. A program that books none of them gives None.

Its entry stands in BENCHMARK.json since ISSUE 40 (unit ms, layer "window
functions", moves `events_per_s`, cell `top5-hop60.catchup`)."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t:
        return None
    found = [t[p]["self_s" if p == "rank.emit" else "total_s"]
             for p in ("rank.sort", "rank.build", "rank.emit") if p in t]
    return ledger_window.per_close_ms(run, sum(found) if found else None)
