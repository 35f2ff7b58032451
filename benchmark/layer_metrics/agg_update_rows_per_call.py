"""Real rows per `agg.update` call in the window: the rows that
`InstrumentedJit` booked on the ledger's `agg.enqueue` entries over their
count (all accumulators of the job together)."""

import ledger_window


def read(run):
    t = (ledger_window.totals(run) or {}).get("agg.enqueue")
    if not t or not t["count"] or not t["padded"]:
        return None
    return t["n"] / t["count"]
