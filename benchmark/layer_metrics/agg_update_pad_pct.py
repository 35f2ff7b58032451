"""Share of the rows shipped to `agg.update` in the window that were
padding: 1 - real rows / padded rows of the ledger's `agg.enqueue`
entries."""

import ledger_window


def read(run):
    t = (ledger_window.totals(run) or {}).get("agg.enqueue")
    if not t or not t["padded"]:
        return None
    return 100.0 * (1.0 - t["n"] / t["padded"])
