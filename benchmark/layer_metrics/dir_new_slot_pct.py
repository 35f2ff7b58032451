"""Share of the rows handed to a slot directory in the window that opened
a new slot, in the window operator that opened the most: the ledger's
`dir.new` count (slots the call created) over `dir.assign`'s (rows), both
booked by `operators/windows.py` `_scatter`. Near 100 where nearly every
event is a new (bin, key); a few per cent under a hot key."""

import ledger_tasks


def read(run):
    t = ledger_tasks.of_largest(run, "dir.new")
    if not t or not t.get("dir.assign", {}).get("n"):
        return None
    return 100.0 * t["dir.new"]["n"] / t["dir.assign"]["n"]
