"""Thousands of sessions open when a watermark that closes any arrives:
`padded` of the ledger's `sess.expire` (the open sessions at that instant)
over its count, in the operator that closed the most. It says whether the
state is at the configuration's size: at 100,000 events/s of event time
2,000 bidders a second stay open ~0.5 s, the gap and the watermark's
delay, ~21 thousand. None where no watermark in the window closed any."""

import ledger_tasks


def read(run):
    t = (ledger_tasks.of_largest(run, "sess.expire") or {}).get("sess.expire")
    if not t or not t["count"]:
        return None
    return t["padded"] / t["count"] / 1e3
