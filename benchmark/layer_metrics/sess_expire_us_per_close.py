"""Host time of closing one session: the total seconds of the ledger's
`sess.expire` (one entry per watermark that closes any: the expired rows
found by one array expression, the gather, the read and the reset of their
slots, the output batch, and `emit` with what runs downstream inside it:
`operators/windows.py` `SessionWindowOperator.handle_watermark`) over its
`n`, the sessions closed, in microseconds. Read per task, in the operator
that closed the most. None where no watermark in the window closed any."""

import ledger_tasks


def read(run):
    t = (ledger_tasks.of_largest(run, "sess.expire") or {}).get("sess.expire")
    if not t or not t["n"]:
        return None
    return 1e6 * t["total_s"] / t["n"]
