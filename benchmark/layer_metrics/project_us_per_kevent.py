"""Host time of the value operators' projection per thousand events
accepted in the window: the ledger's `project` (an unfused value
operator's program over its batch, `operators/projection.py`: in a
source's chain the projection and predicate over the raw row)."""

import ledger_window


def read(run):
    return ledger_window.us_per_kevent(
        run, ledger_window.seconds(run, "project"))
