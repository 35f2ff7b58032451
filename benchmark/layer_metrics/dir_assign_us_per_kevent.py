"""Host time of the slot directory per thousand events accepted in the
window: the ledger's `dir.assign` (the (bin, key) -> slot call of every
batch, `operators/windows.py` `_scatter`)."""

import ledger_window


def read(run):
    return ledger_window.us_per_kevent(
        run, ledger_window.seconds(run, "dir.assign"))
