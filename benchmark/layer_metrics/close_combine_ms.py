"""`close.combine` per close due in the window: gather program, the
device-to-host read of the closing slots (which waits for every program
queued before it), the host combine per key and the reset."""

import ledger_window


def read(run):
    return ledger_window.per_close_ms(
        run, ledger_window.seconds(run, "close.combine"))
