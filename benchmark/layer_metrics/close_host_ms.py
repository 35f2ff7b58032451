"""Host time of the window operators' closes per close due in the window:
every `close.*` leaf of the ledger (`operators/windows.py`: take, union,
combine, finalize, reset, build, and emit by its self time), all window
operators of the job together."""

import ledger_window


def read(run):
    return ledger_window.per_close_ms(
        run, ledger_window.family_seconds(run, "close.", "close.emit"))
