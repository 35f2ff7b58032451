"""Host time of the window-max join per close due in the window: the
ledger's `join.*` leaves (`operators/joins.py`: buffer, prep, probe, take,
and emit by its self time)."""

import ledger_window


def read(run):
    return ledger_window.per_close_ms(
        run, ledger_window.family_seconds(run, "join.", "join.emit"))
