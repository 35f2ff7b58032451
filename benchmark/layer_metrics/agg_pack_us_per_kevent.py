"""Host time of the accumulator's scatter per thousand events accepted in
the window: `agg.pack` (padding, value columns, host-to-device arrays) plus
`agg.enqueue` (the call of the jitted update: the enqueue, not the run on
the device), `ops/aggregates.py` `Accumulator.update`."""

import ledger_window


def read(run):
    return ledger_window.us_per_kevent(
        run, ledger_window.seconds(run, "agg.pack", "agg.enqueue"))
