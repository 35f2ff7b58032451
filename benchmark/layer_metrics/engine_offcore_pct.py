"""The share of the window in which the loop's thread had work and was not
on a core: 100 x (`loop.run` total seconds - its CPU seconds) / `window_s`.
`loop.run` is the wall between two ticks of the accounting pump less the
idle booked in between, with the loop thread's own `thread_time`
(`obs/timeline.py` `loop_tick`); thread CPU time does not count a wait for
the GIL (the benchmark's producer thread shares the process), nor for the
OS, nor a blocking wait for the device inside a leaf of
`timeline.DEVICE_WAITS`: all three are in here, and
`host_leaf_offcore_pct` tells the first two from the third. None where the
program books no `loop.run`. Entered with `loop_idle_pct.py`."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or "loop.run" not in t or not run.window_s:
        return None
    run_ = t["loop.run"]
    return 100.0 * (run_["total_s"] - run_["cpu_s"]) / run.window_s
