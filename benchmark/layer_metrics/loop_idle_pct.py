"""The share of the window in which the engine's event loop had nothing to
run: 100 x the ledger's `loop.idle` total seconds / `window_s`. The loop's
selector books every `select` that may block (`obs/timeline.py`
`TimedSelector`): a source that found the feed empty and slept, every task
waiting on a queue or a timer at once. With `engine_offcore_pct` and
`engine_unnamed_cpu_pct` it splits what `engine_unnamed_pct` adds up.
None where the program books no `loop.run` (a parent of ISSUE 38).

Its entry stands in BENCHMARK.json since ISSUE 40 (all four cells), word
for word as `tests/benchmark/data/owed_entries.json` held it."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or "loop.run" not in t or not run.window_s:
        return None
    return 100.0 * t.get("loop.idle", {"total_s": 0.0})["total_s"] / run.window_s
