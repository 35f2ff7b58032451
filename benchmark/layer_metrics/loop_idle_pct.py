"""The share of the window in which the engine's event loop had nothing to
run: 100 x the ledger's `loop.idle` total seconds / `window_s`. The loop's
selector books every `select` that may block (`obs/timeline.py`
`TimedSelector`): a source that found the feed empty and slept, every task
waiting on a queue or a timer at once. With `engine_offcore_pct` and
`engine_unnamed_cpu_pct` it splits what `engine_unnamed_pct` adds up.
None where the program books no `loop.run` (a parent of ISSUE 38).

No entry in BENCHMARK.json yet (`tests/benchmark/data/owed_entries.json`
holds it word for word): `tests/benchmark/test_bench_ledger_metrics.py`
holds the entries of source `program_span` to its eight; a builder reads it
through `--benchmark-file`."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or "loop.run" not in t or not run.window_s:
        return None
    return 100.0 * t.get("loop.idle", {"total_s": 0.0})["total_s"] / run.window_s
