"""CPU the loop's thread spent where no phase is, as a share of the window:
100 x (`loop.run` CPU seconds - the self CPU seconds of every named phase)
/ `window_s`; named as in `engine_unnamed_pct` (every phase outside
`timeline.ENCLOSING` and `WAITS`), so the enclosures' own self CPU, the
runner's and the operators' un-named work, is in here with asyncio's
machinery, the controller and the benchmark's source. `self_cpu_s` holds
the loop thread's CPU alone, so a phase on a worker thread does not enter
the subtraction. What `engine_unnamed_pct` reads is this, plus
`loop_idle_pct`, plus the part of `engine_offcore_pct` outside named
phases. None where the program books no `loop.run`. Entered with
`loop_idle_pct.py`."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or "loop.run" not in t or not run.window_s:
        return None
    from arroyo_tpu.obs import timeline

    skip = set(timeline.ENCLOSING) | set(timeline.WAITS)
    named = sum(v["self_cpu_s"] for p, v in t.items() if p not in skip)
    return 100.0 * (t["loop.run"]["cpu_s"] - named) / run.window_s
