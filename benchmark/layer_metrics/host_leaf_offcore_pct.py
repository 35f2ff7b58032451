"""Of the self seconds of the leaves that never block, the share their
thread spent off a core: 100 x sum(self s - self CPU s) / sum(self s) over
every ledger phase outside `timeline.ENCLOSING`, `WAITS` and
`DEVICE_WAITS`. Such a leaf (`win.keys`, `dir.assign`, `agg.pack`,
`audit.attest`, ...) is plain host code, so its wall less its CPU is a wait
for the GIL or for the OS and nothing else: the number PERF.md's "waits
behind the producer's GIL" was an inference for. None where the program
books no `loop.run`. Entered with `loop_idle_pct.py`."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or "loop.run" not in t:
        return None
    from arroyo_tpu.obs import timeline

    skip = set(timeline.ENCLOSING) | set(timeline.WAITS) | set(
        timeline.DEVICE_WAITS)
    wall = sum(v["self_s"] for p, v in t.items() if p not in skip)
    cpu = sum(v["self_cpu_s"] for p, v in t.items() if p not in skip)
    return 100.0 * (wall - cpu) / wall if wall else None
