"""What the ledger still cannot name: 100 x (1 - named seconds / window),
where named seconds are the self times of every phase but the enclosing
ones (`process`, `watermark`: their self time is exactly the unnamed part
of a batch or a close) and the waits (`timeline.ENCLOSING`, `WAITS`). The
engine runs on one thread, so self times add up to at most the window; the
rest is the runner's loop, the source and sink, the controller and
whatever else runs outside a phase."""

import ledger_window


def read(run):
    t = ledger_window.totals(run)
    if not t or not run.window_s:
        return None
    from arroyo_tpu.obs import timeline

    skip = set(timeline.ENCLOSING) | set(timeline.WAITS)
    named = sum(v["self_s"] for p, v in t.items() if p not in skip)
    return 100.0 * (1.0 - named / run.window_s)
