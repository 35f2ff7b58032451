"""The program's own phase ledger (`arroyo_tpu/obs/timeline.py`) over the
timed window, for the per-layer readers of source `program_span`.

The window's edges reach a reader as wall-clock times (`run.start["t_ns"]`,
`run.end["t_ns"]`), the clock the ledger stamps. A program without
`timeline.totals` (a parent commit of the PR that added it) gives None, and
so does a phase that was never booked: the reader then returns None and the
result line leaves the metric out.
"""


def totals(run):
    """{phase: {count, total_s, self_s, max_s, n, padded}} over the window,
    all tasks of the job together; None where the program has no such
    ledger."""
    try:
        from arroyo_tpu.obs.timeline import totals as ledger_totals
    except ImportError:
        return None
    return ledger_totals(run.start["t_ns"] / 1e3, run.end["t_ns"] / 1e3)


def seconds(run, *phases):
    """The summed total seconds of the named phases; None if none was
    booked."""
    t = totals(run)
    if not t:
        return None
    found = [t[p]["total_s"] for p in phases if p in t]
    return sum(found) if found else None


def family_seconds(run, prefix, emit):
    """Host seconds of one operator's close: the total seconds of its
    leaves `<prefix>*`, except the leaf `emit`, which counts by its self
    time: the operators downstream and the out queue's wait run inside it
    and are not this operator's work. Sub-steps booked inside a leaf
    (`agg.read` inside `close.combine`) are part of the leaf's total."""
    t = totals(run)
    if not t:
        return None
    # one dot: `join.probe.count` is a sub-step inside `join.probe`
    found = [v["self_s"] if p == emit else v["total_s"]
             for p, v in t.items()
             if p.startswith(prefix) and p.count(".") == 1]
    return sum(found) if found else None


def per_close_ms(run, secs):
    if secs is None or not run.closes:
        return None
    return 1e3 * secs / len(run.closes)


def us_per_kevent(run, secs):
    if secs is None or not run.events_in_window:
        return None
    return 1e6 * secs / (run.events_in_window / 1e3)
