"""The longest `checkpoint.capture` span that began inside the window."""


def read(run):
    lo, hi = run.start["t_ns"] / 1e3, run.end["t_ns"] / 1e3
    durs = [s["dur"] / 1e3 for s in run.spans if lo <= s["ts"] <= hi]
    return max(durs) if durs else None
