"""Device time of one `agg.update` call, from the profiler trace: the
seconds of the `jit_update` modules over their calls. (Its share of the
memory roofline needs the rows of each call, which neither the trace nor
the program's counters give today: see PERF.md, Open questions.)"""


def read(run):
    if run.trace is None:
        return None
    m = run.trace.modules.get("agg.update")
    if not m or not m["calls"]:
        return None
    return 1e6 * m["seconds"] / m["calls"]
