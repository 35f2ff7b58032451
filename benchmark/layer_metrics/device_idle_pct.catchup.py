"""1 - (union of device-operation intervals / traced window), mean over the
devices used, from the profiler trace of the whole window."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct()
