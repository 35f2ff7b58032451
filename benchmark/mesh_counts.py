"""The mesh exchange's counters (`MESH_STATS` of the program, snapshot
whole at the window's edges) over the timed window, for the readers of
layer "mesh exchange"."""


def delta(run, key):
    """How much counter `key` grew inside the window; None where the
    program does not count it (a parent commit of the PR that added the
    key) or the run has no window."""
    start = (getattr(run, "start", None) or {}).get("mesh") or {}
    end = (getattr(run, "end", None) or {}).get("mesh") or {}
    if key not in end:
        return None
    return end[key] - start.get(key, 0)
