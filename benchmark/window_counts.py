"""Differences of the program's counters over the timed window."""


def program_delta(run, field: str) -> dict:
    """{program: count of `field` inside the window}."""
    out = {}
    for name, after in run.end["programs"].items():
        before = run.start["programs"].get(name, {})
        d = after.get(field, 0) - before.get(field, 0)
        if d:
            out[name] = d
    return out


def compiles_in_window(run):
    """Compiles booked inside the window (a persistent-cache hit is still
    booked: the program counts the first call of a shape signature)."""
    return sum(program_delta(run, "compiles").values())
