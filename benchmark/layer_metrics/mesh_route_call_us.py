"""Device time of one exchange step, from the profiler trace: the seconds
of the mesh update programs' modules (`jit_mesh_route`, or whichever step
the exchange setting chose) over their calls, per device. The keyed
operator's steps and the salted one's are the same program name and are
read together. None where the trace has no module of these names (a
program whose steps are all called `jit_step` cannot be told apart)."""

STEPS = ("jit_mesh_route", "jit_mesh_step", "jit_mesh_step_direct")


def read(run):
    modules = getattr(getattr(run, "trace", None), "modules", None) or {}
    mine = [modules[name] for name in STEPS if name in modules]
    calls = sum(m.get("calls", 0) for m in mine)
    if not calls:
        return None
    return 1e6 * sum(m.get("seconds", 0.0) for m in mine) / calls
