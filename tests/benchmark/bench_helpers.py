"""Shared by the benchmark's tests: run `benchmark/run.py` as the driver
does (a process of its own) and read its result line."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(REPO, "benchmark", "run.py")


# Keep a run's many runtime threads on two cores and behind the others:
# tier-1's other workers run timing-sensitive tests beside it. The child
# sets both on itself and then execs the run, so every thread the run
# starts inherits them from its first instruction on. Not a `preexec_fn`:
# that runs Python between fork and exec, and in a worker that holds jax's
# threads such a child once never reached its exec (the test sat in `Popen`
# until the watchdog). Not from outside after the spawn either: threads
# started before the parent's call would miss the mask.
TWO_CORES = (
    "import os, sys; "
    "os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[-2:]); "
    "os.nice(10); "
    "os.execv(sys.executable, [sys.executable] + sys.argv[1:])")


def listed(bench: dict, cell: str, group: str) -> set:
    """The names of the `group` metrics that `cell` reports: those that
    list it and those with no `workloads` key. Tests hold a result line
    to this and to the names they know, never to an exact set or a last
    place: a later PR appends entries and may edit no test."""
    return {m["name"] for m in bench[group]
            if cell in m.get("workloads", [cell])}


def run_cell(*argv, script=RUN, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, "-c", TWO_CORES, script, *argv],
        env={**os.environ, **env}, cwd=cwd, capture_output=True, text=True,
        timeout=600)


def rehearse(cell, *argv, seed=7, seconds=4, trace=0, script=RUN):
    """`cell` is the first word of the command line: a cell's name, or what
    a script other than run.py takes there."""
    argv = (cell, *argv) if script != RUN else ("--workload", cell, *argv)
    out = run_cell(*argv, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace), "--rehearsal", script=script)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    said = [ln for ln in lines if ln.startswith("[")]
    assert said and all(
        ln.startswith("[platform=cpu, rehearsal]") for ln in said)
    return json.loads(lines[-1]), said
