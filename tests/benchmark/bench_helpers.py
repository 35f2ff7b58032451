"""Shared by the benchmark's tests: run `benchmark/run.py` as the driver
does (a process of its own) and read its result line."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(REPO, "benchmark", "run.py")


def two_cores():
    """Keep a run's many runtime threads on two cores and behind the
    others: tier-1's other workers run timing-sensitive tests beside it."""
    cpus = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, set(cpus[-2:]))
    os.nice(10)


def run_cell(*argv, script=RUN, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, script, *argv], env={**os.environ, **env}, cwd=cwd,
        capture_output=True, text=True, timeout=600, preexec_fn=two_cores)


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
