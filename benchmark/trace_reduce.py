"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

`reduce(path)` reads the file with `jax.profiler.ProfileData` and nothing
else: the device planes' operation line gives each device's busy time (the
union of the intervals in which an operation ran) and the time per
program; the host plane gives the benchmark's own annotations, by which a
device's longest idle gaps are attributed. `python trace_reduce.py --dump
<file>` prints the planes, lines and most frequent names of a trace: look
at one by hand before trusting a name.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The program's jitted functions as the trace's module names show them,
# mapped to the program names of obs/device.py. Only names that one look
# at a real trace showed to be unambiguous are here (see PERF.md).
MODULE_NAMES: Dict[str, str] = {
    "jit_update": "agg.update",     # ops/aggregates.py, the only jitted `update`
    "jit_reset": "agg.reset",       # ops/aggregates.py
    "jit_phase1": "join.phase1",    # ops/device_join.py
    "jit_impl": "join.phase2",      # ops/device_join.py phase2_at's `impl`
}
WAIT_BEGIN = "bench.source.wait.begin"
WAIT_END = "bench.source.wait.end"
SINK = "bench.sink"
PYTHON_LINE = "python"   # the host thread that runs the engine's loop


class NoDevicePlane(ValueError):
    """The trace holds no accelerator's operation line."""


def union_ns(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total length of the union of [start, end) intervals, and the merged
    intervals in order."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def module_program(name: str) -> str:
    """`jit_update(123456789)` -> the mapped program name, or the module's
    own name without its fingerprint."""
    base = re.sub(r"\(\d+\)$", "", name)
    return MODULE_NAMES.get(base, base)


@dataclasses.dataclass
class Summary:
    window_s: float                   # the traced slice, by the trace's events
    busy_s: float                     # mean over the device planes
    busy_by_device: Dict[str, float]
    modules: Dict[str, dict]          # program -> {"seconds", "calls"}
    gaps: List[Tuple[str, float]]     # longest idle gaps by what the host did

    def idle_pct(self) -> float:
        """1 - busy / traced window, in %."""
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        """The programs that took most device time (seconds per device in
        the slice) and the longest idle gaps."""
        top = sorted(((n, m["seconds"]) for n, m in self.modules.items()),
                     key=lambda x: -x[1])
        return {"device_ops": [[n, s] for n, s in top[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield ev.name, start, start + int(ev.duration_ns)


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[int, int]]] = {}
    modules: Dict[str, dict] = {}
    host_marks: List[Tuple[str, int, int]] = []
    host_calls: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv = device_ops.setdefault(plane.name, [])
                    iv.extend((s, e) for _name, s, e in _events(line))
                elif line.name == MODULES_LINE:
                    for name, s, e in _events(line):
                        m = modules.setdefault(
                            module_program(name), {"seconds": 0.0, "calls": 0})
                        m["seconds"] += (e - s) / 1e9
                        m["calls"] += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name in (WAIT_BEGIN, WAIT_END, SINK):
                        host_marks.append((name, s, e))
                    elif line.name == PYTHON_LINE:
                        host_calls.append((name, s, e))
    if not device_ops:
        raise NoDevicePlane(
            f"{path}: no device plane with a {OPS_LINE!r} line")
    length_ns = slice_length_ns(data)
    busy: Dict[str, float] = {}
    merged_by_device = {}
    for name, iv in device_ops.items():
        total, merged = union_ns(iv)
        busy[name] = total / 1e9
        merged_by_device[name] = merged
    # per-module times are summed over devices: divide by the device count
    # so that a program's seconds read per device
    n_dev = len(device_ops)
    for m in modules.values():
        m["seconds"] /= n_dev
        m["calls"] //= n_dev
    first = sorted(merged_by_device)[0]
    gaps = attribute_gaps(
        [(0, 0)] + merged_by_device[first] + [(length_ns, length_ns)],
        host_marks, host_calls)
    return Summary(window_s=length_ns / 1e9,
                   busy_s=sum(busy.values()) / n_dev, busy_by_device=busy,
                   modules=modules, gaps=gaps)


def slice_length_ns(data) -> int:
    """The traced slice's length: the profiler's own start and stop times
    (plane `Task Environment`). Event times count from the start. A device
    that ran nothing for seconds leaves no event there, so the span of the
    device's events is NOT the slice."""
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            return int(stats["profile_stop_time"] - stats["profile_start_time"])
    raise ValueError("the trace has no `Task Environment` plane")


def attribute_gaps(merged, host_marks, host_calls=(), keep: int = 10):
    """The longest idle gaps of one device, each named for what the
    benchmark saw the host doing in it: `bench.source.wait` (a paced source
    sleeping until its next batch is due), `bench.sink` (the sink keeping a
    result), else `engine` (everything inside the program). An engine gap
    spent mostly inside one runtime call that the profiler names on the
    engine's thread (a device-to-host read, say) carries that name."""
    waits = []
    begin = None
    for name, s, _e in sorted(host_marks, key=lambda m: m[1]):
        if name == WAIT_BEGIN:
            begin = s
        elif name == WAIT_END and begin is not None:
            waits.append((begin, s))
            begin = None
    sinks = [(s, e) for name, s, e in host_marks if name == SINK]

    def overlap(gap, spans):
        return sum(max(0, min(gap[1], e) - max(gap[0], s)) for s, e in spans)

    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g in gaps[:keep]:
        length = g[1] - g[0]
        if overlap(g, waits) * 2 > length:
            who = "bench.source.wait"
        elif overlap(g, sinks) * 2 > length:
            who = SINK
        else:
            who = "engine"
            inside: Dict[str, int] = {}
            for name, s, e in host_calls:
                o = max(0, min(g[1], e) - max(g[0], s))
                if o:
                    inside[name] = max(inside.get(name, 0), o)
            if inside:
                name, o = max(inside.items(), key=lambda x: x[1])
                if o * 2 > length:
                    who = f"engine:{name}"
        out.append((who, length / 1e9))
    return out


def dump(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names: Dict[str, list] = {}
            n = 0
            for name, s, e in _events(line):
                n += 1
                c = names.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += e - s
            print(f"  line {line.name!r}: {n} events, {len(names)} names")
            for name, (count, ns) in sorted(
                    names.items(), key=lambda x: -x[1][1])[:top]:
                print(f"    {count:8d} {ns / 1e6:12.3f} ms  {name[:120]}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        print(__doc__)
        sys.exit(2)
