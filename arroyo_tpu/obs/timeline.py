"""Always-on phase ledger (ISSUE 11, regrained in ISSUE 24): the one way
host work gets a name.

`phase(name, ...)` is a context manager around a piece of host work: a
`perf_counter` pair and two readings of the thread's CPU clock, a contextvar
frame for nesting, a ring append and a bucket update on exit (3.3 us before
ISSUE 38 and about 3.7 after on the chip's host, plus the CPU clock's
bounded share below: `tools/phase_cost.py`).
`note(name, dur_s, ...)` books the same entry for callers that have a
duration in hand. An entry carries the phase, job and task, `n` (a count of
the work: rows, keys or slots), `key` (what caused it: the window's end for
a close), its self time: the duration less the phases booked inside it, so
a leaf's seconds add up and an enclosing phase's self time is what no leaf
names yet; and beside both the CPU seconds of the thread that ran it
(ISSUE 38). Wall less CPU is the time that thread was not on a core inside
the phase: for a leaf that never blocks that is the wait for the GIL or the
OS, for a leaf of `DEVICE_WAITS` mostly the wait for the device. A phase of
`WAITS` books no CPU on the loop's thread (it runs other tasks inside a
`queue.wait`); a phase that awaits one keeps its self CPU its own, its
`cpu_s` holds the others' work too. `flush.resolve`, the one wait that is
a worker thread's work, keeps that thread's CPU.

The CPU clock is a system call (`time.thread_time`). Where it is cheap
(0.35 us a reading in the sandbox) every edge of a phase reads it and every
entry's CPU is its own. On the chip's host a reading costs 6 us and the
clock steps by 10 ms (my chip run, PR 38: read at every edge a phase went
from 3.3 to 14.7 us, 3-4 % of a q5 window), so where it is dear a thread
reads it on a grid of instants of its own, `cpu_every_s()` apart (the cost
of a reading over `_CPU_SHARE`: 4 ms there; jittered by half, so that it
cannot lock onto a workload that repeats), and an edge takes the thread's
CPU as of the last grid instant: the first edge past an instant reads the
clock and reckons back to the newest instant that has passed, at the rate
since the reading before.
The grid does not know the phases, so this is a sampler with no favourite:
CPU seconds still add up exactly (a frame's children and its self CPU
partition its own; `loop.run` holds the true total), a phase longer than
the grid reads true to within a step of it, a shorter one gets a step's CPU
when an instant falls inside it and none when not, so a single entry's CPU
means nothing there and a sum over many entries of one kind (all leaves
that never block; all device waits; an enclosure's self) is right in
expectation: it tells the CPU of stretches a few milliseconds long apart,
not of a 0.4 ms leaf from the 30 us after it.

The event loop's own time (ISSUE 38). `event_loop()` makes the loop of every
`asyncio.run` of `__main__.py`: its selector books each `select` that may
block as `loop.idle`, and the accounting pump (`obs/attribution.py`) calls
`loop_tick()` on the loop's thread, which books `loop.run`: the wall since
the last tick less the idle in between, with that thread's CPU (`n` = the
turns the loop took: its `select` calls). Over any window, from `totals`
alone (within a bucket at each edge):

1. `loop.idle` + `loop.run` = the window;
2. `loop.run` total_s - cpu_s = the loop's thread had work and no core;
3. `loop.run` cpu_s - the self_cpu_s of every phase outside `ENCLOSING` and
   `WAITS` = CPU the loop's thread spent where no phase is (`self_cpu_s`
   holds the loop thread's CPU alone: a phase on a worker thread books its
   `cpu_s` and no self CPU);
4. per task, the self of `process` and `watermark` = what the runner and
   the operators did un-named for that task; `loop.run` less every
   top-level phase = the process's own (asyncio, RPC, the pump).

Two stores, one writer:

* a bounded ring of the newest entries (`obs.timeline_events`), which the
  Perfetto export renders as one track per (job, phase);
* cumulative totals per (phase, job, task) in quarter-second buckets by
  the entry's END on the wall clock, which outlive the ring: `totals(t0_us,
  t1_us)` answers for a whole benchmark window (tens of thousands of
  entries), and `phase_totals` and the bottleneck doctor read the same
  store.

The shared clock with the device: while a profiler session runs (the
profiler's own state, `TraceMe.is_enabled()`; the program is never told),
a `phase` also holds a `jax.profiler.TraceAnnotation(name)`, so the same
interval lands on its thread's line of the profiler's host plane and an
idle gap of the device can be named for the leaf that covers it. Enclosing
phases (`process`, `watermark`), waits and every phase that awaits (`emit`,
`close.emit`, `join.emit`: over a full out queue their interval covers what
the other tasks do meanwhile) pass `annotate=False`: one of them would cover
every gap and say nothing (the first traced chip run read `engine:emit` ten
times). Gated on `obs.timeline_events > 0`.
"""

from __future__ import annotations

import asyncio
import contextvars
import random
import selectors
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..config import config as _config
from .attribution import current_job as _current_job

# ring entries: (ts_us_end, dur_us, phase, job, task, n, key, self_us, cpu_us)
_RING: deque = deque(maxlen=8192)
_LOCK = threading.Lock()

# cumulative store: (bucket id, {(phase, job, task): cell}) oldest first,
# cell = [count, total_us, self_us, max_us, n, padded, cpu_us, self_cpu_us].
# A bucket is the
# quarter second in which its entries ENDED. Bounded by cells, so a fleet
# of many jobs keeps a shorter history than one job (one q5 job books
# ~40 cells a bucket: over five minutes).
BUCKET_US = 250_000
_MAX_CELLS = 65_536
_BUCKETS: deque = deque()
_N_CELLS = 0

# phases that enclose a whole batch, a whole watermark signal or a whole
# stretch of the loop: their SELF time is the host time that no leaf names
ENCLOSING = ("process", "watermark", "loop.run")
# phases that measure waiting, not work on the engine's thread (a full out
# queue, a storage thread, the loop's lag, a `select` with nothing ready):
# left out of a sum of named work. `flush.resolve` is the storage thread's
# own work inside `flush` (ISSUE 39: what a capture staged unresolved, the
# serve view's merge and the blob's packing): seconds beside the loop, so
# a wait to its sums, booked with that thread's CPU
WAITS = ("queue.wait", "flush", "flush.resolve", "loop.lag", "loop.idle")
# leaves that block on the device, on a transfer back or (`compile`) on
# the compiler's own threads: their wall less their CPU is mostly that
# wait, not a wait for a core. Settled by the code and by traced runs of
# the four cells (PERF.md section 5): each reads a result back, while
# `dispatch`, `agg.enqueue` and `agg.gather` only enqueue and stay on a
# core as plain host code does
DEVICE_WAITS = ("join.probe.count", "join.probe.expand", "agg.read",
                "agg.reset", "compile")

# the open phase of the current task or thread (asyncio tasks and
# to_thread calls copy the context, so a frame never crosses runners)
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "arroyo_timeline_open", default=None)
_TRACEME = None  # jax.profiler.TraceAnnotation, once jax is imported
# the thread of the loop whose pump ticks (`loop_tick`); None until it has
_LOOP_THREAD: Optional[int] = None
_get_ident = threading.get_ident
# a reading of the thread CPU clock under `_CPU_CHEAP_S` is taken at every
# edge (`cpu_every_s()` 0); readings of a dearer clock may take `_CPU_SHARE`
# of a thread's time. Per thread: [the next grid instant, the CPU handed to
# edges since the last, the last reading's wall, its CPU]
_CPU_CHEAP_S = 1e-6
_CPU_SHARE = 0.0015
_CPU_EVERY_S: Optional[float] = None
_CPU_AT: Dict[int, list] = {}


def _resize() -> None:
    global _RING
    cap = int(_config().obs.timeline_events)
    if cap > 0 and _RING.maxlen != cap:
        with _LOCK:
            _RING = deque(_RING, maxlen=cap)


def _annotation():
    """`jax.profiler.TraceAnnotation` while a profiler session runs, else
    None. Never imports jax: a process that has not cannot be traced."""
    global _TRACEME
    cls = _TRACEME
    if cls is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        cls = _TRACEME = profiler.TraceAnnotation
    return cls if cls.is_enabled() else None


def cpu_every_s() -> float:
    """The spacing of a thread's grid of CPU readings, from the cost of one
    (the best of five, measured once): 0 where the clock is cheap, else
    what keeps the readings within `_CPU_SHARE` of the thread's time."""
    global _CPU_EVERY_S
    if _CPU_EVERY_S is None:
        cost = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            time.thread_time()
            cost = min(cost, time.perf_counter() - t0)
        _CPU_EVERY_S = 0.0 if cost < _CPU_CHEAP_S else cost / _CPU_SHARE
    return _CPU_EVERY_S


def _thread_cpu(now: float, tid: int) -> float:
    """The thread's CPU seconds as of its last grid instant (module
    docstring); `now` is the `perf_counter()` of the edge."""
    at = _CPU_AT.get(tid)
    if at is None:
        cpu = time.thread_time()
        at = _CPU_AT[tid] = [now, cpu, now, cpu]
    elif now >= at[0]:
        cpu = time.thread_time()
        every = _CPU_EVERY_S if _CPU_EVERY_S is not None else cpu_every_s()
        wall = now - at[2]
        if every > 0 and wall > 0:
            # the newest instant not after this edge: the one that was
            # due or, behind a stretch with no edge in it (a long leaf, an
            # idle thread), one of those that passed unread meanwhile
            due = at[0]
            if now - due > 8 * every:
                due = now - every * random.random()
            nxt = due + (0.5 + random.random()) * every
            while nxt <= now:
                due = nxt
                nxt = due + (0.5 + random.random()) * every
            # back from this reading to that instant, at the rate since
            # the reading before
            at[0], at[1] = nxt, cpu - (cpu - at[3]) * (now - due) / wall
        else:
            at[0], at[1] = now, cpu
        at[2], at[3] = now, cpu
    return at[1]


def thread_cpu(now: float) -> float:
    """`_thread_cpu` for a caller of `note(cpu_s=)`: `now` is the
    `perf_counter()` it has just taken. 0.0 with the ledger off."""
    return _thread_cpu(now, _get_ident()) if _capacity() > 0 else 0.0


def _book(phase_name: str, dur_s: float, child_s: float, job: Optional[str],
          task: str, n: int, key, padded: int, cpu_s: float = 0.0,
          child_cpu_s: float = 0.0) -> None:
    global _N_CELLS
    tid = _get_ident()
    parent = _OPEN.get()
    if parent is not None:
        # the work of one task of one job: a leaf need not be told whose
        parent._child += dur_s
        if parent._tid == tid:
            # a frame copied into a worker thread (`asyncio.to_thread`)
            # keeps its own thread's clock: another thread's CPU is no
            # part of it
            parent._child_cpu += cpu_s
        if not task:
            task = parent.task
        if job is None:
            job = parent.job
    if job is None:
        job = _current_job()
    if phase_name in WAITS and (_LOOP_THREAD is None or tid == _LOOP_THREAD):
        # a wait that awaits (`queue.wait`) hands the CPU of its stretch to
        # its parent's tally above, so that the parent's self CPU stays
        # its own, and books none: the thread ran other tasks' work in it.
        # (A wait booked on a worker thread, `flush.resolve`, is that
        # thread's work and keeps its CPU.)
        cpu_s = child_cpu_s = 0.0
    ts_us = time.time() * 1e6
    dur_us = dur_s * 1e6
    self_us = max(0.0, dur_us - child_s * 1e6)
    cpu_us = cpu_s * 1e6
    # self CPU is the loop thread's alone (identity 3 of the module
    # docstring); before the pump has named that thread, every thread's
    self_cpu_us = max(0.0, cpu_us - child_cpu_s * 1e6) if (
        _LOOP_THREAD is None or tid == _LOOP_THREAD) else 0.0
    _RING.append((ts_us, dur_us, phase_name, job, task, n, key, self_us,
                  cpu_us))
    bucket = int(ts_us) // BUCKET_US
    with _LOCK:
        if not _BUCKETS or _BUCKETS[-1][0] != bucket:
            _BUCKETS.append((bucket, {}))
            while _N_CELLS > _MAX_CELLS and len(_BUCKETS) > 1:
                _N_CELLS -= len(_BUCKETS.popleft()[1])
        cells = _BUCKETS[-1][1]
        cell = cells.get((phase_name, job, task))
        if cell is None:
            cells[(phase_name, job, task)] = [
                1, dur_us, self_us, dur_us, n, padded, cpu_us, self_cpu_us]
            _N_CELLS += 1
        else:
            cell[0] += 1
            cell[1] += dur_us
            cell[2] += self_us
            if dur_us > cell[3]:
                cell[3] = dur_us
            cell[4] += n
            cell[5] += padded
            cell[6] += cpu_us
            cell[7] += self_cpu_us


def _capacity() -> int:
    cap = _config().obs.timeline_events
    if cap > 0 and _RING.maxlen != cap:
        _resize()
    return cap


class phase:
    """`with timeline.phase("close.combine", task=tid, n=slots, key=end):`
    books the block as one ledger entry. `n` and `padded` may be set on the
    object inside the block, when the count is known only then; a device
    program called inside it (`InstrumentedJit`) adds its real and padded
    rows itself. A block that awaits books wall time, the awaited work
    included: a wait gets a phase of its own (`queue.wait`) so that self
    time stays work. `annotate=False` for an enclosing phase, a wait, a
    sub-step of a leaf, and any block that awaits. `elapsed()` inside the
    block is the wall time since it was entered, ledger on or off: for a
    caller whose own counters want the phase's duration."""

    __slots__ = ("name", "task", "job", "n", "key", "padded", "_annotate",
                 "_child", "_child_cpu", "_tid", "_tok", "_ann", "_t0", "_c0")

    def __init__(self, name: str, *, task: str = "",
                 job: Optional[str] = None, n: int = 0, key=None,
                 annotate: bool = True):
        self.name = name
        self.task = task
        self.job = job
        self.n = n
        self.key = key
        self.padded = 0
        self._annotate = annotate
        self._tok = None

    def __enter__(self):
        if _capacity() <= 0:
            self._t0 = time.perf_counter()
            return self
        self._child = self._child_cpu = 0.0
        self._tid = _get_ident()
        self._ann = None
        parent = _OPEN.get()
        if parent is not None:
            # for the phases booked inside this one (`_book` does the same)
            self.task = self.task or parent.task
            if self.job is None:
                self.job = parent.job
        if self._annotate:
            cls = _annotation()
            if cls is not None:
                self._ann = cls(self.name)
                self._ann.__enter__()
        self._tok = _OPEN.set(self)
        self._t0 = now = time.perf_counter()
        self._c0 = _thread_cpu(now, self._tid)
        return self

    def __exit__(self, *exc):
        if self._tok is None:
            return False
        now = time.perf_counter()
        dt = now - self._t0
        cpu = _thread_cpu(now, self._tid) - self._c0
        _OPEN.reset(self._tok)
        self._tok = None
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _book(self.name, dt, self._child, self.job, self.task, self.n,
              self.key, self.padded, cpu, self._child_cpu)
        return False

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0


def open_phase() -> Optional[phase]:
    """The innermost open `phase` of this task or thread, if any: where a
    callee books counts that its caller's phase should carry."""
    return _OPEN.get()


def note(phase: str, dur_s: float, *, job: Optional[str] = None,
         task: str = "", n: int = 0, key=None, padded: int = 0,
         cpu_s: float = 0.0) -> None:
    """Record one phase instant (duration ending now) for a caller that
    has the duration in hand. `job` defaults to the ambient attribution
    context; inside an open `phase` the duration counts as its child.
    `padded`, as on a `phase`: the whole that `n` is the real part of.
    `cpu_s`: the calling thread's CPU seconds inside the duration, where
    the caller took them (two readings of `thread_cpu`; a count and a wait
    book none)."""
    if _capacity() <= 0:
        return
    _book(phase, dur_s, 0.0, job, task, n, key, padded, cpu_s)


class TimedSelector(selectors.DefaultSelector):
    """The event loop's selector with its waiting on the ledger: a `select`
    that may block (`timeout` is not 0) is the loop with nothing to run,
    booked as `loop.idle` (`n` = the handles it returned) and, while a
    profiler session runs, held as a `TraceAnnotation("loop.idle")` on the
    loop thread's line of the trace. `idle_s` sums them for `loop_tick`."""

    def __init__(self):
        super().__init__()
        self.idle_s = 0.0
        self.turns = 0      # selects so far: one a turn of the loop
        self.mark = None    # `loop_tick`'s last (wall, thread CPU, idle_s, turns)

    def select(self, timeout=None):
        self.turns += 1
        if (timeout is not None and timeout <= 0) or _capacity() <= 0:
            return super().select(timeout)
        cls = _annotation()
        t0 = time.perf_counter()
        if cls is None:
            events = super().select(timeout)
        else:
            with cls("loop.idle"):
                events = super().select(timeout)
        dt = time.perf_counter() - t0
        self.idle_s += dt
        _book("loop.idle", dt, 0.0, "", "", len(events), None, 0)
        return events


def event_loop() -> asyncio.AbstractEventLoop:
    """The `loop_factory` of every `asyncio.run` of `__main__.py`."""
    return asyncio.SelectorEventLoop(TimedSelector())


def loop_tick() -> None:
    """Book `loop.run` for the stretch since the last tick: called by the
    accounting pump on the loop's thread, every `obs.loop_lag_interval`. A
    loop that `event_loop` did not make books nothing: its idle time is
    unknown, and wall with idle inside it is no running time."""
    global _LOOP_THREAD
    sel = getattr(asyncio.get_running_loop(), "_selector", None)
    if not isinstance(sel, TimedSelector) or _capacity() <= 0:
        return
    _LOOP_THREAD = _get_ident()
    now = (time.perf_counter(), time.thread_time(), sel.idle_s, sel.turns)
    mark, sel.mark = sel.mark, now
    if mark is not None:
        _book("loop.run", max(0.0, now[0] - mark[0] - (now[2] - mark[2])),
              0.0, "", "", now[3] - mark[3], None, 0, now[1] - mark[1])


def snapshot(job: Optional[str] = None) -> List[dict]:
    """The ring as dicts, oldest first; `job` filters one job's
    entries."""
    with _LOCK:
        entries = list(_RING)
    out = []
    for ts_us, dur_us, name, j, task, n, key, self_us, cpu_us in entries:
        if job is not None and j != job:
            continue
        out.append({"ts": ts_us - dur_us, "dur": dur_us, "phase": name,
                    "job": j, "task": task, "n": n, "key": key,
                    "self": self_us, "cpu": cpu_us})
    return out


def totals(t0_us: Optional[float] = None, t1_us: Optional[float] = None,
           task: Optional[str] = None,
           job: Optional[str] = None) -> Dict[str, dict]:
    """Per phase {count, total_s, self_s, max_s, n, padded, cpu_s,
    self_cpu_s} of the entries
    that ended in [t0_us, t1_us) on the wall clock (`time.time() * 1e6`;
    None = no bound), at the buckets' grain: the buckets that START in the
    interval are summed, so the covered length is the interval's within a
    quarter second either way, with no bias. Independent of the ring's
    size."""
    lo = None if t0_us is None else -(-int(t0_us) // BUCKET_US)
    hi = None if t1_us is None else -(-int(t1_us) // BUCKET_US)
    out: Dict[str, list] = {}
    with _LOCK:
        for bucket, cells in _BUCKETS:
            if (lo is not None and bucket < lo) or (
                    hi is not None and bucket >= hi):
                continue
            for (name, j, t), c in cells.items():
                if (job is not None and j != job) or (
                        task is not None and t != task):
                    continue
                acc = out.get(name)
                if acc is None:
                    out[name] = list(c)
                else:
                    acc[0] += c[0]
                    acc[1] += c[1]
                    acc[2] += c[2]
                    acc[3] = max(acc[3], c[3])
                    acc[4] += c[4]
                    acc[5] += c[5]
                    acc[6] += c[6]
                    acc[7] += c[7]
    return {
        name: {"count": c[0], "total_s": round(c[1] / 1e6, 6),
               "self_s": round(c[2] / 1e6, 6), "max_s": round(c[3] / 1e6, 6),
               "n": c[4], "padded": c[5], "cpu_s": round(c[6] / 1e6, 6),
               "self_cpu_s": round(c[7] / 1e6, 6)}
        for name, c in out.items()}


def phase_totals(job: Optional[str] = None) -> Dict[str, dict]:
    """`totals` over everything the store still holds: the doctor's
    primary signal."""
    return totals(job=job)


def expunge_job(job_id: str) -> int:
    """Job-scoped GC (StopJob / Registry.drop_job path): drop the torn-
    down job's entries from the ring and from the buckets instead of
    letting them linger until overwrite. Returns the ring entries
    removed."""
    global _N_CELLS
    with _LOCK:
        kept = [e for e in _RING if e[3] != job_id]
        removed = len(_RING) - len(kept)
        _RING.clear()
        _RING.extend(kept)
        for _bucket, cells in _BUCKETS:
            for k in [k for k in cells if k[1] == job_id]:
                del cells[k]
                _N_CELLS -= 1
    return removed


def clear() -> None:
    global _N_CELLS, _LOOP_THREAD
    with _LOCK:
        _RING.clear()
        _BUCKETS.clear()
        _N_CELLS = 0
        _LOOP_THREAD = None
        _CPU_AT.clear()
    _resize()
