"""Always-on phase ledger (ISSUE 11, regrained in ISSUE 24): the one way
host work gets a name.

`phase(name, ...)` is a context manager around a piece of host work: a
`perf_counter` pair, a contextvar frame for nesting, a ring append and a
bucket update on exit (about 2 us with no profiler session:
`tools/phase_cost.py`). `note(name, dur_s, ...)` books the same entry for
callers that have a duration in hand. An entry carries the phase, job and task, `n` (a count of the work:
rows, keys or slots), `key` (what caused it: the window's end for a close)
and its self time: the duration less the phases booked inside it, so a
leaf's seconds add up and an enclosing phase's self time is what no leaf
names yet.

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

import contextvars
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..config import config as _config
from .attribution import current_job as _current_job

# ring entries: (ts_us_end, dur_us, phase, job, task, n, key, self_us)
_RING: deque = deque(maxlen=8192)
_LOCK = threading.Lock()

# cumulative store: (bucket id, {(phase, job, task): cell}) oldest first,
# cell = [count, total_us, self_us, max_us, n, padded]. A bucket is the
# quarter second in which its entries ENDED. Bounded by cells, so a fleet
# of many jobs keeps a shorter history than one job (one q5 job books
# ~40 cells a bucket: over five minutes).
BUCKET_US = 250_000
_MAX_CELLS = 65_536
_BUCKETS: deque = deque()
_N_CELLS = 0

# phases that enclose a whole batch or a whole watermark advance: their
# SELF time is the host time inside the engine that no leaf names
ENCLOSING = ("process", "watermark")
# phases that measure waiting, not work on the engine's thread (a full out
# queue, a storage thread, the loop's lag): left out of a sum of named work
WAITS = ("queue.wait", "flush", "loop.lag")

# the open phase of the current task or thread (asyncio tasks and
# to_thread calls copy the context, so a frame never crosses runners)
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "arroyo_timeline_open", default=None)
_TRACEME = None  # jax.profiler.TraceAnnotation, once jax is imported


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


def _book(phase_name: str, dur_s: float, child_s: float, job: Optional[str],
          task: str, n: int, key, padded: int) -> None:
    global _N_CELLS
    parent = _OPEN.get()
    if parent is not None:
        # the work of one task of one job: a leaf need not be told whose
        parent._child += dur_s
        if not task:
            task = parent.task
        if job is None:
            job = parent.job
    if job is None:
        job = _current_job()
    ts_us = time.time() * 1e6
    dur_us = dur_s * 1e6
    self_us = max(0.0, dur_us - child_s * 1e6)
    _RING.append((ts_us, dur_us, phase_name, job, task, n, key, self_us))
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
                1, dur_us, self_us, dur_us, n, padded]
            _N_CELLS += 1
        else:
            cell[0] += 1
            cell[1] += dur_us
            cell[2] += self_us
            if dur_us > cell[3]:
                cell[3] = dur_us
            cell[4] += n
            cell[5] += padded


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
    sub-step of a leaf, and any block that awaits."""

    __slots__ = ("name", "task", "job", "n", "key", "padded", "_annotate",
                 "_child", "_tok", "_ann", "_t0")

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
            return self
        self._child = 0.0
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
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._tok is None:
            return False
        dt = time.perf_counter() - self._t0
        _OPEN.reset(self._tok)
        self._tok = None
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _book(self.name, dt, self._child, self.job, self.task, self.n,
              self.key, self.padded)
        return False


def open_phase() -> Optional[phase]:
    """The innermost open `phase` of this task or thread, if any: where a
    callee books counts that its caller's phase should carry."""
    return _OPEN.get()


def note(phase: str, dur_s: float, *, job: Optional[str] = None,
         task: str = "", n: int = 0, key=None, padded: int = 0) -> None:
    """Record one phase instant (duration ending now) for a caller that
    has the duration in hand. `job` defaults to the ambient attribution
    context; inside an open `phase` the duration counts as its child.
    `padded`, as on a `phase`: the whole that `n` is the real part of."""
    if _capacity() <= 0:
        return
    _book(phase, dur_s, 0.0, job, task, n, key, padded)


def snapshot(job: Optional[str] = None) -> List[dict]:
    """The ring as dicts, oldest first; `job` filters one job's
    entries."""
    with _LOCK:
        entries = list(_RING)
    out = []
    for ts_us, dur_us, name, j, task, n, key, self_us in entries:
        if job is not None and j != job:
            continue
        out.append({"ts": ts_us - dur_us, "dur": dur_us, "phase": name,
                    "job": j, "task": task, "n": n, "key": key,
                    "self": self_us})
    return out


def totals(t0_us: Optional[float] = None, t1_us: Optional[float] = None,
           task: Optional[str] = None,
           job: Optional[str] = None) -> Dict[str, dict]:
    """Per phase {count, total_s, self_s, max_s, n, padded} of the entries
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
    return {
        name: {"count": c[0], "total_s": round(c[1] / 1e6, 6),
               "self_s": round(c[2] / 1e6, 6), "max_s": round(c[3] / 1e6, 6),
               "n": c[4], "padded": c[5]}
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
    global _N_CELLS
    with _LOCK:
        _RING.clear()
        _BUCKETS.clear()
        _N_CELLS = 0
    _resize()
