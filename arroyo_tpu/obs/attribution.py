"""Per-job cost attribution on multiplexed workers (ISSUE 11).

PR 10 multiplexed subtasks of 100+ jobs onto one event loop and one JAX
runtime, but every cost signal stayed per-process: nothing said which
tenant was burning the shared CPU/device time. This module threads a
job-id contextvar through the hot paths (runner batch loop, exchange
pumps, checkpoint flushes, `InstrumentedJit`) and accumulates per-job
deltas in plain dicts — the hot path pays one contextvar read and one
dict update, never a metric-registry lock — which a per-worker
accounting pump periodically rolls into the `arroyo_job_attributed_*`
metric families:

* busy seconds (mirrors the per-subtask `arroyo_worker_busy_seconds`
  sites, so attributed busy sums to the worker's measured busy time —
  the fleet harness asserts >= 95% coverage);
* process-CPU seconds (each pump flush apportions the interval's
  process-CPU delta across jobs proportional to attributed busy);
* device seconds + dispatch counts (the per-job dimension of the XLA
  telemetry — jitted programs are cached process-wide across jobs, so
  the per-program families cannot carry a job label themselves).
  "Device seconds" here and in `arroyo_job_attributed_device_seconds`
  are the HOST time of the calls into jitted programs, as
  `obs/device.py`'s `InstrumentedJit` clocks them: the enqueue (and, on
  a first call, trace and compile), never the program's run on the
  device, which only a profiler trace shows;
* bytes. (Per-phase wall seconds live in the timeline ledger's own
  store, `obs/timeline.py`: its `phase_totals` is what the doctor reads.)

Shared-plan apportioning (ISSUE 16): a shared source scan runs as a
hidden host job `__shared/<fp>`, so its runner notes busy/device time
under a job id no tenant owns. Each flush reassigns the host's pending
deltas across the scan's subscribers pro-rata by the rows each consumed
from the bus in the interval (`SharedChannel.consumed`), sum-preserving
— attributed cost per tenant survives the collapse of N scans into one,
and the fleet harness's >= 95% coverage gate holds over shared fleets
with no `__shared/*` escape bucket.

The pump also samples event-loop lag (sleep-overshoot of a fixed
timer) into `arroyo_worker_loop_lag_seconds` — the signal that
separates "my job is starved" from "a co-resident tenant is hogging
the loop" in the bottleneck doctor — and on the same cadence books the
loop's running time and its thread's CPU into the phase ledger
(`timeline.loop_tick`: `loop.run`, beside the selector's `loop.idle`).

Everything is gated on `obs.attribution` (independent of `obs.enabled`:
attribution is plain metrics, no spans, so the fleet harness can run it
with the span recorder off).
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import threading
import time
from collections import deque
from typing import Dict, List, Optional

# the ambient job id ("" = unattributed); set once per runner/pump task
# at spawn, inherited by tasks it creates (asyncio copies the context)
_JOB: contextvars.ContextVar[str] = contextvars.ContextVar(
    "arroyo_job_attr", default=""
)


def enabled() -> bool:
    from ..config import config

    return bool(config().obs.attribution)


def current_job() -> str:
    return _JOB.get()


def set_job(job_id: str):
    """Bind the ambient job id for the current task's context; returns a
    token for reset. Runner/pump tasks call this once at task start so
    every await-descendant (flushes, storage threads) inherits it."""
    return _JOB.set(job_id)


def reset_job(token) -> None:
    _JOB.reset(token)


@contextlib.contextmanager
def job_scope(job_id: str):
    tok = _JOB.set(job_id)
    try:
        yield
    finally:
        _JOB.reset(tok)


class _Pending:
    """One job's unflushed deltas (plain floats; lock held by Accounting)."""

    __slots__ = ("busy", "device", "dispatches", "bytes",
                 "first_ts", "last_ts")

    def __init__(self):
        self.busy = 0.0
        self.device = 0.0
        self.dispatches = 0
        self.bytes = 0
        self.first_ts = time.monotonic()
        self.last_ts = self.first_ts


class Accounting:
    """Process-wide attribution accumulator + flush into the metric
    families. Thread-safe: device dispatches can fire from to_thread
    storage work, and the lock is uncontended on the single-loop path."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: Dict[str, _Pending] = {}
        # cumulative per-job totals (survive flushes): the doctor and the
        # fleet harness read these without touching the registry
        self._totals: Dict[str, Dict[str, float]] = {}
        # per-job active window [first note, last note] for busy ratios
        self._windows: Dict[str, List[float]] = {}
        self._handles: Dict[str, dict] = {}
        # shared-plan apportioning: fp -> last-seen per-tenant consumed
        # row counts (the deltas weight each interval's split)
        self._shared_marks: Dict[str, Dict[str, int]] = {}
        self._cpu_mark: Optional[float] = None
        # bounded loop-lag sample window (seconds) for p99 without
        # histogram-bucket snapping
        self.lag_samples: deque = deque(maxlen=2048)

    # ---------------------------------------------------------- hot path

    def note(self, *, job: Optional[str] = None, busy: float = 0.0,
             device: float = 0.0, dispatches: int = 0,
             nbytes: int = 0) -> None:
        """Accumulate one site's delta under `job` (default: the ambient
        job id). Unattributed work lands under "" and is surfaced as the
        coverage gap, never silently dropped."""
        if job is None:
            job = _JOB.get()
        with self._lock:
            p = self._pending.get(job)
            if p is None:
                p = self._pending[job] = _Pending()
            p.busy += busy
            p.device += device
            p.dispatches += dispatches
            p.bytes += nbytes
            p.last_ts = time.monotonic()

    def note_lag(self, lag: float) -> None:
        from ..metrics import LOOP_LAG_SECONDS

        self.lag_samples.append(lag)
        LOOP_LAG_SECONDS.labels().observe(lag)

    # ------------------------------------------------------------- flush

    def _apportion_shared(self, pending: Dict[str, _Pending]) -> None:
        """Reassign `__shared/<fp>` host-job deltas across the scan's
        subscribers, weighted by the rows each consumed from the bus
        since the last flush (even split across attached readers when no
        rows moved — an idle scan's heartbeat cost is theirs too).
        Sum-preserving: float fields give the last tenant the exact
        remainder, integer fields apportion by floor with the remainder
        on the heaviest consumer. A host with no subscribers keeps its
        own bucket — still attributed, visible as unapportioned scan
        cost. Caller holds self._lock."""
        from ..engine.shared import BUS, HOST_PREFIX

        for host_id in [j for j in pending if j.startswith(HOST_PREFIX)]:
            channel = BUS.get(host_id[len(HOST_PREFIX):])
            if channel is None:
                continue
            consumed = dict(channel.consumed)
            marks = self._shared_marks.get(channel.fingerprint, {})
            self._shared_marks[channel.fingerprint] = consumed
            weights = {
                t: c - marks.get(t, 0)
                for t, c in consumed.items() if c - marks.get(t, 0) > 0
            }
            if not weights:
                weights = {t: 1 for t in channel.cursors}
            if not weights:
                continue
            p = pending.pop(host_id)
            total = sum(weights.values())
            tenants = sorted(weights)

            def split_f(value):
                out, acc = {}, 0.0
                for t in tenants[:-1]:
                    out[t] = value * weights[t] / total
                    acc += out[t]
                out[tenants[-1]] = value - acc
                return out

            def split_i(value):
                out = {t: value * weights[t] // total for t in tenants}
                heaviest = max(tenants, key=lambda t: weights[t])
                out[heaviest] += value - sum(out.values())
                return out

            busy = split_f(p.busy)
            device = split_f(p.device)
            disp = split_i(p.dispatches)
            nbytes = split_i(p.bytes)
            for t in tenants:
                q = pending.get(t)
                if q is None:
                    q = pending[t] = _Pending()
                q.busy += busy[t]
                q.device += device[t]
                q.dispatches += disp[t]
                q.bytes += nbytes[t]
                q.first_ts = min(q.first_ts, p.first_ts)
                q.last_ts = max(q.last_ts, p.last_ts)

    def _job_handles(self, job: str) -> dict:
        from ..metrics import (
            JOB_ATTR_BUSY_SECONDS,
            JOB_ATTR_BYTES,
            JOB_ATTR_CPU_SECONDS,
            JOB_ATTR_DEVICE_SECONDS,
            JOB_ATTR_DISPATCHES,
        )

        h = self._handles.get(job)
        if h is None:
            h = self._handles[job] = {
                "busy": JOB_ATTR_BUSY_SECONDS.labels(job=job),
                "cpu": JOB_ATTR_CPU_SECONDS.labels(job=job),
                "device": JOB_ATTR_DEVICE_SECONDS.labels(job=job),
                "dispatches": JOB_ATTR_DISPATCHES.labels(job=job),
                "bytes": JOB_ATTR_BYTES.labels(job=job),
            }
        return h

    def flush(self) -> None:
        """Roll pending deltas into the metric families and apportion the
        interval's process-CPU delta across jobs proportional to their
        attributed busy time in the interval. Idempotent; called by the
        pump each interval and by scrape-side readers (doctor, harness)."""
        with self._lock:
            pending, self._pending = self._pending, {}
            cpu_now = time.process_time()
            cpu_delta = (
                cpu_now - self._cpu_mark if self._cpu_mark is not None
                else 0.0
            )
            self._cpu_mark = cpu_now
            if any(j.startswith("__shared/") for j in pending):
                self._apportion_shared(pending)
        if not pending:
            return
        busy_total = sum(p.busy for p in pending.values())
        for job, p in pending.items():
            h = self._job_handles(job)
            tot = self._totals.setdefault(
                job, {"busy": 0.0, "cpu": 0.0, "device": 0.0,
                      "dispatches": 0, "bytes": 0},
            )
            win = self._windows.setdefault(job, [p.first_ts, p.last_ts])
            win[0] = min(win[0], p.first_ts)
            win[1] = max(win[1], p.last_ts)
            if p.busy:
                h["busy"].inc(p.busy)
                tot["busy"] += p.busy
                # CPU apportioning: the process-CPU delta is split by
                # attributed busy share — exact per-job CPU accounting
                # would need per-batch clock_gettime(THREAD_CPUTIME)
                # pairs, and busy-share tracks it closely on a worker
                # whose loop does the work
                if cpu_delta > 0 and busy_total > 0:
                    share = cpu_delta * (p.busy / busy_total)
                    h["cpu"].inc(share)
                    tot["cpu"] += share
            if p.device:
                h["device"].inc(p.device)
                tot["device"] += p.device
            if p.dispatches:
                h["dispatches"].inc(p.dispatches)
                tot["dispatches"] += p.dispatches
            if p.bytes:
                h["bytes"].inc(p.bytes)
                tot["bytes"] += p.bytes

    # ----------------------------------------------------------- surface

    def summary(self) -> dict:
        """Structured per-job rollup for /debug/attribution, the doctor,
        and the fleet harness: cumulative attributed totals, active
        windows, coverage vs the unattributed bucket, and loop-lag
        percentiles."""
        self.flush()
        jobs = {}
        with self._lock:
            for job, tot in self._totals.items():
                win = self._windows.get(job)
                jobs[job or "(unattributed)"] = {
                    **{k: round(v, 4) if isinstance(v, float) else v
                       for k, v in tot.items()},
                    "window_s": round(win[1] - win[0], 3) if win else 0.0,
                }
            lags = sorted(self.lag_samples)
        attributed = sum(
            v["busy"] for k, v in jobs.items() if k != "(unattributed)"
        )
        unattributed = jobs.get("(unattributed)", {}).get("busy", 0.0)
        total = attributed + unattributed
        out = {
            "jobs": jobs,
            "attributed_busy_s": round(attributed, 4),
            "unattributed_busy_s": round(unattributed, 4),
            "coverage": round(attributed / total, 4) if total else 1.0,
        }
        if lags:
            out["loop_lag_ms"] = {
                "p50": round(1e3 * lags[len(lags) // 2], 3),
                "p99": round(1e3 * lags[min(len(lags) - 1,
                                            int(0.99 * len(lags)))], 3),
                "max": round(1e3 * lags[-1], 3),
                "samples": len(lags),
            }
        return out

    def job_busy(self, job: str) -> float:
        self.flush()
        with self._lock:
            return self._totals.get(job, {}).get("busy", 0.0)

    def drop_job(self, job_id: str) -> None:
        """Cardinality GC hook (Registry.drop_job path): a torn-down
        job's pending deltas, cached handles, totals and window state
        must not outlive its metric series."""
        with self._lock:
            self._pending.pop(job_id, None)
            self._handles.pop(job_id, None)
            self._totals.pop(job_id, None)
            self._windows.pop(job_id, None)
            if job_id.startswith("__shared/"):
                self._shared_marks.pop(job_id[len("__shared/"):], None)

    def reset(self) -> None:
        with self._lock:
            self._pending.clear()
            self._handles.clear()
            self._totals.clear()
            self._windows.clear()
            self._shared_marks.clear()
            self._cpu_mark = None
            self.lag_samples.clear()


ACCOUNTING = Accounting()


def note(**kw) -> None:
    """Module-level hot-path shim: no-op unless obs.attribution is on."""
    if enabled():
        ACCOUNTING.note(**kw)


# -- the per-worker accounting pump ------------------------------------------

_PUMP_TASK: Optional[asyncio.Task] = None
_PUMP_REFS = 0


async def _pump_loop():
    """Flush cadence + event-loop lag sampler + history scrape. One pump
    per process even when several embedded WorkerServers share the loop
    (refcounted): a second sampler would double-count lag observations.

    The watchtower's per-worker scrape rides this cadence machinery
    (ISSUE 13): each interval the pump offers the live registry to the
    process's metric-history tier; `MetricHistory.sample_registry`'s own
    `watch.sample_interval` guard turns the offer into the configured
    sampling rate (and dedupes against a co-resident controller
    watchtower pumping the same history)."""
    from ..config import config
    from . import history, timeline

    while True:
        cfg = config().obs
        interval = max(0.05, float(cfg.loop_lag_interval or
                                   cfg.attribution_flush_interval or 0.5))
        t0 = time.monotonic()
        await asyncio.sleep(interval)
        if enabled():
            lag = max(0.0, time.monotonic() - t0 - interval)
            if cfg.loop_lag_interval:
                ACCOUNTING.note_lag(lag)
                if lag > 0.001:
                    # visible stalls land in the timeline ledger so
                    # Perfetto dumps and the offline doctor see loop
                    # pressure
                    timeline.note("loop.lag", lag, job="")
                # and the stretch since the last tick, idle taken out,
                # with the loop thread's CPU: `loop.run`
                timeline.loop_tick()
            ACCOUNTING.flush()
        history.HISTORY.sample_registry()


def _history_enabled() -> bool:
    from ..config import config

    return bool(config().watch.enabled)


def ensure_pump() -> None:
    """Start (or ref) the process's accounting pump on the running loop.
    Runs when attribution OR the watchtower history tier wants the
    cadence (each part gates itself per iteration)."""
    global _PUMP_TASK, _PUMP_REFS
    if not (enabled() or _history_enabled()):
        return
    _PUMP_REFS += 1
    if _PUMP_TASK is None or _PUMP_TASK.done():
        _PUMP_TASK = asyncio.ensure_future(_pump_loop())


def release_pump() -> None:
    """Drop one pump reference; the last release cancels the task and
    takes a final flush so teardown never strands pending deltas."""
    global _PUMP_TASK, _PUMP_REFS
    if _PUMP_REFS > 0:
        _PUMP_REFS -= 1
    if _PUMP_REFS == 0 and _PUMP_TASK is not None:
        _PUMP_TASK.cancel()
        _PUMP_TASK = None
        ACCOUNTING.flush()
