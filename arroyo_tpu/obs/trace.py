"""Flight-recorder trace spans: cross-process causal tracing primitives.

Dapper-style spans (Sigelman et al. 2010) layered over the engine's
aligned-snapshot checkpoints (Carbone et al. 2015): the controller mints
one trace per checkpoint epoch / job lifecycle event, and the trace
context — a (trace_id, span_id) pair — propagates through the gRPC-analog
control plane (`__trace__` message key), ControlMsg barriers
(CheckpointBarrier.trace_id/span_id), and the TCP Arrow-IPC data plane
(frame headers carry a send timestamp on every frame plus a sampled trace
preamble), so controller → worker → operator runner → state storage
stitch into one tree across processes.

Spans land in a bounded per-process ring buffer (`TraceRecorder`) on
finish; exports are Chrome trace-event JSON (Perfetto-loadable) via
`chrome_trace()`. Everything is a no-op when `obs.enabled` is off or no
trace context is active, so the hot path pays one contextvar read.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# the active trace context: (trace_id, span_id) or None
_CTX: contextvars.ContextVar[Optional[Tuple[str, str]]] = contextvars.ContextVar(
    "arroyo_trace_ctx", default=None
)


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def new_trace(*parts) -> str:
    """Canonical trace id: '/'-joined parts, job id first, so per-job
    exports can filter on the `{job_id}/` prefix."""
    return "/".join(str(p) for p in parts)


class Span:
    """One timed operation. Use as a context manager (attaches the trace
    context for the dynamic extent) or finish() explicitly for async hops
    that outlive the creating frame."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "cat", "attrs",
        "events", "start_us", "end_us", "_token", "_finished",
    )

    def __init__(self, trace_id: str, span_id: str, parent_id: Optional[str],
                 name: str, cat: str, attrs: Dict[str, Any]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.events: List[tuple] = []
        self.start_us = time.time() * 1e6
        self.end_us: Optional[float] = None
        self._token = None
        self._finished = False

    @property
    def recording(self) -> bool:
        return True

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs) -> None:
        self.events.append((time.time() * 1e6, name, attrs))

    def attach(self):
        """Make this span the ambient trace context (returns a token for
        detach). Used on async hops where `with` can't scope the extent."""
        return _CTX.set((self.trace_id, self.span_id))

    @staticmethod
    def detach(token) -> None:
        _CTX.reset(token)

    def finish(self, recorder: Optional["TraceRecorder"] = None) -> None:
        if self._finished:
            return
        self._finished = True
        self.end_us = time.time() * 1e6
        if recorder is None:
            from . import recorder as _get_recorder

            recorder = _get_recorder()
        recorder.record(self.to_dict())

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "cat": self.cat,
            "ts": self.start_us,
            "dur": (self.end_us or time.time() * 1e6) - self.start_us,
            "attrs": dict(self.attrs),
            "events": [
                {"ts": ts, "name": n, "attrs": a} for ts, n, a in self.events
            ],
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }

    def __enter__(self) -> "Span":
        self._token = self.attach()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._token is not None:
            _CTX.reset(self._token)
            self._token = None
        if exc is not None:
            self.attrs["error"] = repr(exc)[:300]
        self.finish()


class _NullSpan:
    """Inert span: returned when tracing is disabled or no context is
    active, so call sites never branch."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = None
    recording = False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def event(self, name: str, **attrs) -> None:
        pass

    def attach(self):
        return None

    @staticmethod
    def detach(token) -> None:
        pass

    def finish(self, recorder=None) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


NULL_SPAN = _NullSpan()


class TraceRecorder:
    """Bounded in-memory ring buffer of finished spans (oldest dropped
    first); thread-safe — storage spans finish from to_thread workers."""

    def __init__(self, capacity: int, role: str = ""):
        self.capacity = max(1, int(capacity))
        self.role = role or f"proc-{os.getpid()}"
        self.spans: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self._lock = threading.Lock()

    def record(self, span_dict: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.spans) == self.capacity:
                self.dropped += 1
                dropped = True
            else:
                dropped = False
            span_dict.setdefault("role", self.role)
            self.spans.append(span_dict)
        if dropped:
            # exported as a real counter so sustained overflow is
            # alertable (watchtower trace_drops rule) instead of only
            # visible to someone reading /debug/trace at the right moment
            from ..metrics import TRACE_DROPPED_SPANS

            TRACE_DROPPED_SPANS.labels().inc()

    def snapshot(self, trace_prefix: Optional[str] = None,
                 trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            spans = list(self.spans)
        if trace_prefix is not None:
            spans = [s for s in spans
                     if s.get("trace_id", "").startswith(trace_prefix)]
        if trace_id is not None:
            spans = [s for s in spans if s.get("trace_id") == trace_id]
        return spans

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.dropped = 0

    def expunge_job(self, job_id: str) -> int:
        """Job-scoped GC: drop every span of one job (trace ids are
        '{job_id}/...'-prefixed by new_trace). Without this, a torn-down
        job's spans linger in the ring until overwrite — wired into the
        StopJob / Registry.drop_job metrics-GC path so trace exports of
        a multiplexed worker only show live tenants. Returns the number
        of spans removed."""
        prefix = f"{job_id}/"
        with self._lock:
            kept = [s for s in self.spans
                    if not s.get("trace_id", "").startswith(prefix)]
            removed = len(self.spans) - len(kept)
            self.spans.clear()
            self.spans.extend(kept)
        return removed

    def __len__(self) -> int:
        with self._lock:
            return len(self.spans)


def current() -> Optional[Tuple[str, str]]:
    """The ambient (trace_id, span_id), or None."""
    return _CTX.get()


def attach(trace_id: str, span_id: str):
    return _CTX.set((trace_id, span_id))


def detach(token) -> None:
    _CTX.reset(token)


def chrome_trace(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Spans → Chrome trace-event JSON (load in Perfetto / chrome://tracing).
    Complete spans become 'X' events; span events and instants become 'i'
    events; per-pid process_name metadata names each role."""
    events: List[Dict[str, Any]] = []
    roles: Dict[int, str] = {}
    for s in spans:
        pid = s.get("pid", 0)
        roles.setdefault(pid, s.get("role", str(pid)))
        args = {
            "trace_id": s.get("trace_id"),
            "span_id": s.get("span_id"),
            "parent_id": s.get("parent_id"),
            **(s.get("attrs") or {}),
        }
        if s.get("instant"):
            events.append({
                "name": s["name"], "cat": s.get("cat", "obs"), "ph": "i",
                "ts": s["ts"], "pid": pid, "tid": s.get("tid", 0),
                "s": "p", "args": args,
            })
            continue
        events.append({
            "name": s["name"], "cat": s.get("cat", "obs"), "ph": "X",
            "ts": s["ts"], "dur": max(0.0, s.get("dur") or 0.0),
            "pid": pid, "tid": s.get("tid", 0), "args": args,
        })
        for ev in s.get("events", []):
            events.append({
                "name": ev["name"], "cat": s.get("cat", "obs"), "ph": "i",
                "ts": ev["ts"], "pid": pid, "tid": s.get("tid", 0),
                "s": "t",
                "args": {"span_id": s.get("span_id"), **(ev.get("attrs") or {})},
            })
    for pid, role in roles.items():
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": role},
        })
    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _phase_tid(job: str, phase: str) -> int:
    """Stable synthetic thread id for one (job, phase) ledger track —
    kept far above real thread idents' low range is impossible (idents
    are arbitrary), so phase tracks get their own namespace via a
    deterministic hash with bit 62 set: collisions with a real tid would
    merge a phase track into a span track."""
    h = 0
    for ch in f"{job}\x00{phase}":
        h = (h * 131 + ord(ch)) & 0x3FFFFFFFFFFFFFFF
    return h | (1 << 62)


def perfetto_trace(spans: List[Dict[str, Any]],
                   timeline: Optional[List[Dict[str, Any]]] = None,
                   job: Optional[str] = None) -> Dict[str, Any]:
    """Spans (+ the batch-phase ledger) as Perfetto-ready Chrome
    trace-event JSON. On top of `chrome_trace`:

    * each (job, phase) pair of the timeline ledger renders as its own
      named track ('X' events with thread_name metadata), so a q5
      checkpoint epoch or a rescale shows decode/dispatch/exchange/
      emit/flush as parallel swimlanes under the process;
    * `job` filters both spans (trace-id prefix) and ledger entries to
      one tenant.

    Served by `/debug/trace?fmt=perfetto`, the REST traces route, and
    `tools/trace_report.py --perfetto`."""
    if job is not None:
        prefix = f"{job}/"
        spans = [s for s in spans
                 if s.get("trace_id", "").startswith(prefix)]
    doc = chrome_trace(spans)
    events = doc["traceEvents"]
    if timeline is None:
        from . import timeline as _timeline

        timeline = _timeline.snapshot(job)
    elif job is not None:
        timeline = [e for e in timeline if e.get("job") == job]
    pid = os.getpid()
    named: set = set()
    for e in timeline:
        tid = _phase_tid(e.get("job", ""), e["phase"])
        if tid not in named:
            named.add(tid)
            jlabel = e.get("job") or "worker"
            events.append({
                "name": "thread_name", "ph": "M", "pid": e.get("pid", pid),
                "tid": tid,
                "args": {"name": f"{jlabel} · {e['phase']}"},
            })
        events.append({
            "name": f"phase.{e['phase']}", "cat": "phase", "ph": "X",
            "ts": e["ts"], "dur": max(0.0, e.get("dur") or 0.0),
            "pid": e.get("pid", pid), "tid": tid,
            "args": {"job": e.get("job", ""), "task": e.get("task", ""),
                     "n": e.get("n", 0), "key": e.get("key"),
                     "self": e.get("self", e.get("dur") or 0.0),
                     "cpu": e.get("cpu", 0.0)},
        })
    events.sort(key=lambda ev: (ev.get("ts", 0), ev.get("pid", 0)))
    doc["phaseCount"] = len(timeline)
    return doc
