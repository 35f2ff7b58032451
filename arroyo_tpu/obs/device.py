"""Device-tier observatory: XLA compile & dispatch telemetry.

The JAX tier compiles one executable per (program, shape signature):
every new padding rung, accumulator capacity or dtype layout traces and
compiles a fresh program — ~ms on CPU-jax, more on a chip — and until
now those cycles were invisible (ROADMAP item 1: the 8-way mesh path
loses to one process and nobody can say how much of the gap is compile
storms vs padding vs dispatch).

`InstrumentedJit` wraps a jitted callable and, per call, classifies it
as a compile (first time this process sees the call's shape signature)
or a steady-state dispatch:

* compiles feed `arroyo_xla_compiles_total`, the
  `arroyo_xla_compile_seconds` histogram, a compile-cache miss, a
  bounded recompile-cause log naming the program, the offending shape
  signature and the packing rung that produced it, and — when a trace
  context is ambient — a `jax.compile:<program>` span inside whatever
  batch/checkpoint trace triggered the compile;
* dispatches feed `arroyo_device_dispatch_seconds` and a cache hit.

Every second booked here is HOST time: how long the call of the jitted
function took to return, which for a dispatch is the enqueue (argument
transfer and launch) and never the program's run on the device. JAX
returns before the device finishes; `dispatch_s`, the "device seconds" of
the attribution accounting and the ledger's `dispatch` phase all mean
this. Device time per program comes from a profiler trace only
(`benchmark/trace_reduce.py`): no call here waits for the device. A fresh
signature's call is booked as the phase `compile` (`key` = the program)
instead of `dispatch`, and is annotated, so that a device gap under a
compile on the loop reads `engine:compile` in a trace.

A call site that pads passes `rung=` (the padded rows) and `rows=` (the
real ones); both are summed per program (`summary()["programs"][p]`:
`rows`, `padded_rows`) and onto the ledger entry of the phase that
encloses the call (`timeline.phase`: `n`, `padded`).

`note_padding` records the per-(program, rung) padding-waste gauge from
the packing paths (aggregates + the mesh exchange in parallel/).

Everything is gated on `obs.device_telemetry`; when off, the wrapper
forwards straight to the jitted callable.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..metrics import (
    DEVICE_EXCHANGE_SECONDS,
    DEVICE_PADDING_WASTE,
    DEVICE_DISPATCH_SECONDS,
    XLA_COMPILE_CACHE,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
)
from . import attribution, timeline, trace

logger = logging.getLogger("arroyo.obs.device")

_LOCK = threading.Lock()
_RECOMPILE_LOG: deque = deque(maxlen=256)
# bumped whenever a jax.compile span lands in the recorder: the runner's
# lazy batch anchors use it to decide whether to materialize themselves
_SPAN_EPOCH = 0
# per-(program, rung) cached gauge handles for the padding-waste path
_PAD_HANDLES: Dict[Tuple[str, str], Any] = {}
# program -> [real rows, padded rows] summed over its calls
_ROWS: Dict[str, List[int]] = {}


def enabled() -> bool:
    from ..config import config

    return bool(config().obs.device_telemetry)


def span_epoch() -> int:
    return _SPAN_EPOCH


def recompile_log() -> List[dict]:
    """The bounded recompile-cause log, oldest first. Each entry names
    the program, the full shape signature that forced the compile, the
    packing rung the call site padded to, and the call's wall time."""
    with _LOCK:
        return list(_RECOMPILE_LOG)


def reset() -> None:
    """Clear telemetry state (tests)."""
    global _SPAN_EPOCH
    with _LOCK:
        _RECOMPILE_LOG.clear()
        _PAD_HANDLES.clear()
        for counts in _ROWS.values():
            counts[:] = [0, 0]
        _SPAN_EPOCH = 0


def _resize_log() -> None:
    from ..config import config

    global _RECOMPILE_LOG
    cap = int(config().obs.recompile_log_entries)
    if cap > 0 and _RECOMPILE_LOG.maxlen != cap:
        _RECOMPILE_LOG = deque(_RECOMPILE_LOG, maxlen=cap)


def _sig_part(a: Any, parts: List[str]) -> None:
    if isinstance(a, (list, tuple)):
        for x in a:
            _sig_part(x, parts)
        return
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None:
        parts.append(
            f"{dtype}[{'x'.join(str(d) for d in shape)}]"
        )
    else:
        parts.append(type(a).__name__)


def signature_of(args: tuple) -> str:
    """The call's shape signature — the key XLA specializes on: dtype and
    dimensions of every array argument, pytree-flattened in order."""
    parts: List[str] = []
    _sig_part(args, parts)
    return "(" + ", ".join(parts) + ")"


def _sig_key_part(a: Any, parts: List) -> None:
    if isinstance(a, (list, tuple)):
        for x in a:
            _sig_key_part(x, parts)
        return
    shape = getattr(a, "shape", None)
    if shape is not None:
        parts.append((getattr(a, "dtype", None), shape))
    else:
        parts.append(type(a))


def signature_key(args: tuple) -> tuple:
    """Hashable fast form of signature_of: (dtype, shape) tuples instead
    of built strings. The hot dispatch path classifies every call — at
    hundreds of dispatches per second the string rendering itself showed
    up in the mesh profile — so the string form is only materialized
    when a call is actually fresh (compiles are rare)."""
    parts: List = []
    _sig_key_part(args, parts)
    return tuple(parts)


def _record_compile(program: str, sig: str, rung: Optional[int],
                    nth: int, secs: float, start_us: float) -> None:
    global _SPAN_EPOCH
    cause = "first-compile" if nth == 1 else "shape-change"
    entry = {
        "ts": time.time(),
        "program": program,
        "signature": sig,
        "rung": rung,
        "nth_compile": nth,
        "compile_s": round(secs, 4),
        "cause": cause,
    }
    with _LOCK:
        _resize_log()
        _RECOMPILE_LOG.append(entry)
    logger.info(
        "xla compile #%d for %s (%s): signature=%s rung=%s %.3fs",
        nth, program, cause, sig, rung, secs,
    )
    ctx = trace.current()
    if ctx is None:
        return
    # retroactive span over the compiling call, parented into whatever
    # batch/checkpoint trace was ambient when the compile fired
    import os

    trace_id, parent_id = ctx
    from . import recorder

    recorder().record({
        "trace_id": trace_id,
        "span_id": trace.new_span_id(),
        "parent_id": parent_id,
        "name": f"jax.compile:{program}",
        "cat": "device",
        "ts": start_us,
        "dur": secs * 1e6,
        "attrs": {"signature": sig, "rung": rung, "nth_compile": nth,
                  "cause": cause},
        "events": [],
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    })
    with _LOCK:
        _SPAN_EPOCH += 1


class InstrumentedJit:
    """Wrap one jitted program with compile/dispatch telemetry. The
    in-process signature set classifies each call: an unseen signature
    means jax traces + XLA compiles inside this call (cache miss), a seen
    one is a pure dispatch (cache hit). The persistent on-disk XLA cache
    (ops/_jax.py) can make a "miss" cheap — the compile
    histogram will show it — but it still costs a python-side trace."""

    __slots__ = ("program", "fn", "seen", "_compiles", "_hit", "_miss",
                 "_compile_h", "_dispatch_h", "_exchange_h", "_rows")

    def __init__(self, program: str, fn, exchange: bool = False):
        self.program = program
        self.fn = fn
        self.seen: set = set()
        self._compiles = XLA_COMPILES.labels(program=program)
        self._hit = XLA_COMPILE_CACHE.labels(program=program, result="hit")
        self._miss = XLA_COMPILE_CACHE.labels(program=program, result="miss")
        self._compile_h = XLA_COMPILE_SECONDS.labels(program=program)
        self._dispatch_h = DEVICE_DISPATCH_SECONDS.labels(program=program)
        # [real rows, padded rows] of the calls that gave both, shared by
        # every wrapper of one program (join.phase2 has one per size)
        self._rows = _ROWS.setdefault(program, [0, 0])
        # exchange programs (the mesh keyed shuffle: route/step kernels)
        # additionally feed arroyo_device_exchange_seconds so the
        # collective's per-flush cost is separable from emission reads
        self._exchange_h = (
            DEVICE_EXCHANGE_SECONDS.labels(program=program)
            if exchange else None
        )

    def __call__(self, *args, rung: Optional[int] = None,
                 rows: Optional[int] = None,
                 padded: Optional[int] = None):
        """`rows` of the call's buffers are real, `padded` is what they
        hold: the rung itself unless the caller says otherwise (a mesh
        program's buffers hold S or S x S cells of its rung)."""
        if not enabled():
            return self.fn(*args)
        if rows is not None and rung is not None:
            if padded is None:
                padded = rung
            self._rows[0] += rows
            self._rows[1] += padded
            open_phase = timeline.open_phase()
            if open_phase is not None:
                open_phase.n += rows
                open_phase.padded += padded
        key = signature_key(args)
        fresh = key not in self.seen
        start_us = time.time() * 1e6
        t0 = time.perf_counter()
        c0 = timeline.thread_cpu(t0)
        if fresh:
            # trace and compile, seconds long and on the caller's thread:
            # a phase of its own, on the profiler's clock too, INSTEAD of
            # `dispatch` (a note tallies no children: both would count
            # the seconds twice)
            with timeline.phase("compile", key=self.program):
                out = self.fn(*args)
        else:
            out = self.fn(*args)
        t1 = time.perf_counter()
        dt = t1 - t0
        # per-job device attribution (ISSUE 11): jitted programs are
        # cached process-wide ACROSS jobs, so the per-program families
        # cannot carry a job label — the ambient job context gives
        # dispatch/compile seconds their job dimension instead, and the
        # timeline ledger its `dispatch` swimlane. `dt` is the host time
        # of the enqueue (module docstring), not time on the device
        attribution.note(device=dt, dispatches=1)
        if fresh:
            self.seen.add(key)
            self._compiles.inc()
            self._miss.inc()
            self._compile_h.observe(dt)
            _record_compile(self.program, signature_of(args), rung,
                            len(self.seen), dt, start_us)
        else:
            timeline.note("dispatch", dt,
                          cpu_s=timeline.thread_cpu(t1) - c0)
            self._hit.inc()
            self._dispatch_h.observe(dt)
            if self._exchange_h is not None:
                self._exchange_h.observe(dt)
        return out


def note_padding(program: str, rung: int, rows: int, shipped: int) -> None:
    """Record the padding waste of one packed dispatch: `rows` real rows
    shipped in a `shipped`-row buffer padded to `rung`. Gauge semantics
    (last dispatch wins) per (program, rung): the steady-state waste of
    each rung the pipeline actually hits, not a lifetime average — the
    lifetime totals stay in MESH_STATS / rows_padded."""
    if shipped <= 0 or not enabled():
        return
    key = (program, str(rung))
    h = _PAD_HANDLES.get(key)
    if h is None:
        with _LOCK:
            h = _PAD_HANDLES.setdefault(
                key,
                DEVICE_PADDING_WASTE.labels(program=program, rung=str(rung)),
            )
    h.set(round((shipped - rows) / shipped, 4))


# -- lazy trace anchors -------------------------------------------------------


class _NullAnchor:
    __slots__ = ()

    def close(self) -> None:
        pass


NULL_ANCHOR = _NullAnchor()


class _Anchor:
    """A deferred span: attaches a fresh trace context for the extent of
    one batch (or watermark advance), but only materializes the span in
    the recorder if a jax.compile span landed during the extent — so the
    hot loop pays a contextvar set/reset per batch, not a recorded span
    per batch (which would churn the ring buffer)."""

    __slots__ = ("trace_id", "span_id", "name", "attrs", "start_us",
                 "_tok", "_epoch0")

    def __init__(self, trace_id: str, name: str, attrs: dict):
        self.trace_id = trace_id
        self.span_id = trace.new_span_id()
        self.name = name
        self.attrs = attrs
        self.start_us = time.time() * 1e6
        self._epoch0 = _SPAN_EPOCH
        self._tok = trace.attach(trace_id, self.span_id)

    def close(self) -> None:
        trace.detach(self._tok)
        if _SPAN_EPOCH == self._epoch0:
            return
        import os

        from . import recorder

        recorder().record({
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": None,
            "name": self.name,
            "cat": "runner",
            "ts": self.start_us,
            "dur": time.time() * 1e6 - self.start_us,
            "attrs": dict(self.attrs),
            "events": [],
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        })


def anchor(trace_id: str, name: str, **attrs):
    """A lazy compile-trace anchor for the runner's batch/watermark hot
    paths. Inert when telemetry is off or a real trace context is already
    ambient (checkpoint captures: compiles parent there instead)."""
    from . import enabled as obs_enabled

    if not obs_enabled() or not enabled() or trace.current() is not None:
        return NULL_ANCHOR
    return _Anchor(trace_id, name, attrs)


# -- summary ------------------------------------------------------------------


def summary() -> dict:
    """Structured device-telemetry summary for /debug/latency and
    tools/trace_report.py: per-program compile/dispatch stats, padding
    gauges, and the recompile-cause log."""
    from ..metrics import REGISTRY, hist_quantiles

    snap = REGISTRY.snapshot()

    def by_program(name: str) -> Dict[str, Any]:
        return {
            labels.get("program", "?"): value
            for labels, value in snap.get(name, [])
        }

    programs: Dict[str, dict] = {}
    for prog, v in by_program("arroyo_xla_compiles_total").items():
        programs.setdefault(prog, {})["compiles"] = int(v)
    for prog, h in by_program("arroyo_xla_compile_seconds").items():
        programs.setdefault(prog, {})["compile_s_total"] = round(
            h.get("sum", 0.0), 4)
    for prog, h in by_program("arroyo_device_dispatch_seconds").items():
        p = programs.setdefault(prog, {})
        p["dispatches"] = int(h.get("count", 0))
        p["dispatch_s_total"] = round(h.get("sum", 0.0), 4)
        p["dispatch_quantiles"] = {
            q: round(v, 6) for q, v in hist_quantiles(h).items()
        }
    for prog, h in by_program("arroyo_device_exchange_seconds").items():
        p = programs.setdefault(prog, {})
        p["exchange_dispatches"] = int(h.get("count", 0))
        p["exchange_s_total"] = round(h.get("sum", 0.0), 4)
        p["exchange_quantiles"] = {
            q: round(v, 6) for q, v in hist_quantiles(h).items()
        }
    for prog, (real, padded) in list(_ROWS.items()):
        if padded:
            p = programs.setdefault(prog, {})
            p["rows"] = real
            p["padded_rows"] = padded
    for labels, v in snap.get("arroyo_xla_compile_cache_total", []):
        p = programs.setdefault(labels.get("program", "?"), {})
        p[f"cache_{labels.get('result', '?')}"] = int(v)
    padding = [
        {"program": labels.get("program"), "rung": labels.get("rung"),
         "waste": v}
        for labels, v in snap.get("arroyo_device_padding_waste", [])
    ]
    padding.sort(key=lambda e: (e["program"], int(e["rung"] or 0)))
    # fused-segment ledger (engine/segments.py): per-segment dispatch
    # stats plus the fused-op count — what the mesh_profile BASELINE
    # ledger renders as per-segment rows (a segment runs on the host)
    segments: Dict[str, dict] = {}
    for labels, h in snap.get("arroyo_segment_dispatch_seconds", []):
        s = segments.setdefault(labels.get("program", "?"), {})
        s["host_dispatches"] = int(h.get("count", 0))
        s["host_s_total"] = round(h.get("sum", 0.0), 4)
        s["host_quantiles"] = {
            q: round(v, 6) for q, v in hist_quantiles(h).items()
        }
    for labels, v in snap.get("arroyo_segment_fused_ops", []):
        s = segments.setdefault(labels.get("program", "?"), {})
        s["fused_ops"] = int(v)
    return {
        "programs": programs,
        "padding_waste": padding,
        "segments": segments,
        "recompiles": recompile_log(),
    }
