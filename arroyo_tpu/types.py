"""Core substrate types: ids, time, watermarks, signals, checkpoint barriers.

Capability parity with the reference's `arroyo-types` crate
(/root/reference/crates/arroyo-types/src/lib.rs): Watermark (:176),
SignalMessage (:188), CheckpointBarrier (:500), TaskInfo (:391),
hash→partition range mapping (:640-661). Re-designed for a Python/JAX host
runtime: messages are lightweight dataclasses, data payloads are pyarrow
RecordBatches, and the hash-range math is vectorized with numpy so the same
partitioning is computable on host (shuffle) and on device (mesh shuffle).
"""

from __future__ import annotations

import dataclasses
import enum
import time as _time
import uuid
from typing import Optional, Union

import numpy as np
import pyarrow as pa

# ---------------------------------------------------------------------------
# Ids
# ---------------------------------------------------------------------------


def gen_id(prefix: str) -> str:
    return f"{prefix}_{uuid.uuid4().hex[:12]}"


@dataclasses.dataclass(frozen=True)
class JobId:
    id: str

    def __str__(self) -> str:
        return self.id


@dataclasses.dataclass(frozen=True)
class WorkerId:
    id: int

    def __str__(self) -> str:
        return str(self.id)


# ---------------------------------------------------------------------------
# Time — event time is int64 nanoseconds since the unix epoch, matching the
# reference's TimestampNanosecond `_timestamp` column.
# ---------------------------------------------------------------------------

NANOS_PER_SEC = 1_000_000_000
NANOS_PER_MILLI = 1_000_000
NANOS_PER_MICRO = 1_000


def now_nanos() -> int:
    return _time.time_ns()


def to_nanos(seconds: float) -> int:
    return int(round(seconds * NANOS_PER_SEC))


def from_nanos(nanos: int) -> float:
    return nanos / NANOS_PER_SEC


def to_millis(nanos: int) -> int:
    return nanos // NANOS_PER_MILLI


# ---------------------------------------------------------------------------
# Watermarks & signals
# ---------------------------------------------------------------------------


class WatermarkKind(enum.Enum):
    EVENT_TIME = "event_time"
    IDLE = "idle"


@dataclasses.dataclass(frozen=True)
class Watermark:
    """Event-time watermark. `IDLE` marks a quiet input that should not hold
    back the min-merge (reference: arroyo-types Watermark::Idle)."""

    kind: WatermarkKind
    timestamp: Optional[int] = None  # nanos; None for IDLE

    @staticmethod
    def event_time(ts: int) -> "Watermark":
        return Watermark(WatermarkKind.EVENT_TIME, ts)

    @staticmethod
    def idle() -> "Watermark":
        return Watermark(WatermarkKind.IDLE, None)

    def is_idle(self) -> bool:
        return self.kind == WatermarkKind.IDLE


# u64::MAX analogue: the "end of time" watermark emitted on EndOfData so that
# all windows flush (reference: watermark_generator.rs on_close).
WATERMARK_END = (1 << 63) - 1


@dataclasses.dataclass(frozen=True)
class CheckpointBarrier:
    epoch: int
    min_epoch: int
    timestamp: int  # nanos when initiated
    then_stop: bool = False
    # flight-recorder trace context (obs): the controller mints one trace
    # per epoch; span_id is rewritten at each hop (worker fan-out, subtask
    # re-broadcast) so downstream alignment spans parent to their causal
    # predecessor. Empty strings = untraced barrier (obs disabled).
    trace_id: str = ""
    span_id: str = ""

    def with_span(self, span_id: str) -> "CheckpointBarrier":
        """The barrier re-broadcast downstream, parented to this hop."""
        if not self.trace_id:
            return self
        return dataclasses.replace(self, span_id=span_id)


class SignalKind(enum.Enum):
    BARRIER = "barrier"
    WATERMARK = "watermark"
    STOP = "stop"
    END_OF_DATA = "end_of_data"
    LATENCY_MARKER = "latency_marker"


@dataclasses.dataclass(frozen=True)
class LatencyMarker:
    """Flink-style latency marker (flink FLIP-27 LatencyMarker): sources
    stamp one periodically with their wall clock; it flows through queues
    and the TCP exchange like a watermark but never blocks barrier
    alignment and never touches event time. Every operator (and the sink)
    records `now - stamp_ns` into its latency histogram, so the marker's
    transit time IS the end-to-end record latency up to that operator."""

    source_task: str  # task_id of the stamping source subtask
    seq: int
    stamp_ns: int  # wall-clock nanos at the stamping source


@dataclasses.dataclass(frozen=True)
class SignalMessage:
    """Control signals that flow *in-band* through the dataflow edges,
    interleaved with data batches (reference: arroyo-types SignalMessage)."""

    kind: SignalKind
    watermark: Optional[Watermark] = None
    barrier: Optional[CheckpointBarrier] = None
    marker: Optional[LatencyMarker] = None

    @staticmethod
    def barrier_of(b: CheckpointBarrier) -> "SignalMessage":
        return SignalMessage(SignalKind.BARRIER, barrier=b)

    @staticmethod
    def watermark_of(w: Watermark) -> "SignalMessage":
        return SignalMessage(SignalKind.WATERMARK, watermark=w)

    @staticmethod
    def marker_of(m: LatencyMarker) -> "SignalMessage":
        return SignalMessage(SignalKind.LATENCY_MARKER, marker=m)

    @staticmethod
    def stop() -> "SignalMessage":
        return SignalMessage(SignalKind.STOP)

    @staticmethod
    def end_of_data() -> "SignalMessage":
        return SignalMessage(SignalKind.END_OF_DATA)


# A message on a dataflow edge is either data (pyarrow.RecordBatch) or a
# signal. We avoid a wrapper class on the data path — isinstance dispatch on
# the hot loop is cheaper than an envelope object per batch.
ArrowMessage = Union["pyarrow.RecordBatch", SignalMessage]  # noqa: F821


# ---------------------------------------------------------------------------
# Task identity
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TaskInfo:
    job_id: str
    node_id: int  # logical node id
    operator_name: str
    task_index: int  # subtask index within the logical node
    parallelism: int

    @property
    def task_id(self) -> str:
        return f"{self.node_id}-{self.task_index}"

    def key_range(self) -> range:
        """The hash-range this subtask owns (for state sharding)."""
        lo, hi = range_for_server(self.task_index, self.parallelism)
        return range(lo, hi)


class JoinType(enum.Enum):
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    FULL = "full"


class StopMode(enum.Enum):
    GRACEFUL = "graceful"  # stop signal flows through the dataflow
    IMMEDIATE = "immediate"  # tear down now


# ---------------------------------------------------------------------------
# Hash-range partitioning.
#
# The u64 hash space is divided into `n` equal consecutive ranges; both the
# keyed shuffle and state sharding use the same mapping, so rescaling is a
# restore-time re-read of overlapping ranges (reference:
# arroyo-types/src/lib.rs:640-661 server_for_hash / range_for_server).
# ---------------------------------------------------------------------------

_U64 = 1 << 64


def _range_size(n: int) -> int:
    return (_U64 + n - 1) // n  # ceil(2^64 / n)


def range_for_server(i: int, n: int) -> tuple[int, int]:
    """[start, end) of the hash range owned by partition i of n."""
    size = _range_size(n)
    start = i * size
    end = _U64 if i == n - 1 else min((i + 1) * size, _U64)
    return start, end


def server_for_hash(h: int, n: int) -> int:
    return min(int(h) // _range_size(n), n - 1)


def server_for_hash_array(hashes: np.ndarray, n: int) -> np.ndarray:
    """Vectorized hash→partition mapping for a uint64 hash column."""
    if n == 1:
        return np.zeros(len(hashes), dtype=np.int64)
    size = _range_size(n)
    out = (hashes // np.uint64(size)).astype(np.int64)
    np.minimum(out, n - 1, out=out)
    return out


# ---------------------------------------------------------------------------
# Hashing of key columns. One canonical 64-bit hash used by the shuffle, the
# state key-ranges and the device-side kernels. We use the splitmix64-style
# finalizer over per-column hashes, combined with multiply-rotate; columns of
# string/binary type are hashed via pandas' vectorized siphash
# (pandas.util.hash_array) which is deterministic for a fixed hash_key.
#
# A string's hash is a function of its bytes and the key alone, so an Arrow
# string column is hashed once per DISTINCT value (`hash_string_array`: the
# column's dictionary through `hash_column`, then a take by the indices)
# where the distinct values are few, and row by row through an object array
# where they are not. Both routes give every row the same uint64, bit for
# bit: digests, shuffle owners and state key ranges do not depend on the
# route, only the cost does.
# ---------------------------------------------------------------------------

HASH_SEED = np.uint64(0x243F6A8885A308D3)  # fixed so checkpoints are portable

_PANDAS_HASH_KEY = "arroyo_tpu_hash0"  # must be exactly 16 bytes


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def hash_arrays(columns: list[np.ndarray]) -> np.ndarray:
    """Combine pre-hashed (uint64) per-column arrays into one hash column."""
    out = np.full(len(columns[0]), HASH_SEED, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for col in columns:
            out = _splitmix64(out ^ col)
    return out


def hash_column(values) -> np.ndarray:
    """Hash one column (numpy array or list) to uint64."""
    arr = np.asarray(values)
    if arr.dtype.kind in ("i", "u", "b"):
        return _splitmix64(arr.astype(np.uint64, copy=False))
    if arr.dtype.kind == "f":
        # normalize -0.0 == 0.0 before bit-hashing
        arr = arr + 0.0
        return _splitmix64(arr.view(np.uint64) if arr.dtype == np.float64
                           else arr.astype(np.float64).view(np.uint64))
    if arr.dtype.kind == "M":  # datetime64
        return _splitmix64(arr.view("i8").astype(np.uint64))
    # only object/string columns need pandas; importing it eagerly cost
    # ~0.3s INSIDE the first shuffle of integer-keyed pipelines
    import pandas.util  # local import: pandas is heavy

    return pandas.util.hash_array(
        arr.astype(object), hash_key=_PANDAS_HASH_KEY, categorize=False
    ).astype(np.uint64)


# what a null hashes as in a column of strings (and of any other type with
# no numeric sentinel: `schema._null_sentinel`)
NULL_STRING = "\x00__null__"

# `hash_string_array` encodes the first sixteenth of a column before the
# whole: values drawn evenly from n/2 distinct ones show about 15/16 of such
# a prefix distinct, and from there on a dictionary costs more than it saves
# (on 8,192 rows the two routes meet near 3,500 distinct values); the probe
# costs a few hundredths of the row-by-row route
_PROBE_SHARE = 16
_PROBE_DISTINCT = 15 / 16
# under this many rows the encoding's fixed cost (tens of microseconds)
# is more than the per-row hashes it could save
_DICTIONARY_MIN_ROWS = 256


def is_string_type(t: pa.DataType) -> bool:
    """`string` / `large_string`, plain or as a dictionary's values: the
    columns `hash_string_array` takes."""
    if pa.types.is_dictionary(t):
        t = t.value_type
    return pa.types.is_string(t) or pa.types.is_large_string(t)


def hash_string_array(col: pa.Array) -> tuple[np.ndarray, bool]:
    """(`hash_column` of every row of a string column with its nulls
    replaced by `NULL_STRING`, whether it went through a dictionary).

    Through the dictionary: one siphash per distinct value, then a take
    by the indices; a null takes the index of `NULL_STRING`, appended to
    the values. A column that arrives dictionary-typed uses its own
    dictionary. The dictionary pays only while it is short, which is
    decided from the data, a prefix first: a column of a few rows, one
    whose prefix is nearly all distinct, or one more than half distinct
    goes row by row through an object array. Same key, same bytes per
    value: the same uint64s either way, so the choice moves a cost and
    never a result."""
    n = len(col)
    if n < _DICTIONARY_MIN_ROWS:
        return _hash_strings_by_row(col), False
    if pa.types.is_dictionary(col.type):
        enc = col
    else:
        head = col.slice(0, n // _PROBE_SHARE).dictionary_encode()
        if len(head.dictionary) > _PROBE_DISTINCT * len(head):
            return _hash_strings_by_row(col), False
        enc = col.dictionary_encode()
    values = enc.dictionary
    if 2 * len(values) >= n:
        return _hash_strings_by_row(col), False
    if values.null_count:  # only a dictionary made elsewhere holds nulls
        values = values.fill_null(NULL_STRING)
    distinct = np.empty(len(values) + 1, dtype=object)
    distinct[:-1] = values.to_numpy(zero_copy_only=False)
    distinct[-1] = NULL_STRING
    hashes = hash_column(distinct)
    idx = enc.indices
    if idx.null_count:
        idx = idx.fill_null(len(values))
    return hashes.take(idx.to_numpy()), True


def _hash_strings_by_row(col: pa.Array) -> np.ndarray:
    if pa.types.is_dictionary(col.type):
        col = col.dictionary_decode()
    if col.null_count:
        col = col.fill_null(NULL_STRING)
    return hash_column(col.to_numpy(zero_copy_only=False))
