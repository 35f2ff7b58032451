"""State table implementations.

Capability parity with the reference's table kinds
(/root/reference/crates/arroyo-state/src/tables/):
  * GlobalKeyedTable (global_keyed_map.rs:47): small KV, each subtask writes
    its entries; on restore every subtask sees the union (replication), so
    rescaled operators can filter by key range themselves.
  * ExpiringTimeKeyTable (expiring_time_key_map.rs:53): RecordBatch rows
    bucketed by event time, retention-pruned, key-range filtered on restore;
    checkpoints are incremental (only rows added since the last epoch are
    written; the cumulative live-file list rides in the metadata).
Values are msgpack-encoded (the reference uses bincode).

State-at-scale extensions (ROADMAP item 4):
  * GlobalTable checkpoints are incremental: put/delete mark dirty keys and
    tombstones, serialize_delta emits only the changed entries, and the
    manifest carries a blob *chain* (base + deltas) per (table, subtask)
    that restore replays in epoch order. Entries are epoch-stamped so the
    cross-subtask union is deterministic: replication re-persists every
    subtask's view, and without stamps a STALE copy of key k (written by a
    peer that restored it long ago) could win the restore merge over the
    owner's fresh value depending on blob load order.
  * TimeKeyTable has a disk spill tier: once in-memory batches exceed
    `state.memory_budget_bytes`, the coldest batches (lowest max event
    time) are spooled to local Arrow-IPC spill files and memory-mapped
    back only when expiry/emission/restore needs them. Spilled rows are
    checkpoint-free — the cumulative live-file list already persisted
    them — so spill bounds RAM without touching the durability story.
"""

from __future__ import annotations

import os
import struct
import tempfile
import threading
import uuid
from typing import Any, Dict, List, Optional, Tuple

import msgpack
import numpy as np
import pyarrow as pa

from ..types import server_for_hash_array
from ..utils.logging import get_logger
from .table_config import TableConfig

logger = get_logger("state.tables")

_DEAD = object()  # merge-time tombstone marker


class Deferred:
    """A value computed later, once: what a capture stages at the barrier
    for the flush thread to resolve, as a time-key delta stages a thunk.
    The barrier fixes the inputs (immutable, held by `fn`); `resolve()`
    runs `fn` the first time anyone asks, under a lock, and lets it go.
    `rows` and `nbytes` are what the barrier knew of the inputs: for the
    ledger's `flush.resolve` and for the rebase policy while the bytes
    themselves are not there yet (`nbytes` is their length once they
    are). A `fn` that raises stays: the next `resolve()` raises again."""

    __slots__ = ("_fn", "_value", "_lock", "rows", "nbytes")

    def __init__(self, fn, rows: int = 0, nbytes: int = 0):
        self._fn = fn
        self._value = None
        self._lock = threading.Lock()
        self.rows = rows
        self.nbytes = nbytes

    def resolve(self):
        if self._fn is not None:
            with self._lock:
                if self._fn is not None:
                    self._value = self._fn()
                    self._fn = None
                    if isinstance(self._value, (bytes, memoryview)):
                        self.nbytes = len(self._value)
        return self._value

    __call__ = resolve

    @property
    def pending(self) -> bool:
        return self._fn is not None


def resolved(value):
    return value.resolve() if isinstance(value, Deferred) else value


class GlobalTable:
    """KV map; put/get are synchronous in-memory, persistence happens at
    checkpoint via incremental delta blobs (serialize_delta). A value may
    be a `Deferred`: `get` and `items` resolve it, `raw` hands it back as
    it is, and a capture that holds one packs its blob on the flush."""

    def __init__(self, config: TableConfig):
        self.config = config
        self.data: Dict[Any, Any] = {}
        self.restored: Dict[Any, Any] = {}  # union of all subtasks' entries
        # epoch each key's entry last changed (loaded from blobs; dirty
        # keys are stamped at capture) — the restore-merge tie breaker
        self._stamps: Dict[Any, int] = {}
        # keys whose restore-merge candidate is currently a tombstone
        self._restore_tombs: Dict[Any, int] = {}
        # keys present at load time: a delete of one of these needs its
        # tombstone carried in the next BASE too (a peer's base may still
        # hold a stale copy); keys born and deleted within this
        # incarnation never left this process, so their tombstones can be
        # dropped once the chain rebases
        self._restored_keys: set = set()
        self._dirty: set = set()
        self._dead: Dict[Any, Optional[int]] = {}  # key -> tombstone epoch
        self._has_base = False
        self._approx_bytes = 0  # last serialized size (obs)

    def get(self, key, default=None):
        return resolved(self.raw(key, default))

    def raw(self, key, default=None):
        if key in self.data:
            return self.data[key]
        return self.restored.get(key, default)

    def put(self, key, value):
        self.data[key] = value
        self._dirty.add(key)
        self._dead.pop(key, None)

    def delete(self, key):
        existed = key in self.data or key in self.restored
        self.data.pop(key, None)
        self.restored.pop(key, None)
        self._dirty.discard(key)
        if existed:
            self._dead[key] = None  # stamped at the next capture

    def retain(self, pred):
        """Drop every key where pred(key) is false, WITHOUT tombstones:
        the caller asserts those keys are owned (and re-persisted) by
        other subtasks — rescale-aware keyed operators call this after
        restore so each subtask's chain only carries its own key range
        (which also lets rebase drop tombstones for churned keys)."""
        for k in [k for k in self.data if not pred(k)]:
            del self.data[k]
            self._dirty.discard(k)
        for k in [k for k in self.restored if not pred(k)]:
            del self.restored[k]
            self._stamps.pop(k, None)
            self._restored_keys.discard(k)

    def all_values(self) -> List[Any]:
        """Union view (restored entries from every subtask + local writes);
        used by rescale-aware operators to re-filter by key range."""
        return list(self._merged().values())

    def items(self):
        return self._merged().items()

    def _merged(self, resolve: bool = True) -> dict:
        merged = dict(self.restored)
        merged.update(self.data)
        if resolve:
            for k, v in merged.items():
                if isinstance(v, Deferred):
                    merged[k] = v.resolve()
        return merged

    def state_size(self) -> Tuple[int, int]:
        """(approx bytes as of the last serialization, live entries)."""
        return self._approx_bytes, len(self.restored | self.data)

    # -- persistence --------------------------------------------------------

    def serialize(self) -> bytes:
        """Full-snapshot view (legacy/debug; does NOT clear dirty state)."""
        return msgpack.packb(
            {"v": 2, "b": True,
             "e": [[k, v, self._stamps.get(k, 0)]
                   for k, v in self._merged().items()],
             "t": []},
            use_bin_type=True,
        )

    def serialize_delta(self, epoch: int, force_base: bool = False):
        """Capture this epoch's blob: (blob, is_base).

        The first capture of an incarnation (or a rebase) emits a base —
        the full merged map; afterwards only dirty entries + tombstones
        ride, so capture cost is O(dirty), not O(total). Returns
        (None, False) when nothing changed (the chain is reused as-is).
        Clears the dirty/tombstone sets: the caller owns flushing the
        blob (a failed flush fails the task, and recovery restores from
        the last published manifest)."""
        for k in self._dirty:
            self._stamps[k] = epoch
        for k, st in self._dead.items():
            if st is None:
                self._dead[k] = epoch
        if force_base or not self._has_base:
            # tombstones survive a rebase only for keys that predate this
            # incarnation (a peer's stale copy may still carry them)
            tombs = [
                [k, st] for k, st in self._dead.items()
                if k in self._restored_keys
            ]
            entries = [[k, v, self._stamps.get(k, epoch)]
                       for k, v in self._merged(resolve=False).items()]
            self._dirty.clear()
            self._dead.clear()
            self._has_base = True
            return self._pack(True, entries, tombs), True
        if not self._dirty and not self._dead:
            return None, False
        entries = []
        for k in self._dirty:
            if k in self.data:
                entries.append([k, self.data[k], self._stamps[k]])
            elif k in self.restored:
                entries.append([k, self.restored[k], self._stamps[k]])
        tombs = [[k, st] for k, st in self._dead.items()]
        self._dirty.clear()
        self._dead.clear()
        return self._pack(False, entries, tombs), False

    def _pack(self, base: bool, entries: list, tombs: list):
        """One capture's blob: bytes, or a `Deferred` of the same bytes
        where an entry's value is one (its `rows` the entries' together,
        its `nbytes` what the barrier knows of their sizes)."""
        late = [e[1] for e in entries if isinstance(e[1], Deferred)]

        if not late:
            blob = msgpack.packb(
                {"v": 2, "b": base, "e": entries, "t": tombs},
                use_bin_type=True,
            )
            if base:
                self._approx_bytes = len(blob)
            return blob

        def pack() -> memoryview:
            blob = _packb_beside_the_loop(
                base, [[k, resolved(v), st] for k, v, st in entries], tombs)
            if base:
                self._approx_bytes = len(blob)
            return blob

        return Deferred(pack, rows=sum(v.rows for v in late if v.pending),
                        nbytes=sum(v.nbytes for v in late))

    def load(self, blobs: List[bytes]):
        """Legacy entry: one flat list of blobs (treated as one chain)."""
        self.load_chain(blobs)

    def load_chain(self, blobs: List[bytes]):
        """Replay ONE subtask's blob chain in epoch order, merging into
        the union view. Cross-chain conflicts (replicated stale copies)
        resolve by entry stamp: the highest stamp wins; a tombstone kills
        entries up to its stamp. Call once per subtask chain."""
        for blob in blobs:
            obj = msgpack.unpackb(blob, raw=False, strict_map_key=False)
            if isinstance(obj, list):
                # pre-chain format: [[k, v], ...] full snapshot, stamp 0
                for k, v in obj:
                    self._merge_entry(_hashable(k), v, 0)
                continue
            for ent in obj.get("e", ()):
                k, v, stamp = ent[0], ent[1], ent[2] if len(ent) > 2 else 0
                self._merge_entry(_hashable(k), v, stamp)
            for k, stamp in obj.get("t", ()):
                self._merge_tomb(_hashable(k), stamp)
        self._restored_keys = set(self.restored)

    def _merge_entry(self, k, v, stamp: int):
        if self._restore_tombs.get(k, -1) > stamp:
            return  # deleted later than this entry was written
        if k in self.restored and self._stamps.get(k, 0) > stamp:
            return  # a fresher replica already merged
        self._restore_tombs.pop(k, None)
        self.restored[k] = v
        self._stamps[k] = stamp

    def _merge_tomb(self, k, stamp: int):
        if k in self.restored and self._stamps.get(k, 0) > stamp:
            return  # entry re-written after the delete
        self.restored.pop(k, None)
        self._stamps.pop(k, None)
        if stamp > self._restore_tombs.get(k, -1):
            self._restore_tombs[k] = stamp


def _hashable(k):
    return tuple(_hashable(x) for x in k) if isinstance(k, list) else k


# a binary value from this size up is copied into a blob outside the GIL
# (and from this size up msgpack's header for it is always a bin 32)
_BIG_VALUE = 1 << 16


def _packb_beside_the_loop(base: bool, entries: list,
                           tombs: list) -> memoryview:
    """`msgpack.packb({"v": 2, "b": base, "e": entries, "t": tombs},
    use_bin_type=True)` byte for byte (as a bytes-like view of a numpy
    array), for the flush thread: msgpack frames, and each large binary
    value is copied in by numpy, which lets the GIL go meanwhile.
    (`packb` copies a value twice while it holds the GIL: 0.46 s for a
    180 MB serve segment on the chip's host, 1.7 s for a base of several,
    during which the engine's thread waits: my chip runs, PR 39.)"""
    pack = msgpack.Packer(use_bin_type=True).pack
    parts = [b"\x84", pack("v"), pack(2), pack("b"), pack(base), pack("e"),
             msgpack.Packer().pack_array_header(len(entries))]
    for k, v, stamp in entries:
        parts += [b"\x93", pack(k)]
        if isinstance(v, (bytes, memoryview)) and len(v) >= _BIG_VALUE:
            if len(v) > 0xFFFFFFFF:
                raise ValueError("a binary value over 4 GiB does not fit "
                                 "a msgpack bin")
            parts += [b"\xc6" + struct.pack(">I", len(v)), v]  # bin 32
        else:
            parts.append(pack(v))
        parts.append(pack(stamp))
    parts += [pack("t"), pack(tombs)]
    # (not a bytearray: it would zero its pages first, holding the GIL)
    blob, at = np.empty(sum(map(len, parts)), dtype=np.uint8), 0
    for part in parts:
        blob[at:at + len(part)] = np.frombuffer(part, dtype=np.uint8)
        at += len(part)
    return memoryview(blob)


# -- time-key spill tier ------------------------------------------------------


_SPILL_DIR: Optional[str] = None


def _spill_dir() -> str:
    """Per-process spill scratch directory (state.spill_dir or tempdir)."""
    global _SPILL_DIR
    if _SPILL_DIR is None:
        from ..config import config

        base = config().state.spill_dir or os.path.join(
            tempfile.gettempdir(), "arroyo-tpu-spill"
        )
        _SPILL_DIR = os.path.join(base, f"pid{os.getpid()}")
        os.makedirs(_SPILL_DIR, exist_ok=True)
    return _SPILL_DIR


def _batch_nbytes(batch: pa.RecordBatch) -> int:
    try:
        return batch.nbytes
    except Exception:  # noqa: BLE001 - exotic buffers
        return batch.num_rows * 64


class _Entry:
    """One buffered batch + its event-time metadata. `batch` is None once
    spilled; `path` points at the Arrow-IPC spill file then."""

    __slots__ = ("batch", "path", "min_ts", "max_ts", "rows", "nbytes")

    def __init__(self, batch: pa.RecordBatch, min_ts: int, max_ts: int):
        self.batch: Optional[pa.RecordBatch] = batch
        self.path: Optional[str] = None
        self.min_ts = min_ts
        self.max_ts = max_ts
        self.rows = batch.num_rows
        self.nbytes = _batch_nbytes(batch)

    @property
    def spilled(self) -> bool:
        return self.batch is None

    def spill(self) -> int:
        """Write the batch to an Arrow-IPC file and drop the in-memory
        reference. Returns the bytes released."""
        if self.batch is None:
            return 0
        path = os.path.join(_spill_dir(), f"spill-{uuid.uuid4().hex}.arrow")
        with pa.OSFile(path, "wb") as f:
            with pa.ipc.new_file(f, self.batch.schema) as w:
                w.write_batch(self.batch)
        self.path = path
        self.batch = None
        return self.nbytes

    def load(self) -> pa.RecordBatch:
        """Materialize: memory-map the spill file (zero-copy; the OS pages
        rows in on demand) — spilled entries stay spilled (reading for an
        expiry scan or checkpoint must not re-inflate the budget)."""
        if self.batch is not None:
            return self.batch
        with pa.memory_map(self.path, "rb") as src:
            reader = pa.ipc.open_file(src)
            batches = [reader.get_batch(i) for i in range(reader.num_record_batches)]
        if len(batches) == 1:
            return batches[0]
        return pa.Table.from_batches(batches).combine_chunks().to_batches()[0]

    def unspill(self, batch: pa.RecordBatch):
        """Bring the entry back in-memory (post-restore rebuffering)."""
        self.batch = batch
        self.drop_file()

    def drop_file(self):
        if self.path is not None:
            try:
                os.unlink(self.path)
            except OSError:
                pass
            self.path = None

    def __del__(self):  # best-effort scratch cleanup
        self.drop_file()


class TimeKeyTable:
    """Event-time bucketed RecordBatch store with retention.

    In-memory view is the source of truth while running; checkpoints write
    the *delta* since the previous epoch as parquet and carry the cumulative
    file list forward, dropping files whose max_ts fell behind
    watermark - retention. Batches beyond `state.memory_budget_bytes`
    spill coldest-first to local Arrow-IPC files (see module docstring).
    """

    def __init__(self, config: TableConfig, stream_schema=None):
        from ..config import config as get_config

        self.config = config
        self.schema: Optional[pa.Schema] = None
        self._entries: List[_Entry] = []
        self._dirty: List[pa.RecordBatch] = []
        # carried checkpoint file metadata: [{"path", "min_ts", "max_ts"}]
        self.files: List[dict] = []
        st = get_config().state
        self._budget = int(st.memory_budget_bytes)
        self._compact_fraction = float(st.expire_compact_fraction)
        self._mem_bytes = 0
        self._spilled_bytes = 0

    # -- ingestion ----------------------------------------------------------

    def insert(self, batch: pa.RecordBatch, stage_dirty: bool = True):
        """Buffer a batch in the in-memory view (spilling cold state past
        the budget); by default also stage it for the next checkpoint
        delta. stage_dirty=False re-buffers rows that are already durable
        (restore, operator-internal moves)."""
        if batch.num_rows == 0:
            return
        if self.schema is None:
            self.schema = batch.schema
        ts = self._ts(batch)
        entry = _Entry(batch, int(ts.min()), int(ts.max()))
        self._entries.append(entry)
        self._mem_bytes += entry.nbytes
        if stage_dirty:
            self._dirty.append(batch)
        self._maybe_spill()

    def write_delta(self, batch):
        """Conduit write: stage a delta for the next checkpoint WITHOUT
        keeping it in the in-memory view. Operators whose in-memory source
        of truth lives elsewhere (accumulator slots, join buffers) use this
        so state isn't held twice. `batch` may be a RecordBatch or a
        zero-arg callable returning one — a thunk defers materialization
        (e.g. a dispatched device->host gather) to the flush phase."""
        if not callable(batch) and self.schema is None:
            self.schema = batch.schema
        self._dirty.append(batch)

    def prune_dirty(self, pred):
        """Drop staged (non-thunk) deltas failing pred(batch) — operators
        use it to skip persisting rows already emitted this epoch."""
        self._dirty = [
            b for b in self._dirty if callable(b) or pred(b)
        ]

    # -- views --------------------------------------------------------------

    def all_batches(self) -> List[pa.RecordBatch]:
        return [e.load() for e in self._entries]

    def entry_stats(self) -> Tuple[int, int, int, int]:
        """(in-memory bytes, spilled bytes, rows, batches) for obs."""
        rows = sum(e.rows for e in self._entries)
        return self._mem_bytes, self._spilled_bytes, rows, len(self._entries)

    def clear_batches(self):
        """Drop the in-memory view (conduit operators own the rows after
        restore); releases spill scratch files."""
        for e in self._entries:
            e.drop_file()
        self._entries = []
        self._mem_bytes = 0
        self._spilled_bytes = 0

    def take_bins_upto(self, cutoff: int) -> List[Tuple[int, pa.RecordBatch]]:
        """Pop every row with timestamp <= cutoff, returned as (ts, batch)
        bins sorted by ts (spilled entries are memory-mapped back only
        here — exactly when emission needs them). Rows above the cutoff
        stay buffered; entries wholly above it are never materialized."""
        out: List[Tuple[int, pa.RecordBatch]] = []
        keep: List[_Entry] = []
        for e in self._entries:
            if e.min_ts > cutoff:
                keep.append(e)
                continue
            batch = e.load()
            if e.spilled:
                self._spilled_bytes -= e.nbytes
            else:
                self._mem_bytes -= e.nbytes
            e.drop_file()
            ts = self._ts(batch)
            if e.max_ts > cutoff:
                live = ts > cutoff
                rest = batch.filter(pa.array(live))
                if rest.num_rows:
                    rts = ts[live]
                    e2 = _Entry(rest, int(rts.min()), int(rts.max()))
                    self._mem_bytes += e2.nbytes
                    keep.append(e2)
                batch = batch.filter(pa.array(~live))
                ts = ts[~live]
            out.extend(_split_by_ts(batch, ts))
        self._entries = keep
        self._maybe_spill()
        out.sort(key=lambda p: p[0])
        return out

    # -- retention ----------------------------------------------------------

    def expire(self, watermark_nanos: Optional[int]):
        """Drop whole batches whose max timestamp fell out of retention;
        batches mostly-dead but pinned by a live max timestamp are
        compacted row-level once their expired fraction exceeds
        `state.expire_compact_fraction` (long-retention skew otherwise
        keeps dead rows in RAM indefinitely)."""
        if watermark_nanos is None or self.config.retention_nanos is None:
            return
        cutoff = watermark_nanos - self.config.retention_nanos
        keep: List[_Entry] = []
        for e in self._entries:
            if e.max_ts < cutoff:
                # fully expired: drop without materializing
                if e.spilled:
                    self._spilled_bytes -= e.nbytes
                else:
                    self._mem_bytes -= e.nbytes
                e.drop_file()
                continue
            if (
                not e.spilled
                and e.min_ts < cutoff
                and self._compact_fraction <= 1.0
                and e.rows
            ):
                ts = self._ts(e.batch)
                mask = ts >= cutoff
                dead_frac = 1.0 - (mask.sum() / e.rows)
                if dead_frac > self._compact_fraction:
                    self._mem_bytes -= e.nbytes
                    filtered = e.batch.filter(pa.array(mask))
                    e2 = _Entry(filtered, int(ts[mask].min()),
                                e.max_ts)
                    self._mem_bytes += e2.nbytes
                    keep.append(e2)
                    continue
            keep.append(e)
        self._entries = keep

    def filter_expired(self, watermark_nanos: Optional[int]):
        """Row-level expiry (used on restore)."""
        if watermark_nanos is None or self.config.retention_nanos is None:
            return
        cutoff = watermark_nanos - self.config.retention_nanos
        out: List[_Entry] = []
        for e in self._entries:
            if e.min_ts >= cutoff:
                out.append(e)
                continue
            if e.max_ts < cutoff:
                if e.spilled:
                    self._spilled_bytes -= e.nbytes
                else:
                    self._mem_bytes -= e.nbytes
                e.drop_file()
                continue
            batch = e.load()
            ts = self._ts(batch)
            mask = ts >= cutoff
            if e.spilled:
                self._spilled_bytes -= e.nbytes
            else:
                self._mem_bytes -= e.nbytes
            e.drop_file()
            if mask.any():
                filtered = batch.filter(pa.array(mask))
                e2 = _Entry(filtered, int(ts[mask].min()), e.max_ts)
                self._mem_bytes += e2.nbytes
                out.append(e2)
        self._entries = out
        self._maybe_spill()

    def _maybe_spill(self):
        if not self._budget or self._mem_bytes <= self._budget:
            return
        # spill coldest-first (lowest max event time): expiry/emission
        # touches cold bins last... actually FIRST at drain time, but a
        # drain materializes them exactly once via mmap; the hot tail
        # (still being appended/probed) stays in RAM
        hot = sorted(
            (e for e in self._entries if not e.spilled),
            key=lambda e: e.max_ts,
        )
        for e in hot:
            if self._mem_bytes <= self._budget:
                break
            released = e.spill()
            self._mem_bytes -= released
            self._spilled_bytes += released
            logger.debug("spilled %d bytes (table %s)", released,
                         self.config.name)

    def _ts(self, batch: pa.RecordBatch) -> np.ndarray:
        idx = batch.schema.names.index(self.config.timestamp_field)
        return np.asarray(batch.column(idx).cast(pa.int64()))

    # -- persistence --------------------------------------------------------

    def take_dirty(self) -> Optional[pa.Table]:
        return self.resolve_staged(self.take_dirty_staged())

    def take_dirty_staged(self) -> list:
        """Detach the staged deltas without resolving thunks (capture
        phase; resolution — e.g. a pending device->host copy — happens in
        resolve_staged on the flush path)."""
        staged = self._dirty
        self._dirty = []
        return staged

    @staticmethod
    def resolve_staged(staged: list) -> Optional[pa.Table]:
        batches = []
        for b in staged:
            if callable(b):
                b = b()
            if b is not None and b.num_rows:
                batches.append(b)
        if not batches:
            return None
        return pa.Table.from_batches(batches)

    def live_files(self, watermark_nanos: Optional[int]) -> List[dict]:
        if watermark_nanos is None or self.config.retention_nanos is None:
            return list(self.files)
        cutoff = watermark_nanos - self.config.retention_nanos
        return [f for f in self.files if f["max_ts"] >= cutoff]

    def load_batches(self, batches: List[pa.RecordBatch], key_range=None,
                     key_indices: Optional[List[int]] = None,
                     parallelism: int = 1, task_index: int = 0):
        """Restore: ingest batches, filtering rows to this subtask's key
        range when key columns are declared (rescale support). Batches
        beyond the memory budget spill like live inserts."""
        from ..types import hash_arrays, hash_column

        for b in batches:
            if b.num_rows == 0:
                continue
            if self.config.key_fields and parallelism > 1:
                cols = []
                for name in self.config.key_fields:
                    i = b.schema.names.index(name)
                    col = b.column(i)
                    cols.append(hash_column(
                        col.to_numpy(zero_copy_only=False)))
                hashes = hash_arrays(cols)
                owners = server_for_hash_array(hashes, parallelism)
                mask = owners == task_index
                if not mask.any():
                    continue
                if not mask.all():
                    b = b.filter(pa.array(mask))
            if self.schema is None:
                self.schema = b.schema
            self.insert(b, stage_dirty=False)


def _split_by_ts(batch: pa.RecordBatch,
                 ts: np.ndarray) -> List[Tuple[int, pa.RecordBatch]]:
    """Split one batch into per-timestamp bins (stable order)."""
    if batch.num_rows == 0:
        return []
    uniq = np.unique(ts)
    if len(uniq) == 1:
        return [(int(uniq[0]), batch)]
    order = np.argsort(ts, kind="stable")
    sb = batch.take(pa.array(order))
    sts = ts[order]
    bounds = np.searchsorted(sts, uniq, side="left").tolist()
    bounds.append(len(sts))
    return [
        (int(t), sb.slice(bounds[i], bounds[i + 1] - bounds[i]))
        for i, t in enumerate(uniq)
    ]
