"""Worker-side serve views: epoch-consistent keyed read snapshots.

A `ServeView` hangs off one keyed operator instance (one per subtask)
and mirrors the operator's *emitted* aggregates as a key -> value map
with three layers:

  * `stage` — rows emitted since the last checkpoint barrier. Written
    by the operator's emission path (window results at watermark
    drains, updating-aggregate flushes); never visible to reads.
  * `pending[epoch]` — rows sealed at capture of `epoch` (the runner
    calls `seal_op` right after `handle_checkpoint`, i.e. at the exact
    point PR 8's `serialize_delta` stamps dirty state with the epoch).
  * `served` — the fold of every pending epoch <= the read's published
    epoch. Reads fold lazily, so the view needs no notification when
    the controller publishes a manifest: the published epoch rides in
    on each QueryState request from the gateway.

Representation (ISSUE 25): the view is columnar on its write side. A
window close hands `stage_batch` the Arrow batch it has just built, and
the stage keeps that batch as one *segment* (a list append). `seal`
files the interval's segments under the epoch as ONE layer, `seal_op`
mirrors that layer into the `__serve__` table as ONE entry of Arrow IPC
bytes, and folding appends layers to `served`, which compacts by a
vectorised keep-last-per-key. What the barrier must fix is WHICH rows
belong to the epoch, and staged segments are immutable batches: so a
stage that holds segments alone is detached and filed un-merged (O(1)
on the engine's thread), its merge happens once, under a lock, for
whoever asks first for the layer's table, and the mirror entry's bytes
are a `Deferred` (state/tables.py) that the checkpoint's flush thread
resolves before it writes the epoch's blob: the same merge, the same
bytes, beside the loop. A stage with a dict layer (rows staged one at a
time) is merged at the barrier, as ever: a dict layer's values are the
operator's own objects. A Python key tuple and
value dict exist only for the rows a read returns: a lookup goes newest
layer first through a per-segment index built on the first read that
touches the segment. Row-wise callers (`stage(key, value)`,
`stage_tomb`: updating aggregates, join row sets, session partials)
write dict layers into the same ordered sequence, so last-writer-wins
is one algorithm over two shapes of input. Once a view has been handed
a batch, every layer it seals is a segment (a dict layer is encoded:
values as msgpack bytes in `__row`, retractions in `__tomb`); a view
that only ever sees rows one at a time (updating aggregates, join row
sets) stays a dict per layer and mirrors one table entry per key.

Durability alignment: state the controller published at epoch P is
exactly what the operators had captured at P's barrier, so folding
pending epochs <= P reproduces the last durable view — a read can never
observe a half-captured epoch, a torn value, or (after recovery fenced
a generation) anything newer than the state the restore will replay.
Jobs WITHOUT durable state (no checkpoint barriers ever) run their
views in live mode: staged rows apply immediately and reads see the
latest emission, which is the only consistent level such a job has.

Routing: `owner_subtask` mirrors the engine's shuffle partitioning
exactly — per-column `types.hash_column` (splitmix64 / pandas siphash),
`hash_arrays` combine, `server_for_hash_array` hash-range map — so the
gateway's key -> subtask routing and a worker's local ownership check
agree with `parallel/sharded_state.py owners_for` by construction.
"""

from __future__ import annotations

import datetime
import threading
from typing import Any, Dict, List, Optional, Tuple

import msgpack
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..analysis.model.effects import protocol_effect
from ..config import config
from ..obs import timeline
from ..state.tables import Deferred, _batch_nbytes, resolved
from ..types import hash_arrays, hash_column, server_for_hash_array
from ..utils.logging import get_logger

logger = get_logger("serve")

_TOMB = object()  # sealed deletion marker (updating-aggregate retraction)

# Follower read replicas (ISSUE 20): every viewed operator on a durable
# job mirrors its sealed view rows into a dedicated `__serve__`
# GlobalTable. seal_op runs inside the runner's capture span BEFORE
# table_manager.capture, so mirror writes land in the SAME epoch's delta
# chain as the operator state they reflect — a follower tailing the
# published chains reconstructs exactly the view a worker serves at that
# published epoch. The reserved meta key carries the view's describe()
# so a follower can serve without the compiled program.
SERVE_TABLE = "__serve__"
META_KEY = "__serve_meta__"
# A view that holds segments mirrors each sealed epoch as ONE entry:
# Arrow IPC bytes under `__serve_seg__/<task>/<epoch>/<seq>`. Readers
# replay the entries in (epoch, seq) order; a string can never collide
# with a per-key entry, whose key is a tuple.
SEG_PREFIX = "__serve_seg__"
# reserved segment columns beside the key and value columns
_ROW = "__row"    # msgpack bytes of a value staged row-wise
_TOMBCOL = "__tomb"  # true = the key was retracted at this point
# `served` holds at most this many layers before it compacts into one,
# so a read never scans an unbounded list
_SERVED_SEGMENTS = 8
# a view's mirror holds at most this many segment entries; past it the
# oldest merge into one, leaving half, so the table stays O(distinct
# keys) and the base is rewritten once per `_MIRROR_SEGMENTS / 2` epochs
_MIRROR_SEGMENTS = 16

# key-column kinds: how request/staged values canonicalize + hash.
#   i = signed int / timestamp-as-int   u = unsigned int
#   f = float   s = string   o = other (unroutable; fan-out reads)
_KIND_DTYPE = {"i": np.int64, "u": np.uint64, "f": np.float64}
_KIND_ARROW = {"i": pa.int64(), "u": pa.uint64(), "f": pa.float64(),
               "s": pa.string()}


def _kind_of(arrow_type) -> str:
    if pa.types.is_unsigned_integer(arrow_type):
        return "u"
    if pa.types.is_integer(arrow_type) or pa.types.is_timestamp(arrow_type):
        return "i"
    if pa.types.is_floating(arrow_type):
        return "f"
    if pa.types.is_string(arrow_type) or pa.types.is_large_string(arrow_type):
        return "s"
    return "o"


def canon_value(v, kind: str):
    """Canonical python form of one key component: the same value staged
    from an arrow column and parsed from a JSON request must compare AND
    hash identically."""
    if kind in ("i", "u"):
        if isinstance(v, datetime.datetime):
            return int(np.datetime64(v, "ns").astype(np.int64))
        return int(v)
    if kind == "f":
        return float(v)
    if kind == "s":
        return str(v)
    return _hashable(v)


def _hashable(v):
    """Hashable canonical form of an 'o'-kind key component (struct
    keys arrive as dicts from arrow, as lists from JSON requests)."""
    if isinstance(v, dict):
        return tuple(_hashable(v[k]) for k in sorted(v))
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, datetime.datetime):
        return int(np.datetime64(v, "ns").astype(np.int64))
    if isinstance(v, np.generic):
        return v.item()
    return v


def _plain(v):
    """Msgpack/JSON-safe deep conversion of a staged value."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, datetime.datetime):
        return int(np.datetime64(v, "ns").astype(np.int64))
    if isinstance(v, dict):
        return {str(k): _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, np.generic):
        return v.item()
    return str(v)


def owner_subtask(key: Tuple, kinds: Tuple[str, ...], parallelism: int) -> int:
    """Owning subtask index for one canonical key tuple — the §2.9-2.11
    routing contract: per-column splitmix64/siphash, seeded xor-mix
    combine, contiguous hash-range map (types.server_for_hash_array)."""
    if parallelism <= 1 or not key:
        return 0
    cols = []
    for v, k in zip(key, kinds):
        dtype = _KIND_DTYPE.get(k)
        if dtype is not None:
            arr = np.asarray([v]).astype(dtype)
        else:
            arr = np.array([v], dtype=object)
        cols.append(hash_column(arr))
    return int(server_for_hash_array(hash_arrays(cols), parallelism)[0])


# -- columnar segments --------------------------------------------------------


def _ipc_bytes(table: pa.Table) -> memoryview:
    """The table as an Arrow IPC stream: a bytes-like view of Arrow's own
    buffer (`to_pybytes` would copy it once more, holding the GIL: 0.2 s
    for a 180 MB segment on the chip's host, on whichever thread)."""
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return memoryview(sink.getvalue()).cast("B")


def _keep_last(table: pa.Table, n_keys: int) -> pa.Table:
    """The last row of every key, in arrival order: last-writer-wins
    over a concatenation of segments. Vectorised: each key column is
    dictionary-encoded (Arrow's hash table), the codes are combined,
    and a scatter of row numbers by code leaves the last one."""
    n = table.num_rows
    if n <= 1:
        return table
    if n_keys == 0:
        return table.slice(n - 1)
    codes = None
    for i in range(n_keys):
        col = table.column(i).combine_chunks()
        if pa.types.is_floating(col.type):
            # -0.0 and 0.0 are one key, as they are to a dict
            col = pc.add(col, 0.0)
        enc = pc.dictionary_encode(col)
        c = enc.indices.to_numpy(zero_copy_only=False).astype(np.int64)
        if codes is None:
            codes = c
        else:
            # both factors are < n, so the product stays far below 2**63
            enc2 = pc.dictionary_encode(
                pa.array(codes * np.int64(len(enc.dictionary)) + c))
            codes = enc2.indices.to_numpy(
                zero_copy_only=False).astype(np.int64)
    last = np.empty(int(codes.max()) + 1, dtype=np.int64)
    last[codes] = np.arange(n, dtype=np.int64)  # repeated code: last wins
    if len(last) == n:
        return table
    keep = np.zeros(n, dtype=bool)
    keep[last] = True
    return table.filter(pa.array(keep)).combine_chunks()


def _drop_tombs(table: pa.Table) -> pa.Table:
    """Without its retracted rows and the column that marked them: for
    the oldest layer, under which nothing is left to shadow."""
    if _TOMBCOL not in table.schema.names:
        return table
    tomb = pc.fill_null(table.column(_TOMBCOL), False)
    table = table.drop_columns([_TOMBCOL])
    if pc.any(tomb).as_py():
        table = table.filter(pc.invert(tomb))
    return table


def _leaf_types(arrow_type) -> Optional[list]:
    """The canonical Arrow types of a key column's leaves (a struct
    key counts field by field, in the order of its sorted field names:
    the order `_hashable` gives its values), or None where a leaf has
    no per-column canonical form (a list, a binary, a bool)."""
    if pa.types.is_struct(arrow_type):
        out: list = []
        for f in sorted((arrow_type.field(j)
                         for j in range(arrow_type.num_fields)),
                        key=lambda f: f.name):
            sub = _leaf_types(f.type)
            if sub is None:
                return None
            out.extend(sub)
        return out
    kind = _kind_of(arrow_type)
    return None if kind == "o" else [_KIND_ARROW[kind]]


def _leaves(col) -> Optional[list]:
    """The leaf arrays of a key column in `_leaf_types`' order, as they
    are (no cast yet); None where the column or a leaf holds a null."""
    if col.null_count:
        return None
    if not pa.types.is_struct(col.type):
        return [col]
    out: list = []
    for name in sorted(col.type.field(j).name
                       for j in range(col.type.num_fields)):
        sub = _leaves(col.field(name))
        if sub is None:
            return None
        out.extend(sub)
    return out


def _flat(key: Tuple) -> Tuple:
    """A canonical key with its struct components (nested tuples)
    spliced in: the leaves, as a segment's key columns hold them."""
    if not any(isinstance(v, tuple) for v in key):
        return key
    out: list = []
    for v in key:
        if isinstance(v, tuple):
            out.extend(_flat(v))
        else:
            out.append(v)
    return tuple(out)


class _Segment:
    """An immutable columnar run of view rows: the key's leaf columns
    in canonical form (`__k0`, `__k1`, ...: timestamps as int64 nanos,
    integers widened, strings as `string`) first, then value columns as
    emitted and/or `__row` / `__tomb`. Three origins: an emitted batch
    as it came (`raw`), held so until it is first merged or looked up; a
    table; the layers of one sealed interval, un-merged (`parts`, oldest
    first), which the first call of `table` merges (`ServeView._merge`),
    once, under a lock: the checkpoint's flush thread as a rule, a read
    or a compaction on the loop if it comes first. The index is built on
    the first lookup."""

    __slots__ = ("_raw", "_table", "_parts", "_lock", "_index",
                 "staged_rows")

    def __init__(self, raw=None, table: Optional[pa.Table] = None,
                 parts: Optional[list] = None):
        self._raw = raw
        self._table = table
        self._parts = parts
        self._lock = threading.Lock() if parts is not None else None
        self._index = None
        # the rows handed in: of un-merged parts, all of them (keys that
        # recur still count each time)
        self.staged_rows = (
            sum(p.staged_rows for p in parts) if parts is not None
            else (raw if table is None else table).num_rows)

    @property
    def deferred(self) -> bool:
        """Un-merged parts, still: nobody has asked for the table."""
        return self._parts is not None

    def staged_nbytes(self) -> int:
        """What the barrier can know of an un-merged layer's size."""
        if self._parts is not None:
            return sum(p.staged_nbytes() for p in self._parts)
        return _batch_nbytes(self._raw if self._table is None
                             else self._table)

    def rows(self, view: "ServeView") -> int:
        return (self._raw if self._raw is not None
                else self.table(view)).num_rows

    def table(self, view: "ServeView") -> pa.Table:
        if self._lock is not None and self._table is None:
            with self._lock:
                if self._table is None:
                    self._table = view._merge(self._parts)._table
                    self._parts = None
        if self._table is None:
            raw, names = self._raw, self._raw.schema.names
            cols = []
            for n in view.key_names:
                cols.extend(_leaves(raw.column(names.index(n))))
            for i, t in enumerate(view._key_types):
                if pa.types.is_timestamp(cols[i].type):
                    cols[i] = cols[i].cast(pa.timestamp("ns"))
                if cols[i].type != t:
                    cols[i] = cols[i].cast(t)
            vnames = [n for n in view.value_names if n in names]
            cols.extend(raw.column(names.index(n)) for n in vnames)
            self._table = pa.Table.from_batches([pa.RecordBatch.from_arrays(
                cols, names=view._key_columns() + vnames)])
            self._raw = None
        return self._table

    def find(self, key: Tuple, view: "ServeView") -> int:
        """Row of the last entry for `key`, or -1."""
        types = view._key_types
        if not types:
            return self.rows(view) - 1
        table = self.table(view)
        if self._index is None:
            if len(types) == 1 and types[0] != pa.string():
                keys = table.column(0).to_numpy()
                order = np.argsort(keys, kind="stable")
                self._index = (keys[order], order)
            else:
                # tuples and strings: a dict of Python keys, built once
                with view._materialize(table.num_rows):
                    cols = [table.column(i).to_pylist()
                            for i in range(len(types))]
                    self._index = dict(zip(zip(*cols),
                                           range(table.num_rows)))
        key = _flat(key)
        if isinstance(self._index, dict):
            return self._index.get(key, -1)
        sorted_keys, order = self._index
        try:
            k = np.asarray(key[0], dtype=sorted_keys.dtype)
        except (OverflowError, TypeError, ValueError):
            return -1
        i = int(np.searchsorted(sorted_keys, k, side="right")) - 1
        if i < 0 or sorted_keys[i] != k:
            return -1
        return int(order[i])

    def value(self, row: int, view: "ServeView"):
        """The Python value of one row (`_TOMB` for a retraction): the
        only place a segment's row becomes Python objects."""
        table = self.table(view)
        names = table.schema.names
        with view._materialize(1):
            if _TOMBCOL in names and table.column(_TOMBCOL)[row].as_py():
                return _TOMB
            if _ROW in names:
                packed = table.column(_ROW)[row].as_py()
                if packed is not None:
                    return msgpack.unpackb(packed, raw=False,
                                           strict_map_key=False)
            return {
                n: _plain(_fast_pylist(
                    table.column(n).slice(row, 1).combine_chunks())[0])
                for n in view.value_names if n in names
            }


def _top_dict(layers: list) -> dict:
    """The newest layer if it is a dict, else a new dict on top."""
    if not layers or not isinstance(layers[-1], dict):
        layers.append({})
    return layers[-1]


class ServeView:
    """One subtask's epoch-consistent keyed view of an operator's
    emitted aggregates (see module docstring for the layer semantics).

    A layer is a `_Segment` (an emitted batch, a merge of layers, or a
    sealed interval's layers still to be merged) or a dict key -> value |
    `_TOMB` (rows staged one at a time). `_stage` and `served` are lists
    of layers, oldest first; `pending[epoch]` is one layer."""

    def __init__(self, *, job_id: str, table: str, node_id: int,
                 task_index: int, parallelism: int,
                 key_names: List[str], key_kinds: Tuple[str, ...],
                 value_names: List[str], kind: str, live_mode: bool):
        self.job_id = job_id
        self.table = table
        self.node_id = node_id
        self.task_index = task_index
        self.parallelism = parallelism
        self.key_names = list(key_names)
        self.key_kinds = tuple(key_kinds)
        self.value_names = list(value_names)
        self.kind = kind  # "window" | "updating"
        self.live_mode = live_mode
        self.routable = all(k in _KIND_DTYPE or k == "s"
                            for k in self.key_kinds)
        # the canonical types of the key's leaf columns, once the view
        # has been handed a batch (or restored one): from then on every
        # sealed layer is a segment. None = the view has only ever seen
        # rows staged one at a time, and holds a dict per layer
        self._key_types: Optional[list] = None
        self.served: List[Any] = []
        self.served_epoch = 0          # highest epoch folded into served
        self.pending: Dict[int, Any] = {}
        self._stage: List[Any] = []
        self._max_pending = max(1, int(config().serve.max_pending_epochs))
        # `served` is one merged layer without retractions: its length
        # is the exact key count
        self._served_exact = True
        # the engagement counter's two counts (ISSUE 25): rows handed to
        # the view by a batch, rows turned into Python objects
        self.staged_rows = 0
        self.materialized_rows = 0
        # rows sealed un-merged, for the flush thread (ISSUE 39): all of
        # a view's sealed rows where every stage held segments alone
        self.deferred_rows = 0
        # this view's segment entries in the `__serve__` table, oldest
        # first, the sequence number of the next one, and whether the
        # table (still) holds entries per key
        self._mirror_log: List[str] = []
        self._mirror_seq = 0
        self._mirror_rows = False

    def _key_columns(self) -> List[str]:
        return [f"__k{i}" for i in range(len(self._key_types))]

    def _rows(self, layer) -> int:
        return len(layer) if isinstance(layer, dict) else layer.rows(self)

    def _materialize(self, n: int):
        self.materialized_rows += n
        return timeline.phase("serve.materialize", n=n, annotate=False)

    # -- write side (operator emission + runner capture) ---------------------

    def canon_key(self, values) -> Tuple:
        return tuple(
            canon_value(v, k) for v, k in zip(values, self.key_kinds)
        )

    def _rows_layer(self) -> dict:
        """The dict layer a row-wise write goes to: the newest layer if
        it is one, else a new one on top (so a row staged after a batch
        wins over it, and a batch staged after a row wins over that)."""
        if not self.live_mode:
            return _top_dict(self._stage)
        if (len(self.served) >= _SERVED_SEGMENTS
                and not isinstance(self.served[-1], dict)):
            self._compact()
        self._served_exact = False
        return _top_dict(self.served)

    def stage(self, key: Tuple, value):
        self._rows_layer()[key] = value

    def stage_tomb(self, key: Tuple):
        layer = self._rows_layer()
        if self.live_mode and len(self.served) == 1:
            layer.pop(key, None)  # nothing older to shadow
        else:
            layer[key] = _TOMB

    def stage_segment(self, batch, key_types: list) -> None:
        """Stage an emitted batch as it is: one list append."""
        self.staged_rows += batch.num_rows
        if self._key_types is None:
            self._key_types = key_types
        if batch.num_rows == 0:
            return
        if self.live_mode:
            self.served.append(_Segment(raw=batch))
            self._served_grew()
        else:
            self._stage.append(_Segment(raw=batch))

    def stage_restored(self, key: Tuple, value) -> None:
        """Seed `served` at task start, over what the mirror gave."""
        _top_dict(self.served)[key] = value
        self._served_exact = False

    def seal(self, epoch: int):
        """Move the staged layers under `epoch` as one layer (called at
        checkpoint capture, synchronously at the barrier). The rule is
        read from the stage: segments alone are immutable batches, so
        detaching the list fixes the epoch's rows and the layer is filed
        un-merged (`_Segment(parts=)`: O(1) here, merged once by whoever
        first asks for its table); a stage with a dict layer is merged
        now. Bounded: past serve.max_pending_epochs the oldest pending
        epoch folds forward (publication stalled far beyond the inflight
        window). Returns the sealed delta as one layer (None when
        nothing was staged) — seal_op mirrors it into the `__serve__`
        state table for followers."""
        if not self._stage:
            return None
        staged, self._stage = self._stage, []
        rows = sum(len(x) if isinstance(x, dict) else x.staged_rows
                   for x in staged)
        with timeline.phase("serve.seal", annotate=False, n=rows):
            if self._key_types is not None and not any(
                    isinstance(x, dict) for x in staged):
                sealed = _Segment(parts=staged)
                self.deferred_rows += rows
            else:
                sealed = self._merge(staged)
            if epoch in self.pending:
                self.pending[epoch] = self._merge(
                    [self.pending[epoch], sealed])
            else:
                self.pending[epoch] = sealed
            while len(self.pending) > self._max_pending:
                self._fold_one(min(self.pending))
        return sealed

    def _merge(self, layers: list, bottom: bool = False):
        """One layer holding the last write of every key in `layers`
        (oldest first). `bottom`: nothing older exists, so retractions
        are dropped with what they retract. A view that holds segments
        merges columns and never loops over rows of one; a view that
        was never handed a batch holds dicts only."""
        if self._key_types is None:
            merged: dict = {}
            for layer in layers:
                merged.update(layer)
            if bottom:
                merged = {k: v for k, v in merged.items() if v is not _TOMB}
            return merged
        tables = [self._encode_rows(x) if isinstance(x, dict)
                  else x.table(self) for x in layers]
        table = (tables[0] if len(tables) == 1 else
                 pa.concat_tables(tables, promote_options="default"))
        table = _keep_last(table, len(self._key_types))
        if bottom:
            table = _drop_tombs(table)
        return _Segment(table=table)

    def _encode_rows(self, rows: dict) -> pa.Table:
        """A dict layer as a segment: the keys' leaves by column, each
        value as msgpack bytes in `__row`, retractions flagged in
        `__tomb`."""
        keys, vals = [_flat(k) for k in rows], list(rows.values())
        arrays = [
            pa.array([k[i] for k in keys], type=t)
            for i, t in enumerate(self._key_types)
        ]
        arrays.append(pa.array(
            [None if v is _TOMB else msgpack.packb(
                v, use_bin_type=True, default=_plain)
             for v in vals], type=pa.binary()))
        arrays.append(pa.array([v is _TOMB for v in vals], type=pa.bool_()))
        return pa.Table.from_arrays(
            arrays, names=self._key_columns() + [_ROW, _TOMBCOL])

    def _served_grew(self):
        self._served_exact = False
        if len(self.served) > _SERVED_SEGMENTS:
            self._compact()

    def _compact(self):
        """Merge `served` into one layer without retractions."""
        if not self.served:
            self._served_exact = True
            return
        with timeline.phase("serve.compact", annotate=False) as compact:
            compact.n = sum(map(self._rows, self.served))
            self.served = [self._merge(self.served, bottom=True)]
        self._served_exact = True

    def _fold_one(self, epoch: int):
        self.served.append(self.pending.pop(epoch))
        self.served_epoch = max(self.served_epoch, epoch)
        self._served_grew()

    def fold_to(self, epoch: int):
        for e in sorted(self.pending):
            if e > epoch:
                break
            self._fold_one(e)

    # -- read side -----------------------------------------------------------

    def _lookup(self, layer, key: Tuple):
        """(hit, value | _TOMB) of `key` in one layer."""
        if isinstance(layer, dict):
            v = layer.get(key, layer)  # the dict itself: never a value
            return (False, None) if v is layer else (True, v)
        row = layer.find(key, self)
        if row < 0:
            return False, None
        return True, layer.value(row, self)

    @protocol_effect("serve.read")
    def read(self, key: Tuple, epoch: Optional[int]):
        """(found, value) at the given published epoch (None = live
        mode: serve whatever has been folded/staged so far). Rows sealed
        at epochs > `epoch` stay invisible — the no-torn-read contract
        the model checker's reader actor pins. Newest layer first; only
        the row that answers becomes Python objects."""
        if epoch is not None and not self.live_mode:
            self.fold_to(epoch)
        for layer in reversed(self.served):
            hit, value = self._lookup(layer, key)
            if hit:
                return (False, None) if value is _TOMB else (True, value)
        return False, None

    def stats(self) -> dict:
        if not self._served_exact:
            self._compact()  # the key count is exact, not an estimate
        return {
            "table": self.table,
            "task_index": self.task_index,
            "keys": sum(map(self._rows, self.served)),
            "pending_epochs": len(self.pending),
            "staged": sum(map(self._rows, self._stage)),
            "served_epoch": self.served_epoch,
            "staged_rows": self.staged_rows,
            "materialized_rows": self.materialized_rows,
            "deferred_rows": self.deferred_rows,
        }

    def describe(self) -> dict:
        return {
            "table": self.table,
            "node_id": self.node_id,
            "parallelism": self.parallelism,
            "key_fields": self.key_names,
            "key_kinds": list(self.key_kinds),
            "value_fields": self.value_names,
            "kind": self.kind,
            "routable": self.routable,
            "live_mode": self.live_mode,
        }


# -- operator integration -----------------------------------------------------


def _view_plan(op, task_info) -> Optional[tuple]:
    """(kind, key_names, key_kinds, value_names) for an operator that
    gets a serve view, else None. Shared by register_op (attach at task
    start) and serve_mirror_tables (declare the `__serve__` mirror
    table BEFORE TableManager.open runs — both must agree, or a viewed
    operator would have no chain for followers to tail)."""
    from ..operators.updating import UpdatingAggregateOperator
    from ..operators.updating_join import UpdatingJoinOperator
    from ..operators.windows import WindowOperatorBase
    from ..schema import TIMESTAMP_FIELD

    if isinstance(op, UpdatingAggregateOperator):
        kind = "updating"
    elif isinstance(op, WindowOperatorBase):
        kind = "window"
    elif isinstance(op, UpdatingJoinOperator):
        # join views (ISSUE 20 satellite): key -> current joined row
        # set. Residual (non-equi) predicates filter EMITTED rows only;
        # serving the stored match set would show rows the residual
        # rejected, so such joins stay unserved rather than wrong.
        if op.residual is not None:
            return None
        kind = "join"
    else:
        return None
    if kind == "join":
        key_names = [f"__key{i}" for i in range(op.n_keys)]
    else:
        key_names = list(getattr(op, "_key_names", None) or [])
    if not key_names and task_info.parallelism > 1:
        # keyless aggregate on a parallel node: every subtask holds a
        # PARTIAL — no single owner can answer, so no view
        return None
    schema = op.out_schema.schema
    name_to_type = {f.name: f.type for f in schema}
    key_kinds = tuple(
        _kind_of(name_to_type[n]) if n in name_to_type else "o"
        for n in key_names
    )
    # every non-key output column is value payload EXCEPT the row
    # timestamp and the updating meta column; planner-internal aggregate
    # outputs (__agg_out_N) stay — they ARE the aggregate, the friendly
    # alias often lives on a downstream projection node
    if kind == "updating":
        # updating flushes stage (key -> finalized spec values) directly,
        # so the value names must align with the accumulator spec order
        value_names = [s.name for s in op.specs]
    else:
        # join views serve {"rows": [{field: value}]}; value_names
        # documents the per-row payload fields either way
        value_names = [
            f.name for f in schema
            if f.name not in key_names and f.name != TIMESTAMP_FIELD
            and f.name != "__updating_meta"
        ]
    return kind, key_names, key_kinds, value_names


def _mirror_eligible(op, task_info) -> bool:
    """Will this operator (ever) carry a serve view? The open-time
    twin of _view_plan's gate: serve_mirror_tables runs BEFORE
    on_start, when window/updating operators haven't captured their
    key NAMES yet (`_key_names` lands in _capture_key_meta), so
    keyedness is judged from construction-time attributes instead
    (`key_cols` / `n_keys`). Erring open is harmless — an unwritten
    mirror table captures empty and followers skip it for lack of a
    `__serve_meta__` record; erring closed would leave a viewed
    operator with no chain for followers to tail."""
    from ..operators.updating_join import UpdatingJoinOperator
    from ..operators.windows import WindowOperatorBase

    if isinstance(op, UpdatingJoinOperator):
        if op.residual is not None:
            return False
        keyed = int(op.n_keys) > 0
    elif isinstance(op, WindowOperatorBase):  # updating subclasses it
        keyed = bool(getattr(op, "key_cols", None)
                     or getattr(op, "_key_names", None))
    else:
        return False
    return keyed or task_info.parallelism == 1


def serve_mirror_tables(op, task_info) -> Dict[str, Any]:
    """Extra table configs the runner merges into op.tables() at open:
    viewed operators on durable jobs get the `__serve__` mirror
    GlobalTable (see module constants). Empty for everything else."""
    if not config().serve.enabled:
        return {}
    if not _mirror_eligible(op, task_info):
        return {}
    from ..state.table_config import global_table

    return {SERVE_TABLE: global_table(SERVE_TABLE)}


def register_op(op, ctx) -> Optional[ServeView]:
    """Attach a ServeView to a keyed operator at task start (called by
    the runner after on_start, once restore has run). Returns None —
    and leaves the operator untouched — when serving is disabled, the
    operator kind has no keyed view, or the view would be meaningless
    (keyless state on a parallel node holds per-subtask partials)."""
    if not config().serve.enabled:
        return None
    ti = ctx.task_info
    plan = _view_plan(op, ti)
    if plan is None:
        return None
    kind, key_names, key_kinds, value_names = plan
    view = ServeView(
        job_id=ti.job_id, table=op.name, node_id=ti.node_id,
        task_index=ti.task_index, parallelism=ti.parallelism,
        key_names=key_names, key_kinds=key_kinds,
        value_names=value_names, kind=kind,
        live_mode=ctx.table_manager is None,
    )
    op._serve_view = view
    if ctx.table_manager is not None:
        # restore seeding from the mirror table: the restored `__serve__`
        # chain IS the last published epoch's view (window finals,
        # session partials, join row sets alike) — without it a
        # recovered job would 404 every key until re-emission. The
        # restore unions ALL subtasks' chains; keep only owned keys so
        # per-subtask memory stays O(owned), not O(table).
        mirror = ctx.table_manager.tables.get(SERVE_TABLE)
        if mirror is not None:
            seed_from_mirror(view, mirror, adopt=True)
    if kind == "updating" and getattr(op, "emitted", None):
        # restore seeding (pre-mirror jobs): the restored `emitted` map
        # is authoritative for updating aggregates — overwrite any
        # mirror-seeded copy
        for k, vals in op.emitted.items():
            try:
                key = view.canon_key(op.codec.values(k))
            except Exception:  # noqa: BLE001 - exotic key shape
                continue
            view.stage_restored(key, {
                n: _plain(v) for n, v in zip(view.value_names, vals)
            })
    return view


def _owned(view: ServeView, table: pa.Table) -> pa.Table:
    """The rows of a segment this subtask owns after a restore or a
    rescale: one `hash_column` per key column and one
    `server_for_hash_array` for the segment (`owner_subtask`, per
    column instead of per key)."""
    if (not view.routable or view.parallelism <= 1 or not view.key_kinds
            or not table.num_rows):
        return table
    hashes = [
        hash_column(table.column(i).to_numpy(zero_copy_only=False))
        for i in range(len(view.key_kinds))
    ]
    owner = server_for_hash_array(hash_arrays(hashes), view.parallelism)
    return table.filter(pa.array(owner == view.task_index))


def _owned_rows(view: ServeView, items) -> dict:
    """The per-key entries among a `__serve__` table's items that this
    subtask owns (a view that was never handed a batch writes them, and
    so did every view before ISSUE 25). A restore unions all subtasks'
    chains: the others' entries stay where they are, for their owners."""
    keep_all = not (view.routable and view.parallelism > 1)
    return {
        k: v for k, v in items
        if isinstance(k, tuple) and (
            keep_all or owner_subtask(k, view.key_kinds, view.parallelism)
            == view.task_index)
    }


def _mirror_segments(items) -> list:
    """The segment entries among a `__serve__` table's items in replay
    order, as (epoch, seq, task, table key, Arrow table)."""
    segs = []
    for k, v in items:
        if isinstance(k, str) and k.startswith(SEG_PREFIX + "/"):
            _, task, epoch, seq = k.split("/")
            segs.append((int(epoch), int(seq), int(task), k, v))
    segs.sort()
    return [(*label, pa.ipc.open_stream(blob).read_all())
            for *label, blob in segs]


def seed_from_mirror(view: ServeView, mirror, adopt: bool = False) -> None:
    """Rebuild `view.served` from a `__serve__` table: the one reader
    of the mirror, for a follower's refresh (`adopt=False`: read-only,
    every key) and for a worker's restore (`adopt=True`: only the keys
    this subtask owns, and its segments rewritten as one base). Accepts
    both forms: entries per key (all there is for a view that was never
    handed a batch, and for a chain written before ISSUE 25) are older
    than every segment; segment entries replay in (epoch, seq) order."""
    items = mirror.items()
    rows = _owned_rows(view, items)
    view._mirror_rows = bool(rows)
    view.served = [rows] if rows else []
    view._served_exact = True
    segs = _mirror_segments(items)
    if not segs:
        return
    names = segs[-1][-1].schema.names
    view._key_types = [segs[-1][-1].schema.field(n).type for n in names
                       if n.startswith("__k")]
    view.served.extend(_Segment(table=t) for *_, t in segs)
    view._compact()
    if not adopt:
        return
    # this subtask's chain starts over: what it owns as one base,
    # labelled just after the newest entry there was (a key no subtask
    # has used, so no other subtask's tombstone can hit it); every older
    # segment, and the entries per key the base now holds, go through
    # the table's tombstones (each subtask drops all the segments and
    # re-persists what it owns: the union of the new chains is the view
    # again)
    base = _Segment(table=_owned(view, view.served[0].table(view)))
    view.served = [base]
    for k in [k for *_, k, _t in segs] + list(rows):
        mirror.delete(k)
    view._mirror_rows = False
    epoch, seq = segs[-1][0], segs[-1][1] + 1
    view._mirror_log = []
    view._mirror_seq = seq + 1
    _mirror_segment(view, mirror, epoch, base, seq)


def _fast_pylist(col) -> list:
    """to_pylist with temporal values pre-cast to epoch nanos. Staged
    values land as int nanos anyway (_plain / canon_value), and int64
    to_pylist skips the per-element pandas Timestamp round-trip that
    dominates the staging hot path — including inside struct columns
    (window bounds are struct<start, end> of timestamps)."""
    if pa.types.is_timestamp(col.type):
        col = col.cast(pa.timestamp("ns")).cast(pa.int64())
    elif pa.types.is_struct(col.type) and col.null_count == 0:
        fields = [col.type.field(j).name
                  for j in range(col.type.num_fields)]
        children = [_fast_pylist(col.field(j))
                    for j in range(col.type.num_fields)]
        return [dict(zip(fields, row)) for row in zip(*children)]
    return col.to_pylist()


def stage_batch(view: ServeView, batch, partial: bool = False) -> list:
    """Stage an emitted output batch into the view (the window
    operators' hook: one call per emitted window batch). Key columns
    index by the view's key order; all other non-internal columns are
    the value. A batch of finals goes in whole, as one segment: O(1),
    no Python object per row. `partial=True` (session-window open
    sessions) is the row-wise entry: each row is staged as a value dict
    flagged `partial: True`, and the canonical keys staged are returned
    (partial bookkeeping). A batch whose key does not canonicalise per
    column (a list key, a null key) takes the row-wise entry too."""
    names = batch.schema.names
    if not partial and all(n in names for n in view.key_names):
        types = [_leaf_types(batch.schema.field(n).type)
                 for n in view.key_names]
        if None not in types and all(
                _leaves(batch.column(names.index(n))) is not None
                for n in view.key_names):
            view.stage_segment(batch, [t for ts in types for t in ts])
            return []
    with view._materialize(batch.num_rows):
        cols = {n: _fast_pylist(batch.column(i))
                for i, n in enumerate(names)}
        vnames = [n for n in view.value_names if n in cols]
        kcols = [[canon_value(v, k) for v in cols[n]]
                 for n, k in zip(view.key_names, view.key_kinds)]
        vcols = [(n, [_plain(v) for v in cols[n]]) for n in vnames]
        stage = view.stage
        staged = []
        for r in range(batch.num_rows):
            key = tuple(c[r] for c in kcols)
            value = {n: c[r] for n, c in vcols}
            if partial:
                value["partial"] = True
            stage(key, value)
            staged.append(key)
    return staged


def _mirror_segment(view: ServeView, mirror, epoch: int, seg: _Segment,
                    seq: int) -> None:
    """Write one segment into the `__serve__` table as one entry, and
    keep the view's entries bounded: past `_MIRROR_SEGMENTS` the oldest
    merge into one, stored under the newest merged entry's key (so the
    replay order holds), and the merged-away entries are deleted
    through the table's own tombstones. All of that bookkeeping (keys,
    the log, which entries go) is done here, at the barrier; the bytes
    of a layer still un-merged, and of a merge of entries, are a
    `Deferred` that the flush resolves (or a `get`, if it comes first)."""
    log = view._mirror_log
    if view._mirror_rows:
        # the table holds entries per key (rows sealed before the view
        # was first handed a batch, or a chain of the older format):
        # what this subtask owns of them becomes the oldest segment
        rows = _owned_rows(view, mirror.items())
        for k in rows:
            mirror.delete(k)
        view._mirror_rows = False
        if rows:
            log.insert(0, f"{SEG_PREFIX}/{view.task_index}/0/0")
            mirror.put(log[0], _ipc_bytes(view._encode_rows(rows)))
    key = f"{SEG_PREFIX}/{view.task_index}/{epoch}/{seq}"
    if seg.deferred:
        mirror.put(key, Deferred(lambda: _ipc_bytes(seg.table(view)),
                                 rows=seg.staged_rows,
                                 nbytes=seg.staged_nbytes()))
    else:
        mirror.put(key, _ipc_bytes(seg.table(view)))
    log.append(key)
    if len(log) <= _MIRROR_SEGMENTS:
        return
    old = log[:len(log) - _MIRROR_SEGMENTS // 2]
    # the entries as they are now (bytes, or the `Deferred` of an epoch
    # whose flush is in flight: epoch-ordered, so it resolves first)
    entries = [mirror.raw(k) for k in old]
    n_keys = len(view._key_types)

    def merged() -> memoryview:
        tables = [pa.ipc.open_stream(resolved(v)).read_all()
                  for v in entries]
        return _ipc_bytes(_drop_tombs(_keep_last(
            pa.concat_tables(tables, promote_options="default"), n_keys)))

    for k in old[:-1]:
        mirror.delete(k)
    mirror.put(old[-1], Deferred(merged, nbytes=sum(
        v.nbytes if isinstance(v, Deferred) else len(v) for v in entries)))
    del log[:len(old) - 1]


def seal_op(op, epoch: int, table_manager=None) -> None:
    """Runner hook at checkpoint capture: seal the operator's staged
    rows under this barrier's epoch (no-op without a view). Operators
    exposing `serve_stage_snapshot` (session partials, join row sets)
    stage their snapshot delta first — inside the same barrier, so the
    snapshot rides this epoch. With a table manager, the sealed delta
    mirrors into the `__serve__` GlobalTable before capture serializes
    it, keeping the follower-visible chain in lockstep with the view:
    one segment entry (its bytes deferred to the flush where the seal
    was), or one entry per key for a view that was never handed a
    batch."""
    view = getattr(op, "_serve_view", None)
    if view is None:
        return
    snap = getattr(op, "serve_stage_snapshot", None)
    if snap is not None:
        try:
            snap(view)
        except Exception:  # noqa: BLE001 - serving must not fail a barrier
            logger.exception("serve snapshot staging failed for %s",
                             view.table)
    sealed = view.seal(epoch)
    if table_manager is None or view.live_mode:
        return
    mirror = table_manager.tables.get(SERVE_TABLE)
    if mirror is None:
        return
    desc = view.describe()
    if mirror.get(META_KEY) != desc:
        mirror.put(META_KEY, desc)
    if sealed is None:
        return
    if isinstance(sealed, dict):
        view._mirror_rows = True
        with timeline.phase("serve.mirror", n=len(sealed), annotate=False):
            for k, v in sealed.items():
                if v is _TOMB:
                    mirror.delete(k)
                else:
                    mirror.put(k, v)
        return
    with timeline.phase("serve.mirror", n=sealed.staged_rows,
                        annotate=False):
        _mirror_segment(view, mirror, epoch, sealed, view._mirror_seq)
        view._mirror_seq += 1


# -- the worker read handler --------------------------------------------------


def _views_of(program) -> Dict[str, Dict[int, ServeView]]:
    """{table: {task_index: view}} over one job's local subtasks. Table
    names qualify as `{name}@{node_id}` as well; the bare name resolves
    when it is unique across nodes."""
    out: Dict[str, Dict[int, ServeView]] = {}
    nodes: Dict[str, set] = {}
    for sub in program.subtasks:
        for op in sub.runner.ops:
            view = getattr(op, "_serve_view", None)
            if view is None:
                continue
            out.setdefault(f"{view.table}@{view.node_id}", {})[
                view.task_index] = view
            nodes.setdefault(view.table, set()).add(view.node_id)
    for name, nids in nodes.items():
        if len(nids) == 1:
            out[name] = out[f"{name}@{next(iter(nids))}"]
    return out


def worker_read(program, req: dict) -> dict:
    """Answer one QueryState request against a job's local views —
    synchronous dict work only, nothing here blocks the batch loop.

    Modes: `tables` lists the views this worker hosts; `stats` gives
    their occupancy (`/debug/serve?job=`); `get` resolves each key to
    its owning subtask (same hash the gateway used) and reads the local
    view at the request's published epoch. A key whose owner is not
    hosted here answers `not_owned` (gateway mis-route or rescale race
    — retriable)."""
    if not config().serve.enabled:
        return {"error": "serving disabled", "retriable": False}
    if req.get("mode") == "stats":
        return {"views": view_stats(program)}
    views = _views_of(program)
    if req.get("mode") == "tables":
        seen = []
        for name, by_task in sorted(views.items()):
            if "@" in name:
                continue
            any_view = next(iter(by_task.values()))
            seen.append(any_view.describe())
        for name, by_task in sorted(views.items()):
            if "@" in name and name.split("@")[0] not in views:
                seen.append(next(iter(by_task.values())).describe())
        return {"tables": seen}
    table = req.get("table") or ""
    by_task = views.get(table)
    if by_task is None:
        # retriable: the gateway only routes tables its (fresh) listing
        # knows, so a worker-side miss is a startup race — the runner
        # has not reached on_start/register yet (recovery, rescale).
        # Unknown table NAMES fail fast at the gateway, not here.
        return {"error": f"no such table {table!r} (yet)",
                "retriable": True}
    epoch = req.get("epoch")  # None = live mode
    max_keys = int(config().serve.max_keys)
    keys = req.get("keys") or []
    if len(keys) > max_keys:
        return {"error": f"too many keys (> {max_keys})",
                "retriable": False}
    any_view = next(iter(by_task.values()))
    results = []
    for raw in keys:
        vals = raw if isinstance(raw, (list, tuple)) else [raw]
        if len(vals) != len(any_view.key_kinds):
            results.append({"key": raw, "found": False,
                            "error": "key arity mismatch",
                            "retriable": False})
            continue
        try:
            key = any_view.canon_key(vals)
        except (TypeError, ValueError):
            results.append({"key": raw, "found": False,
                            "error": "bad key", "retriable": False})
            continue
        if any_view.routable:
            owner = owner_subtask(key, any_view.key_kinds,
                                  any_view.parallelism)
            view = by_task.get(owner)
            if view is None:
                results.append({"key": raw, "found": False,
                                "error": "not_owned", "retriable": True,
                                "owner": owner})
                continue
            found, value = view.read(key, epoch)
        else:
            # unroutable key shape: check every local subtask's view
            found, value = False, None
            for view in by_task.values():
                found, value = view.read(key, epoch)
                if found:
                    break
        results.append({"key": raw, "found": found, "value": value})
    return {"results": results, "epoch": epoch}


def view_stats(program) -> List[dict]:
    """Admin surface: per-view occupancy of one job's local views, with
    the write side's two counts (`staged_rows`, `materialized_rows`)."""
    return [
        v.stats()
        for name, by_task in sorted(_views_of(program).items())
        if "@" in name
        for v in by_task.values()
    ]
