"""Host-side slot directory: maps (bin, key) groups to accumulator slots.

This is the "hash table on TPU" compromise documented in SURVEY.md §7:
slot assignment is a host table over the *unique* (bin, key) pairs of each
batch, while the O(rows) arithmetic runs on device.

The layer's two entry points, and the only places that know which table
holds the groups and how a key is encoded for it:

- `make_directory`: the one constructor the operators call. A python
  `SlotDirectory` where a key does not flatten to int64 words (or the
  C++ module is switched off), `ops/native.py`'s table otherwise, behind
  `parallel/sharded_state.py`'s facades on a mesh.
- `KeyCodec`: every conversion between a batch's key columns, the
  table's keys, Arrow arrays of the declared types and the portable
  checkpoint forms, for the `key_encoding` the chosen table declares.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from ..types import hash_column
from .native import (
    NativeSlotDirectory,
    _i64able,
    flat_key_widths,
    load_native,
)


class SlotDirectory:
    # non-integer key values reach the table as process-local interned
    # codes (`_unique_pairs`); see KeyCodec
    key_encoding = "codes"

    def __init__(self):
        self.by_bin: Dict[int, Dict[tuple, int]] = {}
        self.free: List[int] = []
        self.next_slot = 0
        self.n_live = 0
        # slot -> (bin, key) reverse map, maintained by assign/take/remove
        self.key_of: Dict[int, tuple] = {}

    def required_capacity(self) -> int:
        # +1 for the scratch slot used by shape padding
        return self.next_slot + 1

    def assign(
        self, bins: np.ndarray, key_cols: List[np.ndarray]
    ) -> np.ndarray:
        """Vectorized slot assignment for a batch. Returns slots[i] per row;
        allocates new slots for unseen (bin, key) pairs."""
        n = len(bins)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        uniq, inverse = _unique_pairs(bins, key_cols)
        slot_of_unique = np.empty(len(uniq), dtype=np.int64)
        for u, row in enumerate(uniq):
            b = int(row[0])
            key = tuple(row[1:])
            bin_map = self.by_bin.setdefault(b, {})
            slot = bin_map.get(key)
            if slot is None:
                slot = self.free.pop() if self.free else self._alloc()
                bin_map[key] = slot
                self.key_of[slot] = (b, key)
                self.n_live += 1
            slot_of_unique[u] = slot
        return slot_of_unique[inverse]

    def _alloc(self) -> int:
        s = self.next_slot
        self.next_slot += 1
        return s

    # imperative allocation (session windows bypass assign()): a batch's
    # new sessions take their slots in one `alloc_slots` call; the shard
    # hint only matters to the mesh facade, which load-balances with it
    def alloc_slot(self, shard_hint: int = 0) -> int:
        return self.free.pop() if self.free else self._alloc()

    def alloc_block(self, k: int) -> List[int]:
        """Bulk-allocate k slots in one call: drains the free list first,
        then extends the high-water mark once."""
        nf = min(k, len(self.free))
        out = self.free[len(self.free) - nf:]
        del self.free[len(self.free) - nf:]
        rem = k - nf
        if rem:
            start = self.next_slot
            self.next_slot += rem
            out.extend(range(start, start + rem))
        return out

    def alloc_slots(self, n: int, shard_hint: int = 0) -> np.ndarray:
        """Vectorized imperative allocation (mesh facade load-balances
        across shards; here it is just a block)."""
        return np.asarray(self.alloc_block(n), dtype=np.int64)

    def free_slot(self, slot: int):
        self.free.append(int(slot))

    def free_slots(self, slots):
        """Batch free (a watermark's closed sessions): one C-level extend
        instead of a python call per slot."""
        self.free.extend(np.asarray(slots, dtype=np.int64).tolist())

    def bins_up_to(self, bin_exclusive: int) -> List[int]:
        return sorted(b for b in self.by_bin if b < bin_exclusive)

    def live_bins(self) -> List[int]:
        return sorted(self.by_bin)

    def peek_bin(self, b: int) -> Optional[Dict[tuple, int]]:
        return self.by_bin.get(b)

    def slots_for_keys(self, b: int, keys) -> Dict[tuple, int]:
        """{key: slot} for the subset of `keys` live in bin b (point
        lookups, O(len(keys)))."""
        bin_map = self.by_bin.get(b)
        if not bin_map:
            return {}
        return {k: bin_map[k] for k in keys if k in bin_map}

    def bin_entries(self, b: int):
        """(keys, slots) of a live bin without removal; keys as a list of
        tuples (the native directory returns int64 arrays instead)."""
        bin_map = self.by_bin.get(b, {})
        return list(bin_map.keys()), np.fromiter(
            bin_map.values(), dtype=np.int64, count=len(bin_map)
        )

    def take_bin(self, b: int) -> Tuple[List[tuple], np.ndarray]:
        """Remove a bin for emission: returns (keys, slots) and frees the
        slots (caller must reset accumulator slots before reuse)."""
        bin_map = self.by_bin.pop(b, {})
        keys = list(bin_map.keys())
        slots = np.fromiter(bin_map.values(), dtype=np.int64, count=len(bin_map))
        for s in slots:
            self.free.append(int(s))
            self.key_of.pop(int(s), None)
        self.n_live -= len(bin_map)
        return keys, slots

    def remove(self, b: int, keys: List[tuple]) -> np.ndarray:
        """Remove specific keys from a bin (TTL eviction); returns the freed
        slots (caller must reset accumulator slots before reuse)."""
        bin_map = self.by_bin.get(b)
        if not bin_map:
            return np.empty(0, dtype=np.int64)
        freed = []
        for k in keys:
            slot = bin_map.pop(k, None)
            if slot is not None:
                freed.append(slot)
                self.free.append(slot)
                self.key_of.pop(slot, None)
                self.n_live -= 1
        if not bin_map:
            self.by_bin.pop(b, None)
        return np.asarray(freed, dtype=np.int64)

    def keys_for_slots(self, slots: np.ndarray) -> List[Optional[tuple]]:
        """Resolve slots back to their live (bin, key) in O(len(slots)) via
        the incrementally-maintained reverse map."""
        return [self.key_of.get(int(s)) for s in slots]

    def items(self):
        for b, bin_map in self.by_bin.items():
            for key, slot in bin_map.items():
                yield b, key, slot


def _unique_pairs(
    bins: np.ndarray, key_cols: List[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Unique (bin, *keys) rows + inverse mapping. Fast path stacks numeric
    columns into one int64/struct matrix; object columns fall back to pandas
    factorize per column."""
    cols = [np.asarray(bins)]
    for c in key_cols:
        c = np.asarray(c)
        if c.dtype.kind == "M":
            c = c.view("i8")
        if c.dtype == np.uint64:
            # bit-preserving: values >= 2^63 become negative codes; window
            # emission normalizes back mod 2^64
            c = c.view(np.int64)
        if c.dtype.kind not in "iub":
            c = _factorize_to_codes(c, cols)
            cols.append(c)
        else:
            cols.append(c.astype(np.int64, copy=False))
    mat = np.stack([c.astype(np.int64, copy=False) for c in cols], axis=1)
    uniq, inverse = np.unique(mat, axis=0, return_inverse=True)
    return uniq, inverse.ravel()


# object-key interning: codes are only used within one assign() call for
# uniquing; the directory's tuples store the *codes*... that would break
# cross-batch identity, so we intern values globally instead.
_INTERN: Dict[object, int] = {}
_INTERN_REV: List[object] = []


def intern_value(v) -> int:
    if isinstance(v, list):  # msgpack round-trips tuples as lists
        v = tuple(tuple(x) if isinstance(x, list) else x for x in v)
    code = _INTERN.get(v)
    if code is None:
        code = len(_INTERN_REV)
        _INTERN[v] = code
        _INTERN_REV.append(v)
    return code


def unintern_value(code: int):
    return _INTERN_REV[code]


def _factorize_to_codes(col: np.ndarray, _cols) -> np.ndarray:
    return np.fromiter(
        (intern_value(v) for v in col), dtype=np.int64, count=len(col)
    )


# -- the seam: which table, and how its keys are encoded --------------------


def make_directory(key_types, *, mesh_shards: int = 0, salted: bool = False,
                   uses_assign: bool = True):
    """The (bin, key) -> slot table for an operator, chosen ONCE from what
    it can observe: its key types, the mesh it runs on (`mesh_shards` >= 2:
    a facade over per-shard tables, or over one flat table when
    `salted`), and whether it maps rows to slots through `assign` at all
    (sessions allocate slots imperatively and their keys never reach the
    table: `key_types` may be None then).

    Keys that flatten to at most 16 int64 words ride the C++ table; any
    other key type, or a host without the C++ module, the python one.
    What came back declares its `key_encoding`, fixed from here on:
    "words" (bins come back as int64 word arrays: `take_bin_arrays`,
    `bin_entries_multi`, a matrix from `bin_entries`), "codes" (tuples of
    ints, non-integer values interned) or "values" (no key in the
    table)."""
    widths = flat_key_widths(key_types) if uses_assign else None
    if mesh_shards >= 2:
        from ..parallel.sharded_state import (
            MeshSlotDirectory,
            SharedMeshSlotDirectory,
        )

        table = (SharedMeshSlotDirectory if salted
                 else MeshSlotDirectory)(mesh_shards)
        if widths is not None:
            table.swap_to_native(load_native(), sum(widths))
    elif widths is not None:
        table = NativeSlotDirectory(load_native(), n_keys=sum(widths))
    else:
        table = SlotDirectory()
    if not uses_assign:
        table.key_encoding = "values"
    return table


def _to_py(v):
    return v.item() if isinstance(v, np.generic) else v


def _is_interned_type(t: pa.DataType) -> bool:
    return not (
        pa.types.is_integer(t)
        or pa.types.is_boolean(t)
        or pa.types.is_timestamp(t)
    )


class KeyCodec:
    """A grouping key's encodings, for one operator's key types and the
    `key_encoding` of the table `make_directory` chose:

    - "words": every key column is one int64 word, a struct (window) key
      one word per child; table keys are the flat word tuples.
    - "codes": integer-like columns are their int64 bit patterns, every
      other value (strings, floats, struct keys as tuples of their
      children) a process-local interned code.
    - "values": the operator keeps the keys itself, as plain values.

    Portable forms (never a code: codes are process-local): a snapshot
    holds one list of values per key, a struct as the tuple of its
    children; a delta batch holds one `__k{i}` column per key column."""

    def __init__(self, key_types, key_encoding: str):
        self.types: List[pa.DataType] = list(key_types)
        self.words = key_encoding == "words"
        self._coded = key_encoding == "codes"
        # word layout: widths[i] words for key column i, from offsets[i]
        self._widths = [
            t.num_fields if pa.types.is_struct(t) else 1 for t in self.types
        ]
        self._offsets = [0]
        for w in self._widths:
            self._offsets.append(self._offsets[-1] + w)

    # -- batch -> key columns for assign() ----------------------------------

    def columns(self, batch: pa.RecordBatch, key_cols) -> List[np.ndarray]:
        out = []
        for i in key_cols:
            col = batch.column(i)
            if pa.types.is_struct(col.type) and self.words:
                # struct children ride as separate int64 key words — no
                # python tuple per row
                for j in range(col.type.num_fields):
                    out.append(
                        np.asarray(col.field(j).cast(pa.int64()))
                    )
                continue
            if pa.types.is_struct(col.type):
                # struct keys (window structs) become tuples of child values;
                # tuples are built per UNIQUE row (batches share few windows)
                children = [
                    np.asarray(col.field(j).cast(pa.int64()))
                    if _i64able(col.type.field(j).type)
                    else np.array(col.field(j).to_pylist(), dtype=object)
                    for j in range(col.type.num_fields)
                ]
                if all(c.dtype != object for c in children):
                    mat = np.stack(children, axis=1)
                    uniq, inverse = np.unique(mat, axis=0, return_inverse=True)
                    tuples = np.empty(len(uniq), dtype=object)
                    tuples[:] = [tuple(int(x) for x in row) for row in uniq]
                    out.append(tuples[inverse.ravel()])
                else:
                    out.append(
                        np.fromiter(
                            (tuple(int(c[r]) if isinstance(c[r], np.integer)
                                   else c[r] for c in children)
                             for r in range(batch.num_rows)),
                            dtype=object,
                            count=batch.num_rows,
                        )
                    )
                continue
            try:
                out.append(col.to_numpy(zero_copy_only=False))
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                out.append(np.array(col.to_pylist(), dtype=object))
        return out

    # -- table keys -> Arrow arrays of the declared types -------------------

    def arrow_from_words(self, ki: int, word_cols) -> pa.Array:
        """Key column `ki` from the table's int64 word columns (raw bit
        patterns) — the vectorized emission path of a "words" table."""
        kt = self.types[ki]
        off = self._offsets[ki]
        if pa.types.is_struct(kt):
            # regroup the struct's child words
            children = [
                pa.array(word_cols[off + j]).cast(kt.field(j).type)
                for j in range(kt.num_fields)
            ]
            return pa.StructArray.from_arrays(
                children,
                names=[kt.field(j).name for j in range(kt.num_fields)],
            )
        if pa.types.is_unsigned_integer(kt):
            return pa.array(word_cols[off].view(np.uint64), type=kt)
        # signed ints and timestamps cast directly
        return pa.array(word_cols[off]).cast(kt)

    def arrow_from_keys(self, ki: int, keys: List[tuple]) -> pa.Array:
        """Key column `ki` from key tuples as the table (or, under
        "values", the operator) holds them."""
        kt = self.types[ki]
        if self.words:
            n = len(keys)
            lo, hi = self._offsets[ki], self._offsets[ki + 1]
            return self.arrow_from_words(ki, {
                w: np.fromiter((k[w] for k in keys), np.int64, n)
                for w in range(lo, hi)
            })
        vals = [_to_py(k[ki]) for k in keys]
        if self._coded and _is_interned_type(kt):
            vals = [unintern_value(v) for v in vals]
        if pa.types.is_struct(kt):
            children = [
                pa.array(
                    [t[j] for t in vals], type=pa.int64()
                ).cast(kt.field(j).type)
                if _i64able(kt.field(j).type)
                else pa.array([t[j] for t in vals],
                              type=kt.field(j).type)
                for j in range(kt.num_fields)
            ]
            return pa.StructArray.from_arrays(
                children,
                names=[kt.field(j).name for j in range(kt.num_fields)],
            )
        if _is_interned_type(kt):
            return pa.array(vals, type=kt)
        if pa.types.is_unsigned_integer(kt):
            # int64 bit patterns; normalize back
            return pa.array([v % (1 << 64) for v in vals], type=kt)
        if pa.types.is_timestamp(kt):
            return pa.array(vals, type=pa.int64()).cast(kt)
        return pa.array(vals, type=kt)

    # -- "values": the operator keeps its keys as columns --------------------
    # One array per key column, indexed by the operator's own rows (the
    # session table): int64 bit patterns where the type is integer-like
    # (ints, bools, timestamps), plain values in an object array otherwise
    # (strings, floats, a struct as the tuple of its children).

    def int_like(self, ki: int) -> bool:
        kt = self.types[ki]
        return not (pa.types.is_struct(kt) or _is_interned_type(kt))

    def value_columns(self, batch: pa.RecordBatch, key_cols
                      ) -> List[np.ndarray]:
        """A batch's key columns in the stored form. An integer-like
        column that holds nulls arrives as floats and is refused: a NULL
        session key has no bit pattern."""
        out = []
        for ki, c in enumerate(self.columns(batch, key_cols)):
            c = np.asarray(c)
            if not self.int_like(ki):
                out.append(c.astype(object, copy=False))
                continue
            if c.dtype.kind == "M":
                c = c.view("i8")
            elif c.dtype == np.uint64:
                c = c.view(np.int64)
            elif c.dtype.kind not in "iub":
                raise ValueError(
                    f"session key column {ki} ({self.types[ki]}) holds "
                    "nulls")
            out.append(c.astype(np.int64, copy=False))
        return out

    def value_columns_from_values(self, keys: List[list]
                                  ) -> List[np.ndarray]:
        """Portable key rows (a checkpoint's) -> stored columns."""
        out = []
        for ki, kt in enumerate(self.types):
            vals = [k[ki] for k in keys]
            if not self.int_like(ki):
                col = np.empty(len(vals), dtype=object)
                # msgpack round-trips a struct's tuple as a list
                col[:] = [tuple(v) if isinstance(v, list) else v
                          for v in vals]
            elif pa.types.is_unsigned_integer(kt):
                col = np.asarray(vals, dtype=np.uint64).view(np.int64)
            else:
                col = np.asarray(vals, dtype=np.int64)
            out.append(col)
        return out

    def value_lists(self, cols: List[np.ndarray]) -> List[list]:
        """Stored columns -> one list of portable values per column."""
        return [
            (c.view(np.uint64) if c.dtype == np.int64
             and pa.types.is_unsigned_integer(kt) else c).tolist()
            for c, kt in zip(cols, self.types)
        ]

    def arrow_from_arrays(self, ki: int, arrays) -> pa.Array:
        """Key column `ki` from the columns an emission carries: the
        table's word columns ("words") or the operator's stored columns
        ("values")."""
        if self.words:
            return self.arrow_from_words(ki, arrays)
        col = arrays[ki]
        kt = self.types[ki]
        if col.dtype == np.int64:
            if pa.types.is_unsigned_integer(kt):
                return pa.array(col.view(np.uint64), type=kt)
            return pa.array(col).cast(kt)
        return self.arrow_from_keys(
            ki, [(None,) * ki + (v,) for v in col.tolist()])

    # -- table key <-> portable values ---------------------------------------

    def values(self, key: tuple) -> list:
        """Table key tuple -> portable key values."""
        if self.words:
            # struct child words regroup into the portable tuple form
            # (plain ints — nothing is interned here)
            out = []
            off = 0
            for ki, w in enumerate(self._widths):
                if pa.types.is_struct(self.types[ki]):
                    out.append(tuple(int(x) for x in key[off:off + w]))
                else:
                    out.append(_to_py(key[off]))
                off += w
            return out
        out = []
        for ki, k in enumerate(key):
            if self._coded and _is_interned_type(self.types[ki]):
                out.append(unintern_value(_to_py(k)))
            else:
                out.append(_to_py(k))
        return out

    def key(self, values: list) -> tuple:
        """One key's portable values -> the key tuple `values` came from."""
        if self.words:
            out: list = []
            for ki, v in enumerate(values):
                if pa.types.is_struct(self.types[ki]):
                    out.extend(v)
                else:
                    out.append(v)
            return tuple(out)
        if self._coded:
            return tuple(
                intern_value(v) if _is_interned_type(self.types[i]) else v
                for i, v in enumerate(values)
            )
        # msgpack round-trips a struct's tuple as a list
        return tuple(tuple(v) if isinstance(v, list) else v for v in values)

    # -- portable forms -> key columns for assign() -------------------------

    def columns_from_values(self, keys: List[list]) -> List[np.ndarray]:
        """A snapshot's key rows (portable values) -> key columns."""
        key_cols = []
        for i in range(len(keys[0]) if keys else 0):
            vals = [k[i] for k in keys]
            kt = self.types[i]
            if self.words and pa.types.is_struct(kt):
                # portable struct tuples -> child words
                mat = np.asarray([list(v) for v in vals], dtype=np.int64)
                key_cols.extend(mat[:, j] for j in range(self._widths[i]))
            elif _is_interned_type(kt):
                # dtype=object routes through the interning path in
                # assign(); filled in place, or a struct's tuples (lists
                # after msgpack) would make the array 2-d
                col = np.empty(len(vals), dtype=object)
                col[:] = [tuple(v) if isinstance(v, list) else v
                          for v in vals]
                key_cols.append(col)
            else:
                key_cols.append(np.asarray(vals, dtype=np.int64))
        return key_cols

    def columns_from_delta(self, batch: pa.RecordBatch) -> List[np.ndarray]:
        """A delta batch's __k* columns -> key columns (object arrays of
        values for non-integer types, int64 bit patterns otherwise)."""
        names = batch.schema.names
        out = []
        for i, kt in enumerate(self.types):
            col = batch.column(names.index(f"__k{i}"))
            if _is_interned_type(kt):
                out.append(np.array(col.to_pylist(), dtype=object))
            else:
                out.append(np.asarray(col.cast(pa.int64())))
        return out

    # -- key columns / portable rows -> a delta batch's __k* columns --------

    def delta_arrays(self, key_cols: List[np.ndarray]) -> List[pa.Array]:
        """Key columns as `columns` made them (normalized to int64 views
        where integer-like) -> one Arrow array per __k* column."""
        out = []
        for i, kt in enumerate(self.types):
            c = key_cols[i]
            if _is_interned_type(kt):
                out.append(pa.array(c.tolist(), type=kt))
            else:
                out.append(pa.array(c.astype(np.int64, copy=False)))
        return out

    def delta_arrays_from_values(self, key_rows: List[tuple]) -> List[pa.Array]:
        """Portable key rows -> one Arrow array per __k* column (values
        keep their types; the rest are int64 bit patterns whose hash
        matches the shuffle's)."""
        out = []
        for i, kt in enumerate(self.types):
            vals = [k[i] for k in key_rows]
            if _is_interned_type(kt):
                out.append(pa.array(vals, type=kt))
            else:
                out.append(
                    pa.array(np.asarray(vals, dtype=np.int64))
                )
        return out

    # -- portable rows -> the columns the shuffle hashed --------------------

    def hash_columns(self, keys: List[list]) -> list:
        """hash_column per key column (a struct's children in order) of
        portable key rows, dtypes as schema.hash_keys hashed them."""
        cols = []
        for i in range(len(keys[0])):
            vals = [k[i] for k in keys]
            kt = self.types[i]
            if pa.types.is_struct(kt):
                # portable snapshot values are the tuples themselves
                # (msgpack may hand them back as lists); a "codes" key
                # passed in-process is the interned code
                tuples = [
                    unintern_value(v) if isinstance(v, (int, np.integer))
                    else tuple(v)
                    for v in (_to_py(v) for v in vals)
                ]
                for j in range(kt.num_fields):
                    cols.append(hash_column(
                        np.asarray([t[j] for t in tuples], dtype=np.int64)
                    ))
                continue
            if pa.types.is_floating(kt):
                arr = np.asarray(vals, dtype=np.float64)
            elif _is_interned_type(kt):
                arr = np.asarray(vals, dtype=object)
            else:
                arr = np.asarray(vals, dtype=np.int64)
            cols.append(hash_column(arr))
        return cols
