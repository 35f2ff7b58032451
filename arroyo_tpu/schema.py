"""StreamSchema — the engine's schema wrapper.

Capability parity with the reference's `ArroyoSchema`
(/root/reference/crates/arroyo-rpc/src/df.rs:24): a pyarrow schema plus the
index of the mandatory `_timestamp` column (TimestampNanosecond) and the
routing-key column indices used for hash shuffles and state sharding.
Every batch flowing through the engine conforms to a StreamSchema.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Sequence

import numpy as np
import pyarrow as pa

from .types import (
    NULL_STRING, hash_arrays, hash_column, hash_string_array, is_string_type,
    server_for_hash_array,
)

TIMESTAMP_FIELD = "_timestamp"
TIMESTAMP_TYPE = pa.timestamp("ns")

# Metadata column carried on updating (retract) streams; mirrors the
# reference's `__updating_meta` struct column (arroyo-rpc/src/lib.rs:333).
UPDATING_META_FIELD = "__updating_meta"
UPDATING_META_TYPE = pa.struct(
    [pa.field("is_retract", pa.bool_()), pa.field("id", pa.binary(16))]
)


def updating_meta_array(n: int, is_retract: bool) -> "pa.StructArray":
    """__updating_meta column for n rows (random ids, shared by the
    updating aggregate and updating join)."""
    import os

    blob = os.urandom(16 * n)
    return pa.StructArray.from_arrays(
        [
            pa.array([is_retract] * n),
            pa.array(
                [blob[16 * i: 16 * (i + 1)] for i in range(n)],
                type=pa.binary(16),
            ),
        ],
        names=["is_retract", "id"],
    )


def add_timestamp_field(schema: pa.Schema) -> pa.Schema:
    """Append `_timestamp` if absent (reference: planner schemas.rs
    add_timestamp_field)."""
    if TIMESTAMP_FIELD in schema.names:
        return schema
    return schema.append(pa.field(TIMESTAMP_FIELD, TIMESTAMP_TYPE, nullable=False))


@dataclasses.dataclass(frozen=True)
class StreamSchema:
    schema: pa.Schema
    key_indices: tuple[int, ...] = ()  # routing key columns (hash shuffle)

    def __post_init__(self):
        if TIMESTAMP_FIELD not in self.schema.names:
            object.__setattr__(self, "schema", add_timestamp_field(self.schema))

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_fields(
        fields: Sequence[tuple[str, pa.DataType]],
        key_names: Iterable[str] = (),
    ) -> "StreamSchema":
        schema = add_timestamp_field(pa.schema([pa.field(n, t) for n, t in fields]))
        keys = tuple(schema.names.index(k) for k in key_names)
        return StreamSchema(schema, keys)

    def with_keys(self, key_names: Iterable[str]) -> "StreamSchema":
        return StreamSchema(
            self.schema, tuple(self.schema.names.index(k) for k in key_names)
        )

    def without_keys(self) -> "StreamSchema":
        return StreamSchema(self.schema, ())

    # -- accessors ----------------------------------------------------------

    @property
    def timestamp_index(self) -> int:
        return self.schema.names.index(TIMESTAMP_FIELD)

    @property
    def names(self) -> list[str]:
        return list(self.schema.names)

    @property
    def key_names(self) -> list[str]:
        return [self.schema.names[i] for i in self.key_indices]

    def field_index(self, name: str) -> int:
        idx = self.schema.names.index(name)
        return idx

    def is_updating(self) -> bool:
        return UPDATING_META_FIELD in self.schema.names

    # -- batch helpers ------------------------------------------------------

    def empty_batch(self) -> pa.RecordBatch:
        return pa.RecordBatch.from_arrays(
            [pa.array([], type=f.type) for f in self.schema], schema=self.schema
        )

    def timestamps(self, batch: pa.RecordBatch) -> np.ndarray:
        """int64 nanos view of the _timestamp column."""
        col = batch.column(self.timestamp_index)
        return np.asarray(col.cast(pa.int64()))

    def hash_keys(self, batch: pa.RecordBatch) -> np.ndarray:
        """uint64 hash of the routing-key columns, the canonical hash used by
        shuffle + state sharding. Unkeyed schemas hash to zeros. Struct
        columns (e.g. window structs) hash their children in order."""
        if not self.key_indices:
            return np.zeros(batch.num_rows, dtype=np.uint64)
        cols = []
        for i in self.key_indices:
            col = batch.column(i)
            if pa.types.is_struct(col.type):
                for j in range(col.type.num_fields):
                    cols.append(_hash_one(col.field(j)))
                continue
            cols.append(_hash_one(col))
        return hash_arrays(cols)

    def partition(self, batch: pa.RecordBatch, n: int) -> list[Optional[pa.RecordBatch]]:
        """Split a batch into n per-partition sub-batches by key hash range
        (reference: arroyo-operator context.rs repartition). Returns None for
        empty partitions to avoid allocating empty batches."""
        if n == 1:
            return [batch]
        parts = server_for_hash_array(self.hash_keys(batch), n)
        order = np.argsort(parts, kind="stable")
        sorted_parts = parts[order]
        boundaries = np.searchsorted(sorted_parts, np.arange(n + 1))
        indices = pa.array(order)
        taken = batch.take(indices)
        out: list[Optional[pa.RecordBatch]] = []
        for i in range(n):
            lo, hi = int(boundaries[i]), int(boundaries[i + 1])
            out.append(taken.slice(lo, hi - lo) if hi > lo else None)
        return out


def _hash_one(col: pa.Array) -> np.ndarray:
    if is_string_type(col.type):
        return hash_string_array(col)[0]
    if col.null_count:
        # nulls hash as a fixed sentinel: substitute before hashing
        col = col.fill_null(_null_sentinel(col.type))
    return hash_column(_to_numpy(col))


def _to_numpy(col: pa.Array) -> np.ndarray:
    try:
        return col.to_numpy(zero_copy_only=False)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
        return np.array(col.to_pylist(), dtype=object)


def _null_sentinel(t: pa.DataType):
    if pa.types.is_integer(t):
        return -(1 << 62) + 12345
    if pa.types.is_floating(t):
        return float("-1.797e308")
    if pa.types.is_boolean(t):
        return False
    if pa.types.is_timestamp(t):
        return 0
    return NULL_STRING
