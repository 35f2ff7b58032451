"""A string column is hashed through its dictionary (ISSUE 29).

`types.hash_string_array` gives every row the uint64 that the object-array
route gave it (`hash_column` over one Python `str` per row, nulls replaced
by the sentinel), bit for bit, whichever route it takes; `batch_fingerprint`
and `StreamSchema.partition` therefore give the digests and the owners
they gave. The reference here is the object-array route written out, not a
literal: a change of the hash itself shows in `tests/test_audit.py`'s fixed
digests."""

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu import obs
from arroyo_tpu.connectors import nexmark
from arroyo_tpu.obs import audit, timeline
from arroyo_tpu.schema import StreamSchema
from arroyo_tpu.types import (
    NULL_STRING, _splitmix64, hash_arrays, hash_column, hash_string_array,
    server_for_hash_array,
)

N = 8192


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()         # the phase ledger, and `audit.reset()` with it
    yield
    obs.reset()


def by_object_array(col: pa.Array) -> np.ndarray:
    """The parent's route: one Python object per row."""
    values = [NULL_STRING if v is None else v for v in col.to_pylist()]
    return hash_column(np.asarray(values, dtype=object))


def _sparse(distinct, null_share, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, distinct, N)
    null = rng.random(N) < null_share
    return [None if m else f"item-{i:05d}" for i, m in zip(ids, null)]


def _unique():
    return [f"https://example.test/{i:08x}"
            for i in np.random.default_rng(1).permutation(N)]


# name -> (column, whether it goes through a dictionary)
COLUMNS = {
    "all_null": (pa.nulls(N, pa.string()), True),
    "one_value": (pa.array(["Google"] * N), True),
    "sparse_94pct_null": (pa.array(_sparse(492, 0.94)), True),
    "few_hundred_no_null": (pa.array(_sparse(565, 0.0)), True),
    "all_unique": (pa.array(_unique()), False),
    "unique_after_a_constant_prefix": (
        pa.array(["x"] * (N // 8) + _unique()[N // 8:]), False),
    "empty_string": (pa.array(["", None, "a", ""] * 64), True),
    "sentinel_beside_nulls": (
        pa.array([NULL_STRING, None, "a", None, NULL_STRING, "b"] * 50),
        True),
    "large_string": (pa.array(_sparse(40, 0.5), pa.large_string()), True),
    "large_string_unique": (pa.array(_unique(), pa.large_string()), False),
    "sliced": (pa.array(_sparse(30, 0.3, seed=2)).slice(1234, 4000), True),
    "sliced_unique": (pa.array(_unique()).slice(77, 4001), False),
    "dictionary_typed": (
        pa.array(_sparse(100, 0.2)).dictionary_encode(), True),
    "dictionary_typed_sliced": (
        pa.array(_sparse(100, 0.2)).dictionary_encode().slice(5000, 900),
        True),
    "dictionary_with_a_null_value": (
        pa.DictionaryArray.from_arrays(
            pa.array([0, 1, 2, None, 1, 0, 2, 2] * 40, pa.int32()),
            pa.array(["a", None, ""])), True),
    "dictionary_longer_than_the_rows": (
        pa.DictionaryArray.from_arrays(
            pa.array([3, None, 70], pa.int32()),
            pa.array([f"v{i}" for i in range(100)])), False),
    "zero_rows": (pa.array([], pa.string()), False),
    "one_row": (pa.array(["solo"]), False),
    "a_few_rows_of_one_value": (pa.array(["a", None] * 100), False),
}


@pytest.mark.parametrize("name", sorted(COLUMNS))
def test_every_row_hashes_as_through_an_object_array(name):
    col, via_dictionary = COLUMNS[name]
    got, went = hash_string_array(col)
    want = by_object_array(col)
    assert got.dtype == np.uint64 and got.shape == (len(col),)
    assert np.array_equal(got, want)
    assert went is via_dictionary


def _raw_nexmark_batch(first=40_000):
    ns = np.arange(first, first + N, dtype=np.int64)
    return nexmark.gen_batch(ns, ns * 40_000)


def _parent_col_u64(col: pa.Array) -> np.ndarray:
    """`obs/audit.py` `_col_u64` as the parent had it, for flat columns."""
    t = col.type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return by_object_array(col)
    if col.null_count:
        col = col.fill_null(0 if pa.types.is_timestamp(t)
                            else -(1 << 62) + 12345)
    arr = col.to_numpy(zero_copy_only=False)
    if arr.dtype.kind == "M":
        return arr.view("i8").astype(np.uint64)
    assert arr.dtype.kind in "iu", arr.dtype
    return arr.astype(np.uint64, copy=False)


def _parent_fingerprint(batch: pa.RecordBatch):
    cols = []
    for col in batch.columns:
        if pa.types.is_struct(col.type):
            cols += [_parent_col_u64(col.field(j))
                     for j in range(col.type.num_fields)]
        else:
            cols.append(_parent_col_u64(col))
    salts = audit._col_salts(len(cols))
    with np.errstate(over="ignore"):
        acc = cols[0] * salts[0]
        for c, s in zip(cols[1:], salts[1:]):
            acc = acc + c * s
        return batch.num_rows, int(_splitmix64(acc).sum(dtype=np.uint64))


@pytest.mark.parametrize("first", [0, 40_000, 123_456_789])
def test_a_raw_nexmark_batch_keeps_its_digest(first):
    """Three structs, twelve string children, 94-100 % of each null or one
    of a few hundred values: all twelve go through a dictionary and the
    digest is the object-array route's."""
    batch = _raw_nexmark_batch(first)
    strings = [f for col in batch.columns if pa.types.is_struct(col.type)
               for f in col.type if pa.types.is_string(f.type)]
    assert len(strings) == 12
    tally = [0, 0]
    assert audit.batch_fingerprint(batch, tally) == _parent_fingerprint(batch)
    assert tally == [12, 12]
    assert audit.batch_fingerprint(batch) == _parent_fingerprint(batch)


def test_strings_inside_lists_and_structs_take_the_same_route():
    """A list's flattened values and a nested struct's child are string
    arrays like any other: counted, and hashed as by object array."""
    tags = pa.array([["a", "b"], [], None, ["a"]] * 100,
                    pa.list_(pa.string()))
    inner = pa.array([{"s": "p", "k": i % 3} for i in range(400)],
                     pa.struct([("s", pa.string()), ("k", pa.int64())]))
    batch = pa.RecordBatch.from_arrays(
        [tags, pa.array([{"in": v} for v in inner.to_pylist()],
                        pa.struct([("in", inner.type)]))],
        names=["tags", "outer"])
    tally = [0, 0]
    assert audit.batch_fingerprint(batch, tally)[0] == 400
    assert tally == [2, 2]
    for strings in (tags.flatten(), inner.field(0)):
        assert np.array_equal(
            audit._row_u64(strings, [0, 0]), by_object_array(strings))


# -- the engagement counter ----------------------------------------------------


def _mixed_batch():
    """Five string columns, three of them low-cardinality, and an int."""
    low = pa.array(_sparse(20, 0.5))
    return pa.RecordBatch.from_arrays(
        [pa.array(range(N)), low, pa.array(_unique()),
         pa.array(["c"] * N), pa.array(_unique()[::-1]),
         pa.nulls(N, pa.string())],
        names=["i", "low", "uniq", "const", "uniq2", "null"])


def _string_totals():
    s = audit.status()
    return s["string_columns_hashed"], s["string_columns_via_dictionary"]


def test_the_counter_reads_what_happened():
    batch = _mixed_batch()
    tap, other = audit.EdgeTap("1:0->2:0"), audit.EdgeTap("1:0->5:0")
    with timeline.phase("audit.attest", task="1-0", n=N):
        tap.observe(batch)
    assert _string_totals() == (5, 3)
    t = timeline.totals(task="1-0")
    assert t["audit.fp"]["count"] == 1 and t["audit.fp"]["n"] == N
    assert t["audit.fp.str"]["count"] == 1
    assert (t["audit.fp.str"]["padded"], t["audit.fp.str"]["n"]) == (5, 3)
    assert t["audit.fp.str"]["total_s"] == 0.0
    # a memo hit hashes nothing and books nothing more
    with timeline.phase("audit.attest", task="1-0", n=N):
        other.observe(batch)
        tap.observe(batch)
    assert _string_totals() == (5, 3)
    t = timeline.totals(task="1-0")
    assert t["audit.attest"]["count"] == 2      # the two phases above
    assert t["audit.fp"]["count"] == t["audit.fp.str"]["count"] == 1
    s = audit.status()
    assert (s["fingerprints_observed"], s["fingerprints_computed"]) == (3, 1)
    # another object with the same content computes, and adds
    tap.observe(batch.slice(0))
    assert _string_totals() == (10, 6)
    audit.reset()
    assert _string_totals() == (0, 0)


def test_a_batch_without_strings_books_no_string_note():
    batch = pa.RecordBatch.from_arrays(
        [pa.array(range(100)), pa.array([1.5] * 100)], names=["i", "f"])
    with timeline.phase("audit.attest", task="9-0", n=100):
        audit.EdgeTap("e").observe(batch)
    t = timeline.totals(task="9-0")
    assert t["audit.fp"]["count"] == 1 and "audit.fp.str" not in t
    assert _string_totals() == (0, 0)


# -- the shuffle ---------------------------------------------------------------


def _parent_owner(cols, n):
    """`StreamSchema.hash_keys` + `server_for_hash_array` over the parent's
    `_hash_one` for string keys."""
    return server_for_hash_array(
        hash_arrays([by_object_array(c) for c in cols]), n)


@pytest.mark.parametrize("keys", [
    ["low"], ["uniq"], ["null"], ["low", "uniq"], ["const", "low"],
], ids="+".join)
def test_partition_sends_every_row_to_the_owner_it_had(keys):
    """Parallelism 2 (and 3) over string keys of either cardinality: the
    state key ranges of an existing checkpoint stay valid."""
    batch = _mixed_batch()
    ts = pa.array(np.arange(N) * 1000, pa.timestamp("ns"))
    schema = StreamSchema.from_fields(
        [(f.name, f.type) for f in batch.schema], key_names=keys)
    batch = pa.RecordBatch.from_arrays(
        batch.columns + [ts], schema=schema.schema)
    for n in (2, 3):
        want = _parent_owner([batch.column(k) for k in keys], n)
        parts = schema.partition(batch, n)
        assert sum(p.num_rows for p in parts if p is not None) == N
        for owner, part in enumerate(parts):
            rows = np.flatnonzero(want == owner)
            if part is None:
                assert len(rows) == 0
                continue
            # `i` is the row's number: the partition holds exactly the
            # rows the parent's hash named, in their order
            assert np.array_equal(np.asarray(part.column("i")), rows)
    assert np.array_equal(
        schema.hash_keys(batch),
        hash_arrays([by_object_array(batch.column(k)) for k in keys]))
