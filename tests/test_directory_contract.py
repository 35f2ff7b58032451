"""The slot directory's seam (`ops/directory.py`): every table
`make_directory` can return keeps one contract, and `KeyCodec` round-trips
a key through each encoding (batch -> table -> emitted Arrow, snapshot ->
restore, delta columns, the shuffle's hash)."""

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu.ops import native
from arroyo_tpu.ops.directory import KeyCodec, make_directory
from arroyo_tpu.schema import StreamSchema
from arroyo_tpu.types import hash_arrays

# every table the factory can return for integer keys: the python one
# (a host without the C++ module), the native one, and on a mesh the
# per-shard and the salted facade over either
TABLES = {
    "python": dict(),
    "native": dict(),
    "mesh-python": dict(mesh_shards=4),
    "mesh-native": dict(mesh_shards=4),
    "salted-python": dict(mesh_shards=4, salted=True),
    "salted-native": dict(mesh_shards=4, salted=True),
}


def _without_native(monkeypatch):
    monkeypatch.setenv("ARROYO_DISABLE_NATIVE", "1")
    monkeypatch.setattr(native, "_native", None)


@pytest.fixture(params=list(TABLES))
def make(request, monkeypatch):
    """make(n_keys) -> a fresh table for n_keys int64 key columns."""
    name = request.param
    want = "codes" if name.endswith("python") else "words"
    if want == "codes":
        _without_native(monkeypatch)

    def build(n_keys=1):
        d = make_directory([pa.int64()] * n_keys, **TABLES[name])
        assert d.key_encoding == want
        if "mesh" in name or "salted" in name:
            assert d.n_shards == 4
        return d

    return build


def _i(*vals):
    return np.asarray(vals, dtype=np.int64)


def test_assignment_equals_dict_reference(make):
    rng = np.random.default_rng(5)
    d = make()
    ref = {}
    for _ in range(6):
        bins = rng.integers(0, 4, 700)
        keys = rng.integers(0, 150, 700)
        slots = d.assign(bins, [keys])
        want = np.asarray([
            ref.setdefault((int(b), int(k)), len(ref))
            for b, k in zip(bins, keys)
        ])
        # same rows land in the same group (slot numbering may differ):
        # slot <-> reference id is a bijection on the rows
        pairs = set(zip(slots.tolist(), want.tolist()))
        assert len(pairs) == len({p[0] for p in pairs})
        assert len(pairs) == len({p[1] for p in pairs})
    assert d.n_live == len(ref)
    assert d.required_capacity() > max(
        s % (1 << 32) for _b, _k, s in d.items())


def test_same_group_same_slot_across_batches(make):
    d = make()
    s1 = d.assign(_i(1, 1), [_i(7, 8)])
    s2 = d.assign(_i(1, 1, 1), [_i(8, 7, 9)])
    assert s1[0] == s2[1] and s1[1] == s2[0]
    assert s2[2] not in (s1[0], s1[1])


def test_take_bin_frees_and_reuses_slots(make):
    d = make()
    bins, keys = np.zeros(5, dtype=np.int64), np.arange(5)
    slots = d.assign(bins, [keys])
    got_keys, got_slots = d.take_bin(0)
    assert sorted(k[0] for k in got_keys) == list(range(5))
    assert sorted(got_slots.tolist()) == sorted(slots.tolist())
    assert d.n_live == 0
    # the groups are gone: the same (bin, key) pairs draw the freed slots
    s2 = d.assign(bins, [keys])
    assert set(s2.tolist()) == set(slots.tolist())
    assert d.n_live == 5


def test_multi_word_keys_and_bin_isolation(make):
    d = make(2)
    k1, k2 = _i(1, 1, 2), _i(10, 11, 10)
    s = d.assign(_i(0, 0, 0), [k1, k2])
    assert len(set(s.tolist())) == 3
    # same keys, another bin -> other groups
    s_other = d.assign(_i(1, 1, 1), [k1, k2])
    assert not (set(s.tolist()) & set(s_other.tolist()))
    want = [(1, 10), (1, 11), (2, 10)]
    if d.key_encoding == "words":
        cols, slots0 = d.take_bin_arrays(0)
        got = list(zip(cols[0].tolist(), cols[1].tolist()))
    else:
        got, slots0 = d.take_bin(0)
    assert sorted(got) == want
    assert sorted(slots0.tolist()) == sorted(s.tolist())
    assert d.n_live == 3


def test_growth_keeps_entries(make):
    d = make()
    bins, keys = np.zeros(5000, dtype=np.int64), np.arange(5000)
    s1 = d.assign(bins, [keys])
    assert d.n_live == 5000 and len(set(s1.tolist())) == 5000
    assert np.array_equal(d.assign(bins, [keys]), s1)


def test_bin_entries_does_not_consume(make):
    d = make()
    s = d.assign(_i(3, 3), [_i(1, 2)])
    keys, slots = d.bin_entries(3)
    assert sorted(slots.tolist()) == sorted(s.tolist())
    if d.key_encoding == "words":
        assert keys.shape == (2, 1)
        kmat, slots_m = d.bin_entries_multi(_i(2, 3, 4))
        assert sorted(kmat[:, 0].tolist()) == [1, 2]
        assert sorted(slots_m.tolist()) == sorted(s.tolist())
    else:
        assert sorted(keys) == [(1,), (2,)]
    assert d.n_live == 2
    assert list(d.by_bin) == [3] and d.live_bins() == [3]
    assert d.bins_up_to(4) == [3] and d.bins_up_to(3) == []


def test_keys_for_slots_and_point_lookup(make):
    d = make()
    slots = d.assign(np.zeros(6, dtype=np.int64), [_i(10, 20, 30, 10, 20, 40)])
    entries = d.keys_for_slots(np.unique(slots))
    assert all(e is not None and e[0] == 0 for e in entries)
    assert sorted(e[1][0] for e in entries) == [10, 20, 30, 40]
    assert d.keys_for_slots(_i(99999))[0] is None
    peek = d.peek_bin(0)
    assert peek[(10,)] == int(slots[0]) and peek[(40,)] == int(slots[5])
    # point lookups resolve only the keys that are there
    assert d.slots_for_keys(0, [(20,), (77,)]) == {(20,): int(slots[1])}


def test_targeted_remove(make):
    d = make()
    bins, keys = np.zeros(4, dtype=np.int64), _i(1, 2, 3, 4)
    slots = d.assign(bins, [keys])
    freed = d.remove(0, [(2,), (4,)])
    assert sorted(freed.tolist()) == sorted([int(slots[1]), int(slots[3])])
    assert d.n_live == 2
    # survivors keep their slots, the removed keys come back as new groups
    s2 = d.assign(bins, [keys])
    assert s2[0] == slots[0] and s2[2] == slots[2]
    assert d.n_live == 4
    d.remove(0, [(1,), (2,), (3,), (4,)])
    assert d.n_live == 0 and d.peek_bin(0) is None


def test_sessions_get_a_python_table_whatever_the_mesh():
    """uses_assign=False: no key reaches the table, the imperative
    allocator is the python one's, and the codec leaves values alone."""
    for kw in TABLES.values():
        d = make_directory(None, uses_assign=False, **kw)
        assert d.key_encoding == "values"
        got = d.alloc_slots(8).tolist()
        assert len(set(got)) == 8
        d.free_slots(np.asarray(got[:3]))
        assert d.required_capacity() >= 1
    codec = KeyCodec([pa.string(), pa.int64()], "values")
    assert codec.key(["a", 3]) == ("a", 3)
    assert codec.values(("a", np.int64(3))) == ["a", 3]
    assert codec.arrow_from_keys(0, [("a", 3), ("b", 4)]).equals(
        pa.array(["a", "b"]))


def _window_array(starts):
    s = pa.array(np.asarray(starts, dtype=np.int64)).cast(pa.timestamp("ns"))
    e = pa.array(np.asarray(starts, dtype=np.int64) + 10).cast(
        pa.timestamp("ns"))
    return pa.StructArray.from_arrays([s, e], names=["start", "end"])


# name -> (key columns, the encoding the factory must choose, native off)
CODEC_CASES = {
    "int-keys": (
        [pa.array([5, 6, 5, -7], type=pa.int64()),
         pa.array([1, 2, 1, 2 ** 64 - 1], type=pa.uint64())],
        "words", False),
    "string-key": (
        [pa.array(["a", "b", "a", "c"]),
         pa.array([1, 2, 1, 3], type=pa.int32())],
        "codes", False),
    "window-struct-python": (
        [_window_array([0, 10, 0, 20]), pa.array([1, 1, 1, 2])],
        "codes", True),
    "window-struct-native": (
        [_window_array([0, 10, 0, 20]), pa.array([1, 1, 1, 2])],
        "words", False),
}


def _portable(arrays):
    """Key rows of a batch in the snapshot's portable form."""
    cols = []
    for a in arrays:
        if pa.types.is_struct(a.type):
            kids = [a.field(j).cast(pa.int64()).to_pylist()
                    for j in range(a.type.num_fields)]
            cols.append(list(zip(*kids)))
        elif pa.types.is_unsigned_integer(a.type):
            # a key column's int64 bit pattern
            cols.append(np.asarray(a).view(np.int64).tolist())
        else:
            cols.append(a.to_pylist())
    return [list(r) for r in zip(*cols)]


@pytest.mark.parametrize("case", list(CODEC_CASES))
def test_codec_round_trip(case, monkeypatch):
    arrays, encoding, python_only = CODEC_CASES[case]
    if python_only:
        _without_native(monkeypatch)
    types = [a.type for a in arrays]
    names = [f"k{i}" for i in range(len(arrays))]
    batch = pa.RecordBatch.from_arrays(arrays, names=names)
    rows = _portable(arrays)
    distinct = sorted(set(map(tuple, rows)))
    bins = np.zeros(batch.num_rows, dtype=np.int64)

    table = make_directory(types)
    assert table.key_encoding == encoding
    codec = KeyCodec(types, table.key_encoding)
    slots = table.assign(bins, codec.columns(batch, range(len(arrays))))
    assert slots[0] == slots[2] and len(set(slots.tolist())) == 3

    # table key <-> portable values, and the snapshot the operator writes
    snap = [(codec.values(key), slot) for _b, key, slot in table.items()]
    assert sorted(tuple(v) for v, _ in snap) == distinct
    for _b, key, _slot in table.items():
        assert codec.key(codec.values(key)) == key
    assert [tuple(codec.values(k)) for _b, k in
            table.keys_for_slots(slots)] == [tuple(r) for r in rows]

    # snapshot -> restore into a fresh table: every slot holds its key
    table2 = make_directory(types)
    restored = table2.assign(
        np.zeros(len(snap), dtype=np.int64),
        codec.columns_from_values([v for v, _ in snap]),
    )
    assert [tuple(codec.values(k)) for _b, k in
            table2.keys_for_slots(restored)] == [tuple(v) for v, _ in snap]

    # the shuffle's hash of the portable rows is the batch's own
    schema = StreamSchema.from_fields(list(zip(names, types)), names)
    assert np.array_equal(hash_arrays(codec.hash_columns(rows)),
                          schema.hash_keys(batch))

    # delta columns (operators write them for non-struct keys only)
    if not any(pa.types.is_struct(t) for t in types):
        for delta in (
            codec.delta_arrays([np.asarray(c).view(np.int64)
                                if np.asarray(c).dtype == np.uint64 else c
                                for c in codec.columns(batch, range(2))]),
            codec.delta_arrays_from_values(rows),
        ):
            b = pa.RecordBatch.from_arrays(delta, names=["__k0", "__k1"])
            again = table.assign(bins, codec.columns_from_delta(b))
            assert np.array_equal(again, slots)

    # emission: the taken bin's keys as Arrow arrays of the declared types
    want = pa.RecordBatch.from_arrays(arrays, names=names).take(
        pa.array([0, 1, 3]))
    if codec.words:
        word_cols, taken = table2.take_bin_arrays(0)
        got = [codec.arrow_from_words(i, word_cols) for i in range(2)]
        assert sorted(zip(*(g.to_pylist() for g in got)), key=repr) == \
            sorted(zip(*(c.to_pylist() for c in want.columns)), key=repr)
    keys, taken = table.take_bin(0)
    got = [codec.arrow_from_keys(i, keys) for i in range(2)]
    assert [g.type for g in got] == types
    assert sorted(zip(*(g.to_pylist() for g in got)), key=repr) == \
        sorted(zip(*(c.to_pylist() for c in want.columns)), key=repr)
    assert sorted(taken.tolist()) == sorted(set(slots.tolist()))


def test_session_window_with_a_string_key_emits_its_keys():
    """Sessions keep their keys as values ("values" encoding): a string
    key comes out as it went in (it crashed in `unintern_value` while the
    session operator shared the python table's "codes" emission)."""
    import asyncio
    import types

    from arroyo_tpu.operators.windows import SessionWindowOperator
    from arroyo_tpu.types import WatermarkKind

    op = SessionWindowOperator({
        "aggregates": [{"kind": "count", "name": "cnt"}],
        "schema": StreamSchema.from_fields(
            [("k", pa.string()), ("cnt", pa.int64())]),
        "gap_nanos": 1000,
        "key_cols": [0],
    })
    in_schema = StreamSchema.from_fields([("k", pa.string())])
    ctx = types.SimpleNamespace(
        table_manager=None, in_schemas=[in_schema],
        watermarks=types.SimpleNamespace(current_nanos=lambda: None),
    )
    batch = pa.RecordBatch.from_arrays(
        [pa.array(["a", "b", "a"]),
         pa.array([1, 2, 3], type=pa.timestamp("ns"))],
        schema=in_schema.schema,
    )
    out = []

    class Collector:
        async def collect(self, b):
            out.extend(b.to_pylist())

    async def go():
        await op.process_batch(batch, ctx, Collector())
        await op.handle_watermark(
            types.SimpleNamespace(kind=WatermarkKind.EVENT_TIME,
                                  timestamp=10_000), ctx, Collector())

    asyncio.run(go())
    assert sorted((r["k"], r["cnt"]) for r in out) == [("a", 2), ("b", 1)]
    assert op.dir.key_encoding == "values" and not op.sessions
