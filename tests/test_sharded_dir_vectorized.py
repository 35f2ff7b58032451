"""Vectorized ShardedDirectory batch ops (the mesh hot path): every
MeshSlotDirectory batch operation must cross into the native table at
most ONCE per shard (no per-key python iteration), native and python
shard tiers must agree semantically, and the packing rung ladder must
bound padding overshoot. Also covers the micro-flush read-elision of
ShardedAccumulator and the batch free_slots tier the session operator
rides."""

from collections import Counter

import numpy as np
import pytest

from arroyo_tpu.ops.aggregates import AggSpec
from arroyo_tpu.parallel.sharded_state import (
    MESH_STATS,
    STRIDE,
    MeshSlotDirectory,
    _pow2_ladder,
)


@pytest.fixture(scope="module")
def mesh():
    import jax

    from arroyo_tpu.parallel import key_mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs multiple devices")
    return key_mesh(devices)


class CountingSlotDir:
    """Delegating wrapper over the native C SlotDir that counts method
    calls — the unit-level proof that the mesh facade's batch ops are
    one-C-call-per-shard, not per-key loops."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = Counter()

    def __getattr__(self, name):
        fn = getattr(self._inner, name)

        def wrapper(*a, **k):
            self.calls[name] += 1
            return fn(*a, **k)

        return wrapper


def _native_mesh(n_shards=4, n_keys=1):
    from arroyo_tpu.ops.native import load_native

    native = load_native()
    if native is None:
        pytest.skip("native slot directory unavailable")
    d = MeshSlotDirectory(n_shards)
    assert d.swap_to_native(native, n_keys)
    counters = []
    for shard_dir in d.dirs:
        shard_dir._d = CountingSlotDir(shard_dir._d)
        counters.append(shard_dir._d.calls)
    return d, counters


def _populate(d, n=200, bins_mod=3, seed=7):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 60, n)
    bins = rng.integers(0, bins_mod, n)
    slots = d.assign(bins, [keys])
    return bins, keys, slots


def _drain(counters):
    for c in counters:
        c.clear()


def test_batch_ops_one_native_call_per_shard():
    d, counters = _native_mesh()
    _populate(d)
    _drain(counters)

    # items: exactly one entries() crossing per shard, nothing else
    list(d.items())
    assert all(c["entries"] == 1 for c in counters)
    _drain(counters)

    # keys_for_slots over every live slot: one crossing per shard
    all_slots = np.asarray([s for _, _, s in d.items()], dtype=np.int64)
    _drain(counters)
    res = d.keys_for_slots(all_slots)
    assert all(c["keys_for_slots"] <= 1 for c in counters)
    assert sum(c["keys_for_slots"] for c in counters) >= 1
    assert all(r is not None for r in res)
    _drain(counters)

    # slots_for_keys: one lookup per shard for the whole key list
    keys = [k for _, k, _ in d.items()][:50]
    _drain(counters)
    m = d.slots_for_keys(0, keys)
    assert all(c["lookup"] == 1 for c in counters)
    for k, s in m.items():
        assert res[int(np.where(all_slots == s)[0][0])][1] == k
    _drain(counters)

    # bin_entries_multi: one get_bins per shard for all bins at once
    kmat, slots_m = d.bin_entries_multi(np.arange(3))
    assert all(c["get_bins"] == 1 for c in counters)
    assert len(slots_m) == len(all_slots)
    _drain(counters)

    # remove: one crossing per shard, keys matrix built once
    rm = keys[:10]
    freed = d.remove(0, rm)
    assert all(c["remove"] == 1 for c in counters)
    _drain(counters)

    # take_bin_arrays: one take_bin per shard
    cols, slots_t = d.take_bin_arrays(1)
    assert all(c["take_bin"] == 1 for c in counters)
    assert len(cols) == 1 and len(cols[0]) == len(slots_t)


def test_native_matches_python_shard_semantics():
    from arroyo_tpu.ops.native import load_native

    native = load_native()
    if native is None:
        pytest.skip("native slot directory unavailable")
    dp = MeshSlotDirectory(4)
    dn = MeshSlotDirectory(4)
    assert dn.swap_to_native(native, 1)
    bins, keys, _ = _populate(dp)
    _populate(dn)

    assert dp.n_live == dn.n_live
    assert sorted(dp.by_bin) == sorted(dn.by_bin)
    # items agree as sets of (bin, key) with consistent slot ownership
    ip = {(b, k) for b, k, _ in dp.items()}
    in_ = {(b, k) for b, k, _ in dn.items()}
    assert ip == in_

    some = [(int(b), (int(k),)) for b, k in zip(bins[:20], keys[:20])]
    for b, k in some:
        sp = dp.slots_for_keys(b, [k])
        sn = dn.slots_for_keys(b, [k])
        assert set(sp) == set(sn) == {k}
        # same shard ownership (same hash routing) on both tiers
        assert sp[k] // STRIDE == sn[k] // STRIDE

    # keys_for_slots round-trips on both tiers
    for d in (dp, dn):
        slots = np.asarray([s for _, _, s in d.items()], dtype=np.int64)
        back = d.keys_for_slots(slots)
        assert {(b, k) for b, k in back} == ip
        # unknown slot resolves to None on both tiers
        assert d.keys_for_slots(
            np.asarray([7 * STRIDE + 12345], dtype=np.int64)
        ) == [None]

    # remove frees the same (bin, key) population
    rm_keys = [(int(k),) for k in sorted({int(k) for k in keys[:30]})]
    fp = dp.remove(1, rm_keys)
    fn = dn.remove(1, rm_keys)
    assert len(fp) == len(fn)
    assert dp.n_live == dn.n_live


def test_bin_entries_multi_matches_per_bin():
    d, _ = _native_mesh()
    _populate(d, n=300, bins_mod=5)
    kmat, slots = d.bin_entries_multi(np.arange(5))
    per_bin = []
    for b in range(5):
        km, s = d.bin_entries(b)
        if len(s):
            per_bin.append((km, s))
    want_slots = np.concatenate([s for _, s in per_bin])
    assert sorted(slots.tolist()) == sorted(want_slots.tolist())
    want_keys = np.concatenate([k for k, _ in per_bin])
    assert sorted(map(tuple, kmat.tolist())) == sorted(
        map(tuple, want_keys.tolist())
    )


def test_pow2_ladder_overshoot_bounds():
    # eighth rungs from 512, quarters from 64, pure pow2 below: the
    # ladder is deliberately COARSER than the round-5 sixteenth ladder
    # (every distinct rung hit costs a python trace + XLA compile per
    # process; wander is absorbed by _StickyRung, not ladder density)
    ladder = _pow2_ladder(1 << 20, floor=2)
    from arroyo_tpu.ops.aggregates import _bucket

    assert ladder[0] == 2 and ladder[-1] == 1 << 20
    assert list(ladder) == sorted(set(ladder))
    for n in range(2, 50000, 7):
        b = _bucket(n, ladder)
        assert b >= n
        over = b / n
        if n >= 512:
            assert over <= 1.125 + 0.01
        elif n >= 64:
            assert over <= 1.25 + 0.01
        else:
            assert over <= 2.0


def test_sticky_rung_hysteresis():
    """The rung must not follow per-flush wander (each rung change is a
    fresh XLA trace): it climbs exactly on overflow, holds across
    in-rung wander, and decays one rung only after a sustained shrink."""
    from arroyo_tpu.parallel.sharded_state import _StickyRung

    ladder = _pow2_ladder(1 << 16, floor=16)
    r = _StickyRung(ladder, decay_after=4)
    assert r.fit(100) == 112  # first fit: exact bucket, no headroom
    # wander within the rung: no change
    for n in (90, 112, 60, 111):
        assert r.fit(n) == 112
    # overflow climbs to bucket(1.25 * n) — headroom so a ramp does not
    # walk (and trace) every rung on its way up
    assert r.fit(1000) == 1280
    # sizes above half the rung: sticky forever
    for n in (700, 800, 641) * 4:
        assert r.fit(n) == 1280
    # sustained shrink below half: decays ONE rung after decay_after
    for _ in range(3):
        assert r.fit(100) == 1280
    assert r.fit(100) == 1152  # 4th consecutive low fit steps down
    # a single low fit never decays (first fit is exact: bucket(1000))
    r2 = _StickyRung(ladder, decay_after=4)
    assert r2.fit(1000) == 1024
    r2.fit(100)
    assert r2.fit(900) == 1024


def test_free_slots_batch_recycles_per_shard():
    d = MeshSlotDirectory(4)
    slots = d.alloc_slots(32, shard_hint=0)
    d.free_slots(slots)
    assert sum(len(sd.free) for sd in d.dirs) == 32
    # recycled without advancing any shard's high-water mark
    marks = [sd.next_slot for sd in d.dirs]
    again = d.alloc_slots(32, shard_hint=0)
    assert [sd.next_slot for sd in d.dirs] == marks
    assert sorted(np.asarray(again) // STRIDE) == sorted(
        np.asarray(slots) // STRIDE
    )


def test_flush_elision_skips_disjoint_reads(mesh):
    from arroyo_tpu.parallel import ShardedAccumulator

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=64,
                             rows_per_shard=64, flush_rows=1 << 30)
    d = MeshSlotDirectory(acc.n_shards)
    slots_a = d.assign(np.zeros(32, dtype=np.int64),
                       [np.arange(32, dtype=np.int64)])
    vals = np.full(32, 3, dtype=np.int64)
    acc.update(slots_a, {0: vals})
    assert acc._pending, "flush_rows threshold should buffer the update"
    slots_b = d.assign(np.ones(8, dtype=np.int64),
                       [np.arange(8, dtype=np.int64)])
    before = MESH_STATS["flushes_elided"]
    out_b = acc.gather(slots_b)
    # disjoint read: buffered rows stay pending, elision counted
    assert acc._pending
    assert MESH_STATS["flushes_elided"] == before + 1
    assert np.asarray(out_b[0]).tolist() == [0] * 8
    # touching read flushes and observes every buffered row
    out_a = acc.gather(slots_a)
    assert not acc._pending
    assert np.asarray(out_a[0]).tolist() == [1] * 32
    assert np.asarray(out_a[1]).tolist() == [3] * 32
    # reset of disjoint slots also elides; of touched slots flushes
    acc.update(slots_a, {0: vals})
    before = MESH_STATS["flushes_elided"]
    acc.reset_slots(slots_b)
    assert acc._pending and MESH_STATS["flushes_elided"] == before + 1
    acc.reset_slots(slots_a)
    assert not acc._pending
    out_a = acc.gather(slots_a)
    assert np.asarray(out_a[0]).tolist() == [0] * 32


def test_sessions_hold_no_slot_they_do_not_use():
    """Slots are allocated a batch's misses at a time, one directory call
    each, and freed a watermark's closes at a time: no pool stands between
    the operator and the directory, so a checkpoint can strand no
    allocated-but-unused slot (ADVICE round 5) and a close's slots are the
    next open's."""
    import asyncio
    import types

    import pyarrow as pa

    from arroyo_tpu.operators.windows import SessionWindowOperator
    from arroyo_tpu.schema import StreamSchema
    from arroyo_tpu.types import WatermarkKind

    in_schema = StreamSchema.from_fields([("k", pa.int64())])
    op = SessionWindowOperator({
        "aggregates": [{"kind": "count", "name": "cnt"}],
        "schema": StreamSchema.from_fields(
            [("k", pa.int64()), ("cnt", pa.int64())]
        ),
        "gap_nanos": 1000,
        "key_cols": [0],
    })
    ctx = types.SimpleNamespace(
        in_schemas=[in_schema], table_manager=None,
        watermarks=types.SimpleNamespace(current_nanos=lambda: None))
    calls = []
    alloc = op.dir.alloc_slots
    op.dir.alloc_slots = lambda n, hint=0: calls.append(n) or alloc(n, hint)

    def batch(keys, ts):
        return pa.RecordBatch.from_arrays(
            [pa.array(keys, type=pa.int64()),
             pa.array(ts, type=pa.timestamp("ns"))],
            schema=in_schema.schema)

    class Sink:
        async def collect(self, b):
            pass

    async def go():
        await op.process_batch(batch(list(range(70)), [10] * 70), ctx, None)
        assert calls == [70] and op.dir.next_slot == 70 and not op.dir.free
        await op.handle_checkpoint(None, ctx, None)
        assert op.dir.next_slot == 70 and not op.dir.free
        # keys 0..29 go quiet and close; their 30 slots are the next 30
        await op.process_batch(
            batch(list(range(30, 70)), [900] * 40), ctx, None)
        await op.handle_watermark(types.SimpleNamespace(
            kind=WatermarkKind.EVENT_TIME, timestamp=1200), ctx, Sink())
        assert len(op.dir.free) == 30 and len(op.sessions) == 40
        await op.process_batch(
            batch(list(range(100, 130)), [1300] * 30), ctx, None)
        assert calls == [70, 30]
        assert op.dir.next_slot == 70 and not op.dir.free

    asyncio.run(go())
