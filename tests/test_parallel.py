"""Mesh-sharded accumulator on the virtual 8-device CPU mesh: all_to_all
routing + scatter-reduce must match the single-device result exactly."""

import numpy as np
import pandas as pd
import pytest

from arroyo_tpu.ops.aggregates import AggSpec
from arroyo_tpu.types import hash_column, server_for_hash_array


@pytest.fixture(scope="module")
def mesh():
    import jax

    from arroyo_tpu.parallel import key_mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs multiple devices")
    return key_mesh(devices)


def test_sharded_accumulator_matches_pandas(mesh):
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [
        AggSpec("count", None, "cnt"),
        AggSpec("sum", 0, "total"),
        AggSpec("max", 1, "hi", is_float=True),
    ]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=256,
                             rows_per_shard=512)
    d = MeshSlotDirectory(acc.n_shards)
    rng = np.random.default_rng(3)
    n = 6000
    keys = rng.integers(0, 40, n)
    bins = rng.integers(0, 3, n)
    ints = rng.integers(-50, 50, n)
    floats = rng.random(n) * 10
    for lo in range(0, n, 1500):
        hi = min(lo + 1500, n)
        slots = d.assign(bins[lo:hi], [keys[lo:hi]])
        acc.update(slots, {0: ints[lo:hi], 1: floats[lo:hi]})
    df = pd.DataFrame({"b": bins, "k": keys, "i": ints, "f": floats})
    want = df.groupby(["b", "k"]).agg(
        cnt=("i", "size"), total=("i", "sum"), hi=("f", "max")
    )
    seen = 0
    for b in range(3):
        keys_out, slots = d.take_bin(b)
        gathered = acc.gather(slots)
        assert len(keys_out) == len(want.loc[b])
        for key, cnt, total, hi_ in zip(
            keys_out, gathered[0], gathered[1], gathered[2]
        ):
            row = want.loc[(b, key[0])]
            assert cnt == row["cnt"]
            assert total == row["total"]
            assert hi_ == pytest.approx(row["hi"])
            seen += 1
        acc.reset_slots(slots)
    assert seen == len(want)


def test_sharded_routing_respects_hash_ranges(mesh):
    """Rows must land on the shard that owns their hash range — the same
    mapping the host shuffle and state restore use."""
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [AggSpec("count", None, "cnt")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=64,
                             rows_per_shard=256)
    d = MeshSlotDirectory(acc.n_shards)
    keys = np.arange(100, dtype=np.int64)
    # canonical shuffle hash: per-column hashes combined with the seed
    # (types.hash_arrays), matching schema.hash_keys and restore's
    # _range_mask
    from arroyo_tpu.types import hash_arrays

    owners = server_for_hash_array(
        hash_arrays([hash_column(keys)]), acc.n_shards
    )
    slots = d.assign(np.zeros(100, dtype=np.int64), [keys])
    acc.update(slots, {})
    for shard in range(acc.n_shards):
        expect = set(keys[owners == shard].tolist())
        got = {key[0] for _, key, _ in d.dirs[shard].items()}
        assert got == expect


def test_sharded_capacity_growth(mesh):
    """More keys than a shard's initial capacity: grow() must preserve all
    live values (stride-encoded slots are stable across growth)."""
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [AggSpec("sum", 0, "total")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=8,
                             rows_per_shard=64)
    d = MeshSlotDirectory(acc.n_shards)
    rng = np.random.default_rng(11)
    n = 4000
    keys = rng.integers(0, 500, n)
    vals = rng.integers(0, 100, n)
    bins = np.zeros(n, dtype=np.int64)
    for lo in range(0, n, 400):
        hi = min(lo + 400, n)
        slots = d.assign(bins[lo:hi], [keys[lo:hi]])
        need = d.required_capacity()
        if need > acc.capacity - 1:
            acc.grow(need + 1)
        acc.update(slots, {0: vals[lo:hi]})
    assert acc.capacity > 8
    keys_out, slots = d.take_bin(0)
    gathered = acc.gather(slots)
    want = pd.Series(vals).groupby(keys).sum()
    assert len(keys_out) == len(want)
    for key, total in zip(keys_out, gathered[0]):
        assert total == want.loc[key[0]]


def test_sharded_signed_updates(mesh):
    """Retraction path: signed updates must be invertible on the mesh."""
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=64,
                             rows_per_shard=64)
    d = MeshSlotDirectory(acc.n_shards)
    keys = np.array([1, 2, 3, 1, 2, 3], dtype=np.int64)
    vals = np.array([10, 20, 30, 10, 20, 30], dtype=np.int64)
    bins = np.zeros(6, dtype=np.int64)
    slots = d.assign(bins, [keys])
    acc.update(slots, {0: vals})  # two appends per key
    signs = np.array([-1, -1, -1], dtype=np.int64)
    slots_r = d.assign(bins[:3], [keys[:3]])
    acc.update(slots_r, {0: vals[:3]}, signs=signs)  # retract one each
    keys_out, slots_all = d.take_bin(0)
    gathered = acc.gather(slots_all)
    for key, cnt, total in zip(keys_out, gathered[0], gathered[1]):
        assert cnt == 1
        assert total == key[0] * 10


def test_packed_exchange_sized_to_batch(mesh):
    """The all_to_all buffer must be bucketed to the batch, not the
    configured rows_per_shard ceiling: a small uniform batch on 8 shards
    ships far fewer padding rows than the old dense S*S*rows_per_shard
    layout, while a skewed batch still lands every row (VERDICT r3
    item 2)."""
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=4096,
                             rows_per_shard=1024)
    d = MeshSlotDirectory(acc.n_shards)
    S = acc.n_shards

    # uniform batch: per-owner counts ~n/S, per-cell ~n/S^2 -> R buckets
    # near n/S^2, padding bounded by one bucket step (4x), not the 87%
    # of the dense layout
    n = 8192
    keys = np.arange(n) % 1000
    bins = np.zeros(n, dtype=np.int64)
    slots = d.assign(bins, [keys])
    acc.update(slots, {0: np.ones(n, dtype=np.int64)})
    dense = S * S * 1024
    # the host combiner collapses the 8192 rows to their 1000 unique
    # slots before packing; shipped rows are the combined count + rung
    # padding, far under both the raw batch and the dense layout
    assert acc.rows_sent == 1000
    total_shipped = acc.rows_sent + acc.rows_padded
    assert total_shipped < dense / 2, (
        f"shipped {total_shipped} rows, dense layout would ship {dense}"
    )
    assert total_shipped < n

    # skewed batch: every row hits one owner shard; still exact
    acc2 = ShardedAccumulator(specs, mesh, capacity_per_shard=4096,
                              rows_per_shard=1024)
    d2 = MeshSlotDirectory(acc2.n_shards)
    hot = np.full(4096, 7, dtype=np.int64)
    bins2 = np.zeros(4096, dtype=np.int64)
    s2 = d2.assign(bins2, [hot])
    acc2.update(s2, {0: np.ones(4096, dtype=np.int64)})
    _, slots_out = d2.take_bin(0)
    g = acc2.gather(slots_out)
    assert g[0][0] == 4096 and g[1][0] == 4096


def test_the_legacy_a2a_layout_is_refused_by_name(mesh):
    """The mesh has two exchange layouts, `device` and `host_fed`; the
    host-packed [S, S, R] `a2a` went, and asking for it (the ctor's
    `exchange=` or `tpu.mesh_exchange`) is an error like any other
    unknown name, not a quiet fallback."""
    from arroyo_tpu.config import update
    from arroyo_tpu.parallel import ShardedAccumulator

    specs = [AggSpec("count", None, "cnt")]
    with pytest.raises(ValueError, match="auto\\|device\\|host_fed, got 'a2a'"):
        ShardedAccumulator(specs, mesh, exchange="a2a")
    with update(tpu={"mesh_exchange": "a2a"}):
        with pytest.raises(ValueError, match="got 'a2a'"):
            ShardedAccumulator(specs, mesh)
    with pytest.raises(TypeError):
        ShardedAccumulator(specs, mesh, host_fed=False)
    assert ShardedAccumulator(specs, mesh)._exchange == "host_fed"


def test_salted_accumulator_low_cardinality(mesh):
    """Salted mode: rows spread round-robin across shards and fold at
    gather — results identical to pandas; shipped rows stay near the
    batch size even when every row hits ONE group (the case hash
    ownership starves to a single shard)."""
    from arroyo_tpu.parallel import (
        SharedMeshSlotDirectory,
        ShardedAccumulator,
    )

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total"),
             AggSpec("max", 1, "hi")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=256,
                             rows_per_shard=1024, salted=True)
    d = SharedMeshSlotDirectory(acc.n_shards)
    rng = np.random.default_rng(21)
    n = 8000
    # 3 groups over 8 shards: unsalted, at most 3 shards would work
    keys = rng.integers(0, 3, n)
    bins = np.zeros(n, dtype=np.int64)
    ints = rng.integers(-100, 100, n)
    ints2 = rng.integers(0, 10_000, n)
    slots = d.assign(bins, [keys])
    acc.update(slots, {0: ints, 1: ints2})
    # the combiner collapses the whole batch to its 3 groups before the
    # spread — shipped rows are bounded by the packing floor, not the
    # batch, and certainly not S * max-group
    assert acc.rows_sent == 3
    assert acc.rows_sent + acc.rows_padded <= acc.n_shards * 16

    import pandas as pd

    df = pd.DataFrame({"k": keys, "i": ints, "j": ints2})
    want = df.groupby("k").agg(cnt=("i", "size"), total=("i", "sum"),
                               hi=("j", "max"))
    got_keys, got_slots = d.take_bin(0)
    g = acc.gather(got_slots)
    for key, c, t, h in zip(got_keys, g[0], g[1], g[2]):
        row = want.loc[key[0]]
        assert c == row["cnt"] and t == row["total"] and h == row["hi"]
    # reset + reuse: freed slots start neutral on every shard
    acc.reset_slots(got_slots)
    s2 = d.assign(np.ones(4, dtype=np.int64), [np.arange(4)])
    acc.update(s2, {0: np.ones(4, dtype=np.int64),
                    1: np.full(4, 7, dtype=np.int64)})
    g2 = acc.gather(s2)
    assert list(g2[0]) == [1, 1, 1, 1]


def test_salted_restore_roundtrip(mesh):
    """Checkpoint roundtrip: snapshot -> reset -> restore -> gather must
    reproduce values (restore lands on the nominal shard, rest neutral)."""
    from arroyo_tpu.parallel import (
        SharedMeshSlotDirectory,
        ShardedAccumulator,
    )

    specs = [AggSpec("count", None, "cnt"), AggSpec("min", 0, "lo")]
    acc = ShardedAccumulator(specs, mesh, capacity_per_shard=64,
                             rows_per_shard=128, salted=True)
    d = SharedMeshSlotDirectory(acc.n_shards)
    keys = np.arange(5)
    bins = np.zeros(5, dtype=np.int64)
    slots = d.assign(np.repeat(bins, 40), [np.repeat(keys, 40)])
    acc.update(slots, {0: np.tile(np.arange(40), 5)})
    uniq = d.bin_entries(0)[1]
    vals = [np.asarray(v) for v in acc.gather(uniq)]
    acc.reset_slots(uniq)
    acc.restore(uniq, vals)
    back = acc.gather(uniq)
    assert np.array_equal(np.asarray(back[0]), vals[0])
    assert np.array_equal(np.asarray(back[1]), vals[1])


# -- device-resident exchange (ISSUE 7) ---------------------------------------


def test_device_owner_hash_matches_directory():
    """Routing-contract property test: device-side owner hashing
    (device_owners_for — the jax splitmix64 mirror jitted route steps
    use for raw key words) must agree bit-for-bit with
    MeshSlotDirectory.owners_for for random key columns across shard
    counts 2/4/8, including multi-column keys and edge-pattern words."""
    from arroyo_tpu.parallel.sharded_state import (
        MeshSlotDirectory,
        device_owners_for,
    )

    rng = np.random.default_rng(7)
    edge = np.array(
        [0, 1, -1, 2**63 - 1, -(2**63), 42, -42, 2**32, -(2**32)],
        dtype=np.int64,
    )
    for n_shards in (2, 4, 8):
        d = MeshSlotDirectory(n_shards)
        for n_cols in (1, 2, 3):
            for trial in range(4):
                n = int(rng.integers(1, 2000))
                cols = [
                    np.concatenate([
                        rng.integers(-2**62, 2**62, n, dtype=np.int64),
                        edge,
                    ])
                    for _ in range(n_cols)
                ]
                host = d.owners_for(cols, len(cols[0]))
                dev = np.asarray(device_owners_for(cols, n_shards))
                assert host.dtype == np.int64
                assert (host == dev).all(), (
                    f"owner mismatch at shards={n_shards} cols={n_cols}"
                )
                assert (dev >= 0).all() and (dev < n_shards).all()


def test_device_exchange_matches_host_fed(mesh):
    """The fused route+scatter+reduce program (device exchange) must
    produce state identical to the host-fed combiner path for the same
    update stream — signs, duplicate slots, multi-phys layouts and
    growth included."""
    from arroyo_tpu.parallel import MeshSlotDirectory, ShardedAccumulator

    specs = [
        AggSpec("count", None, "cnt"),
        AggSpec("sum", 0, "total"),
        AggSpec("max", 1, "hi"),
        AggSpec("min", 1, "lo"),
    ]
    rng = np.random.default_rng(3)
    accs = {
        mode: ShardedAccumulator(specs, mesh, capacity_per_shard=128,
                                 rows_per_shard=64, exchange=mode)
        for mode in ("host_fed", "device")
    }
    assert accs["device"]._exchange == "device"
    dirs = {m: MeshSlotDirectory(a.n_shards) for m, a in accs.items()}
    all_slots = {}
    for wave in range(4):
        n = int(rng.integers(1, 700))
        keys = rng.integers(0, 97, n, dtype=np.int64)
        bins = rng.integers(0, 3, n, dtype=np.int64)
        v0 = rng.integers(-50, 50, n, dtype=np.int64)
        v1 = rng.integers(-1000, 1000, n, dtype=np.int64)
        for mode, acc in accs.items():
            slots = dirs[mode].assign(bins, [keys])
            if dirs[mode].required_capacity() > acc.capacity - 1:
                acc.grow(dirs[mode].required_capacity() + 1)
            acc.update(slots, {0: v0, 1: v1})
            all_slots[mode] = slots
        # the two directories assign identically (same hash contract)
        assert (all_slots["host_fed"] == all_slots["device"]).all()
    live = {
        m: np.asarray(sorted({int(s) for _, _, s in d.items()}))
        for m, d in dirs.items()
    }
    out_h = accs["host_fed"].gather(live["host_fed"])
    out_d = accs["device"].gather(live["device"])
    for h, dv in zip(out_h, out_d):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(dv))


def test_device_exchange_salted_and_signed(mesh):
    """Salted (positional-spread) device exchange and signed retraction
    rows: fold-at-gather must match host-fed byte-for-byte."""
    from arroyo_tpu.parallel import (
        ShardedAccumulator,
        SharedMeshSlotDirectory,
    )

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total")]
    outs = {}
    for mode in ("host_fed", "device"):
        rng = np.random.default_rng(11)  # same stream per mode
        acc = ShardedAccumulator(specs, mesh, capacity_per_shard=64,
                                 rows_per_shard=32, salted=True,
                                 exchange=mode)
        d = SharedMeshSlotDirectory(acc.n_shards)
        for wave in range(3):
            n = int(rng.integers(1, 300))
            bins = rng.integers(0, 2, n, dtype=np.int64)
            keys = bins.copy()  # window-only grouping
            slots = d.assign(bins, [keys])
            vals = rng.integers(-20, 20, n, dtype=np.int64)
            signs = rng.choice([-1, 1], n).astype(np.int64)
            acc.update(slots, {0: vals}, signs=signs)
        live = np.asarray(sorted({int(s) for _, _, s in d.items()}))
        outs[mode] = [np.asarray(c) for c in acc.gather(live)]
        # reset + reuse round-trips through the salted device path too
        acc.reset_slots(live)
        z = acc.gather(live)
        assert all(int(np.abs(np.asarray(c)).sum()) == 0 for c in z)
    for h, dv in zip(outs["host_fed"], outs["device"]):
        np.testing.assert_array_equal(h, dv)
