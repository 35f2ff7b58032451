"""Accumulator kernels: jax (device) vs numpy (host) vs pandas golden."""

import numpy as np
import pandas as pd
import pytest

from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator
from arroyo_tpu.ops.directory import SlotDirectory

SPECS = [
    AggSpec("count", None, "cnt"),
    AggSpec("sum", 0, "total"),
    AggSpec("min", 1, "lo", is_float=True),
    AggSpec("max", 1, "hi", is_float=True),
    AggSpec("avg", 1, "mean", is_float=True),
]


def golden(bins, keys, ints, floats):
    df = pd.DataFrame({"b": bins, "k": keys, "i": ints, "f": floats})
    g = df.groupby(["b", "k"])
    return pd.DataFrame(
        {
            "cnt": g.size(),
            "total": g["i"].sum(),
            "lo": g["f"].min(),
            "hi": g["f"].max(),
            "mean": g["f"].mean(),
        }
    )


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_accumulator_matches_pandas(backend):
    rng = np.random.default_rng(42)
    n = 5000
    bins = rng.integers(0, 4, n)
    keys = rng.integers(0, 17, n)
    ints = rng.integers(-100, 100, n)
    floats = rng.random(n) * 100
    acc = make_accumulator(SPECS, capacity=64, backend=backend)
    d = SlotDirectory()
    # feed in several batches to exercise slot reuse and growth
    for lo in range(0, n, 1234):
        hi = min(lo + 1234, n)
        slots = d.assign(bins[lo:hi], [keys[lo:hi]])
        if d.required_capacity() > acc.capacity - 1:
            acc.grow(d.required_capacity() + 1)
        acc.update(slots, {0: ints[lo:hi], 1: floats[lo:hi]})
    want = golden(bins, keys, ints, floats)
    for b in d.live_bins():
        got_keys, slots = d.take_bin(b)
        cols = acc.finalize(acc.gather(slots))
        for key, cnt, total, lo_, hi_, mean in zip(
            got_keys, cols[0], cols[1], cols[2], cols[3], cols[4]
        ):
            row = want.loc[(b, key[0])]
            assert cnt == row["cnt"]
            assert total == row["total"]  # exact int arithmetic
            assert lo_ == pytest.approx(row["lo"])
            assert hi_ == pytest.approx(row["hi"])
            assert mean == pytest.approx(row["mean"])


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_slot_reuse_after_reset(backend):
    acc = make_accumulator([AggSpec("sum", 0, "s")], capacity=8, backend=backend)
    d = SlotDirectory()
    slots = d.assign(np.array([1, 1]), [np.array([7, 7])])
    acc.update(slots, {0: np.array([10, 20])})
    _, taken = d.take_bin(1)
    assert acc.finalize(acc.gather(taken))[0][0] == 30
    acc.reset_slots(taken)
    # the freed slot must start clean for a new group
    slots2 = d.assign(np.array([2]), [np.array([9])])
    assert slots2[0] == taken[0]  # reused
    acc.update(slots2, {0: np.array([5])})
    assert acc.finalize(acc.gather(slots2))[0][0] == 5


def test_jax_numpy_bit_identical():
    rng = np.random.default_rng(0)
    n = 2000
    bins = rng.integers(0, 3, n)
    keys = rng.integers(0, 11, n)
    ints = rng.integers(-(2**40), 2**40, n)  # exercise >32-bit sums
    accs = {}
    for backend in ("numpy", "jax"):
        acc = make_accumulator(
            [AggSpec("sum", 0, "s"), AggSpec("count", None, "c")],
            capacity=64,
            backend=backend,
        )
        d = SlotDirectory()
        slots = d.assign(bins, [keys])
        if d.required_capacity() > acc.capacity - 1:
            acc.grow(d.required_capacity() + 1)
        acc.update(slots, {0: ints})
        out = {}
        for b in d.live_bins():
            ks, sl = d.take_bin(b)
            cols = acc.finalize(acc.gather(sl))
            for k, s, c in zip(ks, cols[0], cols[1]):
                out[(b, k[0])] = (int(s), int(c))
        accs[backend] = out
    assert accs["numpy"] == accs["jax"]


def test_directory_growth_and_scratch():
    acc = make_accumulator([AggSpec("count", None, "c")], capacity=4,
                           backend="numpy")
    d = SlotDirectory()
    slots = d.assign(np.zeros(100, dtype=np.int64),
                     [np.arange(100, dtype=np.int64)])
    acc.grow(d.required_capacity() + 1)
    acc.update(slots, {})
    ks, sl = d.take_bin(0)
    assert len(ks) == 100
    assert all(c == 1 for c in acc.finalize(acc.gather(sl))[0])


def test_count_distinct_excludes_nulls():
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    acc = make_accumulator(
        [AggSpec("count_distinct", 0, "d")], backend="numpy"
    )
    slots = np.zeros(5, dtype=np.int64)
    vals = np.array(["a", None, "b", None, "a"], dtype=object)
    acc.update(slots, {0: vals})
    acc.gather(np.array([0]))
    assert acc.finalize([])[0].tolist() == [2]  # NULLs excluded


def test_count_distinct_raw_precision_beyond_2_53():
    """A BIGINT column shared with a float-cast spec must reach the
    multiset uncast: 2^53 and 2^53+1 are equal as float64."""
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    acc = make_accumulator(
        [AggSpec("avg", 0, "a", is_float=True),
         AggSpec("count_distinct", 0, "d")],
        backend="numpy",
    )
    big = np.array([2**53, 2**53 + 1], dtype=np.int64)
    acc.update(np.zeros(2, dtype=np.int64),
               {0: big.astype(np.float64), ("raw", 0): big})
    acc.gather(np.array([0]))
    out = acc.finalize(acc.gather(np.array([0])))
    assert out[1].tolist() == [2], "distinct collapsed via float64 keys"


def test_count_distinct_multiset_snapshot_roundtrip_ragged():
    """Slots with different numbers of distinct values snapshot as ragged
    object columns and must restore exactly."""
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    acc = make_accumulator(
        [AggSpec("count_distinct", 0, "d")], backend="numpy"
    )
    slots = np.array([0, 0, 1, 1, 1], dtype=np.int64)
    vals = np.array(["x", "y", "p", "q", "r"], dtype=object)
    acc.update(slots, {0: vals})
    snap = acc.snapshot(np.array([0, 1]))
    acc2 = make_accumulator(
        [AggSpec("count_distinct", 0, "d")], backend="numpy"
    )
    acc2.restore(np.array([0, 1]), snap)
    acc2.gather(np.array([0, 1]))
    assert acc2.finalize([])[0].tolist() == [2, 3]


def test_32bit_device_accumulators_exact():
    """The opt-in 32-bit device mode (TPU v5e has no native int64)
    produces identical results for count/min/max/avg at 32-bit-safe
    magnitudes."""
    import numpy as np

    from arroyo_tpu.config import config
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    specs = [
        AggSpec("count", None, "c"),
        AggSpec("min", 0, "mn"),
        AggSpec("max", 0, "mx"),
        AggSpec("avg", 0, "a", is_float=True),
    ]
    config().tpu.use_32bit_accumulators = True
    try:
        acc = make_accumulator(specs, capacity=64, backend="jax")
        assert acc.use32
        vals = np.array([5, -3, 1000000, 7, -3], dtype=np.int64)
        slots = np.array([1, 1, 2, 2, 1], dtype=np.int64)
        acc.update(slots, {0: vals.astype(np.float64)})
        out = acc.finalize(acc.gather(np.array([1, 2])))
        assert list(out[0]) == [3, 2]              # counts
        assert list(out[1]) == [-3, 7]             # mins
        assert list(out[2]) == [5, 1000000]        # maxes
        assert np.allclose(out[3], [(5 - 3 - 3) / 3, 1000007 / 2])
    finally:
        config().tpu.use_32bit_accumulators = False


def test_an_undrained_gather_compiles_nothing_per_length_and_build_slices_on_the_host(
        caplog):
    """ISSUE 39: `gather(materialize=False)` hands back its bucket's padded
    device arrays, so three barriers with three different dirty counts in
    one bucket compile nothing after the first (an eager `o[:n]` on the
    device, the parent's, compiles a `dynamic_slice` per new length: the
    control); the delta's `build()`, on the flush path, slices behind the
    copy and returns the parent's rows."""
    import logging

    import jax
    import pyarrow as pa

    from arroyo_tpu.operators.windows import TumblingWindowOperator

    specs = [AggSpec("count", None, "cnt"), AggSpec("sum", 0, "total")]
    acc = make_accumulator(specs, capacity=4096, backend="jax")
    ints = np.arange(2000, dtype=np.int64) * 3
    acc.update(np.arange(2000), {0: ints})
    acc.gather(np.arange(700), materialize=False)  # the bucket's first

    def compiles():
        return [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("Compiling")]

    op = object.__new__(TumblingWindowOperator)
    op.acc = acc
    op.codec = type("Codec", (), {"delta_arrays": staticmethod(
        lambda key_cols: [pa.array(c) for c in key_cols])})()
    with caplog.at_level(logging.WARNING), jax.log_compiles():
        for n in (701, 800, 1000):
            slots = np.arange(n) + 5
            outs = acc.gather(slots, materialize=False)
            assert [o.shape for o in outs] == [(1024,), (1024,)]
            op._dirty_chunks = [(slots, np.full(n, 7, dtype=np.int64),
                                 [slots * 11])]
            op._dirty_rows = op._dirty_base = 0
            batch = op._build_delta_batch(lambda bins: bins * 1_000)()
            assert batch.schema.names == ["__ts", "__bin", "__k0", "__v0",
                                          "__v1"]
            assert batch.num_rows == n
            assert batch.column(2).to_pylist() == (slots * 11).tolist()
            assert batch.column(3).to_pylist() == [1] * n
            assert batch.column(4).to_pylist() == ints[5:n + 5].tolist()
        assert compiles() == []
        outs[0][:999]   # the control: the parent's eager slice
        assert any("dynamic_slice" in m for m in compiles())
