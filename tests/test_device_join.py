"""Device merge-join probe (ops/device_join.py) vs the arrow host join.

Matches the bin-local join semantics of the reference's instant join
(/root/reference/crates/arroyo-worker/src/arrow/instant_join.rs) — the
device path must be a drop-in for pa.Table.join on the inner case.
"""

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu.ops import device_join


def _pairs_via_arrow(lcols, rcols):
    lt = pa.table(
        {f"k{j}": c for j, c in enumerate(lcols)}
        | {"__li": np.arange(len(lcols[0]), dtype=np.int64)}
    )
    rt = pa.table(
        {f"k{j}": c for j, c in enumerate(rcols)}
        | {"__ri": np.arange(len(rcols[0]), dtype=np.int64)}
    )
    keys = [f"k{j}" for j in range(len(lcols))]
    j = lt.join(rt, keys=keys, right_keys=keys, join_type="inner")
    return set(
        zip(
            np.asarray(j.column("__li").combine_chunks()).tolist(),
            np.asarray(j.column("__ri").combine_chunks()).tolist(),
        )
    )


@pytest.mark.parametrize("n_keys", [1, 2, 3])
def test_probe_matches_arrow(n_keys):
    rng = np.random.RandomState(7 + n_keys)
    # small key domain => plenty of duplicate keys both sides
    lcols = [rng.randint(0, 40, 5000).astype(np.int64)
             for _ in range(n_keys)]
    rcols = [rng.randint(0, 40, 300).astype(np.int64)
             for _ in range(n_keys)]
    li, ri = device_join.probe(lcols, rcols)
    got = set(zip(li.tolist(), ri.tolist()))
    assert len(got) == len(li), "duplicate pairs emitted"
    assert got == _pairs_via_arrow(lcols, rcols)


def test_probe_empty_and_disjoint():
    e = np.empty(0, dtype=np.int64)
    li, ri = device_join.probe([e], [np.array([1], dtype=np.int64)])
    assert len(li) == 0 and len(ri) == 0
    li, ri = device_join.probe(
        [np.array([1, 2, 3], dtype=np.int64)],
        [np.array([7, 8], dtype=np.int64)],
    )
    assert len(li) == 0


def test_probe_negative_and_extreme_values():
    lo, hi = np.iinfo(np.int64).min, np.iinfo(np.int64).max
    lc = [np.array([lo, -1, 0, hi, 42], dtype=np.int64)]
    rc = [np.array([hi, 42, lo, 5], dtype=np.int64)]
    li, ri = device_join.probe(lc, rc)
    got = set(zip(li.tolist(), ri.tolist()))
    assert got == {(0, 2), (3, 0), (4, 1)}


def test_instant_join_device_path_matches_host(monkeypatch):
    """Run the same instant-join bin through the device probe and the
    arrow join and compare outputs row-for-row."""
    from arroyo_tpu.config import config
    from arroyo_tpu.operators.joins import InstantJoinOperator
    from arroyo_tpu.schema import StreamSchema

    rng = np.random.RandomState(3)
    n_l, n_r = 4000, 500
    ts = 1_000_000
    out_schema = StreamSchema(
        pa.schema(
            [
                ("__key0", pa.int64()),
                ("a", pa.int64()),
                ("b", pa.int64()),
                ("_timestamp", pa.timestamp("ns")),
            ]
        ),
        (0,),
    )
    def mk(n, payload):
        return pa.table(
            {
                "__key0": rng.randint(0, 64, n).astype(np.int64),
                payload: rng.randint(0, 1000, n).astype(np.int64),
                "_timestamp": pa.array(
                    np.full(n, ts, dtype=np.int64)
                ).cast(pa.timestamp("ns")),
            }
        )

    left, right = mk(n_l, "a"), mk(n_r, "b")
    cfg = {
        "n_keys": 1,
        "join_type": "inner",
        "schema": out_schema,
        "left_fields": ["__key0", "a"],
        "right_fields": ["__key0", "b"],
    }
    op = InstantJoinOperator(cfg)

    monkeypatch.setattr(config().tpu, "enabled", True)
    # the CPU test host is no accelerator; waive the requirement so the
    # probe engages on jax-CPU
    monkeypatch.setattr(config().tpu, "require_accelerator", False)
    monkeypatch.setattr(config().tpu, "device_join", True)
    monkeypatch.setattr(config().tpu, "device_join_min_rows", 0)
    dev = op._join_tables(left, right, ts_value=ts)
    monkeypatch.setattr(config().tpu, "device_join", False)
    host = op._join_tables(left, right, ts_value=ts)

    assert dev is not None and host is not None
    def norm(batch):
        rows = sorted(
            zip(
                *(
                    np.asarray(batch.column(i).cast(pa.int64())).tolist()
                    for i in range(batch.num_columns)
                )
            )
        )
        return rows

    assert norm(dev) == norm(host)
    assert dev.num_rows == host.num_rows


def _pairs_via_arrow_tables(lt, rt, keys):
    lt2 = lt.append_column("__li", pa.array(
        np.arange(lt.num_rows, dtype=np.int64)))
    rt2 = rt.append_column("__ri", pa.array(
        np.arange(rt.num_rows, dtype=np.int64)))
    j = lt2.join(rt2, keys=keys, right_keys=keys, join_type="inner")
    return set(zip(
        np.asarray(j.column("__li").combine_chunks()).tolist(),
        np.asarray(j.column("__ri").combine_chunks()).tolist(),
    ))


def _probe_pairs(lt, rt, keys):
    prep = device_join.prepare_join_keys(lt, rt, keys)
    assert prep is not None
    lcols, rcols, lsel, rsel = prep
    li, ri = device_join.probe(lcols, rcols)
    if lsel is not None:
        li = lsel[li]
    if rsel is not None:
        ri = rsel[ri]
    return set(zip(li.tolist(), ri.tolist()))


def test_prepare_join_keys_strings():
    """String keys ride the probe via a joint dictionary (exact codes,
    not hashes)."""
    rng = np.random.RandomState(3)
    words = np.array([f"w{i}" for i in range(50)])
    lt = pa.table({"k": words[rng.randint(0, 50, 4000)]})
    rt = pa.table({"k": words[rng.randint(0, 50, 250)]})
    assert _probe_pairs(lt, rt, ["k"]) == _pairs_via_arrow_tables(
        lt, rt, ["k"]
    )


def test_prepare_join_keys_nullable():
    """Null keys never match (SQL equi-join): rows with nulls are
    pre-filtered and pair indices map back to original rows."""
    lt = pa.table({"k": pa.array([1, None, 2, 3, None, 2], type=pa.int64())})
    rt = pa.table({"k": pa.array([None, 2, 1, 2], type=pa.int64())})
    assert _probe_pairs(lt, rt, ["k"]) == _pairs_via_arrow_tables(
        lt, rt, ["k"]
    )


def test_prepare_join_keys_string_nullable_multi():
    """Mixed string + int keys with nulls on both sides."""
    rng = np.random.RandomState(9)
    words = np.array([f"s{i}" for i in range(20)])
    lk = words[rng.randint(0, 20, 1500)].astype(object)
    rk = words[rng.randint(0, 20, 400)].astype(object)
    lk[::17] = None
    rk[::11] = None
    lt = pa.table({
        "a": pa.array(lk, type=pa.string()),
        "b": pa.array(rng.randint(0, 5, 1500), type=pa.int64()),
    })
    rt = pa.table({
        "a": pa.array(rk, type=pa.string()),
        "b": pa.array(rng.randint(0, 5, 400), type=pa.int64()),
    })
    assert _probe_pairs(lt, rt, ["a", "b"]) == _pairs_via_arrow_tables(
        lt, rt, ["a", "b"]
    )


# --- the two programs against the parent's arithmetic (ISSUE 37) ----------
# The oracle is the parent's arithmetic in numpy: np.searchsorted over the
# same splitmix64 hashes, the same expansion. Whatever the build bucket and
# whichever way phase 2 fills, the programs must hand back its arrays, not
# just its pairs.

_M64 = (1 << 64) - 1
_SENTINEL = np.uint64(_M64)


def _np_hash_rows(mat):
    from arroyo_tpu.types import _splitmix64

    h = np.zeros(mat.shape[0], dtype=np.uint64)
    for j in range(mat.shape[1]):
        h = _splitmix64(h ^ mat[:, j].astype(np.uint64))
    return h


def _key_hashing_to(h: int) -> int:
    """The one-word int64 key whose row hash is `h`: splitmix64's
    finaliser run backwards."""
    z = h
    z ^= (z >> 31) ^ (z >> 62)
    z = z * pow(0x94D049BB133111EB, -1, 1 << 64) & _M64
    z ^= (z >> 27) ^ (z >> 54)
    z = z * pow(0xBF58476D1CE4E5B9, -1, 1 << 64) & _M64
    z ^= (z >> 30) ^ (z >> 60)
    z = (z - 0x9E3779B97F4A7C15) & _M64
    return z - (1 << 64) if z >= 1 << 63 else z


def _buckets(n_l, n_r):
    return device_join._bucket(n_l), device_join._build_bucket(n_r)


def _oracle(lcols, rcols):
    n_l, n_r = len(lcols[0]), len(rcols[0])
    lb, rb = _buckets(n_l, n_r)
    hl = _np_hash_rows(device_join._pad_matrix(lcols, lb))
    hr = _np_hash_rows(device_join._pad_matrix(rcols, rb))
    hr[n_r:] = _SENTINEL
    order = np.argsort(hr, kind="stable")
    hrs = hr[order]
    lo = np.searchsorted(hrs, hl, side="left")
    hi = np.searchsorted(hrs, hl, side="right")
    offs = np.cumsum(np.where(np.arange(lb) < n_l, hi - lo, 0))
    total = int(offs[-1])
    out = {"order": order, "lo": lo, "offs": offs, "total": total}
    if total == 0:
        e = np.empty(0, dtype=np.int64)
        return out | {"pairs": (e, e)}
    pos = np.arange(device_join._bucket(total))
    li = np.clip(np.searchsorted(offs, pos, side="right"), 0, lb - 1)
    start = np.where(li > 0, offs[li - 1], 0)
    ri = order[np.clip(lo[li] + (pos - start), 0, rb - 1)]
    valid = pos < total
    mask = valid & (li < n_l) & (ri < n_r)
    pl, pr = li[mask], ri[mask]
    keep = np.ones(len(pl), dtype=bool)
    for lc, rc in zip(lcols, rcols):
        keep &= lc[pl] == rc[pr]
    return out | {"li": li, "ri": ri, "valid": valid,
                  "pairs": (pl[keep], pr[keep])}


def _ints(rng, dom, n, words):
    return [rng.randint(0, dom, n).astype(np.int64) for _ in range(words)]


def _case_one_row_build(n_l, words=3):
    """The cells' shape: a window's rows against its one max row."""
    rng = np.random.RandomState(n_l)
    lcols = _ints(rng, 1000, n_l, words)
    hit = rng.randint(0, n_l, 4)
    rcols = [c[hit[:1]].copy() for c in lcols]
    for c, r in zip(lcols, rcols):
        c[hit] = r[0]
    return lcols, rcols


def _case_duplicates(n_l, n_r, words, dom=12):
    rng = np.random.RandomState(n_l + n_r + words)
    return _ints(rng, dom, n_l, words), _ints(rng, dom, n_r, words)


def _case_sentinel():
    k = _key_hashing_to(_M64)
    lcols = [np.array([5, k, 7, k, 9] * 20, dtype=np.int64)]
    return lcols, [np.array([7, k, 11], dtype=np.int64)]


def _case_every_row_matches(n_l, copies):
    return ([np.full(n_l, 42, dtype=np.int64)],
            [np.full(copies, 42, dtype=np.int64)])


_CASES = {
    "one_row_build_5000x3": lambda: _case_one_row_build(5000),
    "one_row_build_70000x3": lambda: _case_one_row_build(70000),
    "one_row_build_5000x1": lambda: _case_one_row_build(5000, words=1),
    "duplicates_1_word": lambda: _case_duplicates(5000, 300, 1, dom=40),
    "duplicates_2_words": lambda: _case_duplicates(5000, 300, 2),
    "duplicates_3_words": lambda: _case_duplicates(3000, 700, 3, dom=5),
    "two_sided_40000x20000": lambda: _case_duplicates(40000, 20000, 2,
                                                      dom=300),
    "probe_rows_eq_bucket": lambda: _case_duplicates(1024, 90, 1, dom=64),
    "probe_rows_bucket_plus_1": lambda: _case_duplicates(1025, 90, 1,
                                                         dom=64),
    "build_rows_eq_small": lambda: _case_duplicates(2048, 8, 1, dom=16),
    "build_rows_small_plus_1": lambda: _case_duplicates(2048, 9, 1, dom=16),
    "build_rows_eq_bucket": lambda: _case_duplicates(2048, 1024, 1,
                                                     dom=900),
    "build_rows_bucket_plus_1": lambda: _case_duplicates(2049, 1025, 2,
                                                         dom=40),
    "probe_hash_eq_sentinel": _case_sentinel,
    "zero_matches": lambda: ([np.arange(3000, dtype=np.int64)],
                             [np.arange(5000, 5600, dtype=np.int64)]),
    "every_probe_row_matches": lambda: _case_every_row_matches(3000, 1),
    # 1,024 x 2 pairs: the output bucket outgrows the probe side's
    "every_probe_row_matches_twice": lambda: _case_every_row_matches(
        1024, 2),
}


@pytest.fixture
def fill(request, monkeypatch):
    """Hold phase 2 to one way of filling whatever the bucket, with the
    jitted programs rebuilt for the test: a traced program has the rule's
    answer baked in. "rule" leaves both as they are."""
    if request.param != "rule":
        monkeypatch.setattr(device_join, "_fill", lambda size: request.param)
        monkeypatch.setattr(device_join, "_fns", None)
    return request.param


@pytest.mark.parametrize("fill", ["rule", "search", "hist"], indirect=True)
@pytest.mark.parametrize("case", sorted(_CASES))
def test_programs_hand_back_the_parents_arrays(case, fill):
    lcols, rcols = _CASES[case]()
    want = _oracle(lcols, rcols)
    n_l, n_r = len(lcols[0]), len(rcols[0])
    lb, rb = _buckets(n_l, n_r)
    phase1, phase2_at = device_join._build_fns()
    order, lo, offs = phase1(
        device_join._pad_matrix(lcols, lb),
        device_join._pad_matrix(rcols, rb),
        np.int64(n_l), np.int64(n_r), rung=rb, rows=n_r)
    for name, got in (("order", order), ("lo", lo), ("offs", offs)):
        assert np.array_equal(np.asarray(got), want[name]), name
    total = want["total"]
    if total:
        li, ri, valid = phase2_at(device_join._bucket(total), order, lo,
                                  offs, total)
        for name, got in (("li", li), ("ri", ri), ("valid", valid)):
            assert np.array_equal(np.asarray(got), want[name]), name
    # probe(): the parent's pairs in the parent's order (probe-side order)
    pl, pr = device_join.probe(lcols, rcols)
    assert np.array_equal(pl, want["pairs"][0])
    assert np.array_equal(pr, want["pairs"][1])
    assert np.all(np.diff(pl) >= 0)
    if case == "zero_matches":
        assert total == 0 and len(pl) == 0
    if case.startswith("every_probe_row_matches"):
        assert len(pl) == total == n_l * n_r
    if case == "probe_hash_eq_sentinel":
        # 40 probe rows collide with the 5 padded build rows: spurious
        # candidates that the bounds and the key check drop, none missing
        assert total == 20 + 40 * 6 and len(pl) == 60


def _ledger_notes(name):
    from arroyo_tpu.obs import timeline

    return [e for e in timeline.snapshot() if e["phase"] == name]


@pytest.mark.parametrize("make, n_r, build_bucket, fill, out_bucket", [
    # q7's shape: a few pairs in the floor bucket
    (lambda: _case_one_row_build(5000), 1, 8, "search", 1024),
    # q5's: every row of the window a candidate of its max row
    (lambda: _case_every_row_matches(3000, 1), 1, 8, "hist", 4096),
    (lambda: _case_duplicates(5000, 9, 1, dom=9), 9, 1024, "hist", 8192),
])
def test_the_ledger_says_what_the_probe_chose(make, n_r, build_bucket, fill,
                                              out_bucket):
    from arroyo_tpu.obs import timeline

    timeline.clear()
    lcols, rcols = make()
    assert len(rcols[0]) == n_r
    pl, _ = device_join.probe(lcols, rcols)
    total = _oracle(lcols, rcols)["total"]
    rank, = _ledger_notes("join.probe.rank")
    got, = _ledger_notes("join.probe.fill")
    assert (rank["n"], rank["dur"]) == (len(lcols[0]), 0)
    assert (got["key"], got["n"], got["dur"]) == (fill, total, 0)
    t = timeline.totals()
    assert t["join.probe.rank"]["padded"] == build_bucket
    assert t["join.probe.fill"]["padded"] == out_bucket
    # no match, no expansion: the rank is booked, the fill is not
    timeline.clear()
    device_join.probe([np.arange(5000, dtype=np.int64)],
                      [np.array([-1], dtype=np.int64)])
    assert len(_ledger_notes("join.probe.rank")) == 1
    assert not _ledger_notes("join.probe.fill")


def test_a_growing_build_side_compiles_phase1_twice(monkeypatch):
    """The small build bucket is one bucket, not a ladder of them: each is a
    compile on the engine's loop (`updating_join`'s other side grows through
    every size)."""
    assert sorted({device_join._build_bucket(n) for n in range(1, 1025)}) \
        == [8, 1024]
    assert device_join._build_bucket(1025) == 2048
    monkeypatch.setattr(device_join, "_fns", None)
    phase1, _ = device_join._build_fns()
    probe_side = [np.arange(3000, dtype=np.int64)]
    for n_r in (1, 2, 8, 9, 100, 1000, 1024):
        device_join.probe(probe_side, [np.arange(n_r, dtype=np.int64)])
    assert len(phase1.seen) == 2


def test_the_fill_rule_reads_the_output_bucket_alone():
    # the cells (module docstring): q7 fills the floor bucket with a pair or
    # two, q5 fills 65,536 positions from its window's ~60,000 rows
    assert device_join._fill(1024) == "search"
    assert device_join._fill(2048) == device_join._fill(65536) == "hist"
