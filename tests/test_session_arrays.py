"""Session bookkeeping as arrays (ISSUE 44): the operator against a plain
sort-and-cut over the same rows, on each accumulator (numpy, jax on XLA's
CPU backend, the virtual 4-device mesh). The plain side below knows nothing
of the operator: per key, sort the rows that were not fully late by time,
cut where two neighbours are the gap or more apart, and a session's row is
(key, count, min, max, sum, first, last + gap). Rows as multisets, limit 0.

The streams keep every row at or past the watermark it arrives under
(fully late rows apart, which both sides drop): a row between a closed
session's end and the watermark would be a different question (the
operator has emitted that session already).
"""

import asyncio
import types
from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu.operators.session_table import SessionTable
from arroyo_tpu.operators.windows import SessionWindowOperator
from arroyo_tpu.schema import StreamSchema
from arroyo_tpu.state.table_config import global_table
from arroyo_tpu.state.tables import GlobalTable
from arroyo_tpu.types import WatermarkKind

GAP = 1_000
BACKENDS = ["numpy", "jax", "mesh4"]
IN = StreamSchema.from_fields([("k", pa.int64()), ("v", pa.int64())])
OUT = StreamSchema.from_fields([
    ("k", pa.int64()), ("cnt", pa.int64()), ("mn", pa.int64()),
    ("mx", pa.int64()), ("sm", pa.int64()),
    ("ws", pa.timestamp("ns")), ("we", pa.timestamp("ns"))])


def operator(backend, key_type=pa.int64()):
    import jax

    config = {
        "aggregates": [{"kind": "count", "name": "cnt"},
                       {"kind": "min", "col": 1, "name": "mn"},
                       {"kind": "max", "col": 1, "name": "mx"},
                       {"kind": "sum", "col": 1, "name": "sm"}],
        "schema": OUT if key_type == pa.int64() else StreamSchema.from_fields(
            [("k", key_type)] + [(f.name, f.type)
                                 for f in list(OUT.schema)[1:7]]),
        "gap_nanos": GAP, "key_cols": [0],
        "window_start_field": "ws", "window_end_field": "we",
    }
    if backend == "mesh4":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        config["mesh_devices"] = 4
    else:
        config["backend"] = backend
    op = SessionWindowOperator(config)
    assert op.acc.backend == ("jax-mesh" if backend == "mesh4" else backend)
    return op


class Harness:
    """One operator, fed batches and watermarks; collects what it emits."""

    def __init__(self, backend, table=None, key_type=pa.int64()):
        self.op = operator(backend, key_type)
        self.key_type = key_type
        self.schema = IN if key_type == pa.int64() else (
            StreamSchema.from_fields([("k", key_type), ("v", pa.int64())]))
        self.wm = None
        self.rows = []
        self.table = table

        async def get_table(name):
            assert name == "sess"
            return self.table

        self.ctx = types.SimpleNamespace(
            in_schemas=[self.schema],
            table_manager=None if table is None else object(),
            table=get_table,
            task_info=types.SimpleNamespace(task_index=0, parallelism=1),
            watermarks=types.SimpleNamespace(current_nanos=lambda: self.wm))
        asyncio.run(self.op.on_start(self.ctx))

    async def collect(self, batch):
        for r in batch.to_pylist():
            self.rows.append((r["k"], r["cnt"], r["mn"], r["mx"], r["sm"],
                              r["ws"].value, r["we"].value))

    def batch(self, keys, ts, vals):
        b = pa.RecordBatch.from_arrays(
            [pa.array(keys, type=self.key_type),
             pa.array(np.asarray(vals, dtype=np.int64)),
             pa.array(np.asarray(ts, dtype=np.int64),
                      type=pa.timestamp("ns"))],
            schema=self.schema.schema)
        asyncio.run(self.op.process_batch(b, self.ctx, self))

    def watermark(self, t):
        self.wm = t if self.wm is None else max(self.wm, t)
        asyncio.run(self.op.handle_watermark(types.SimpleNamespace(
            kind=WatermarkKind.EVENT_TIME, timestamp=self.wm),
            self.ctx, self))

    def checkpoint(self):
        return asyncio.run(self.op.handle_checkpoint(None, self.ctx, self))

    def play(self, steps):
        for step in steps:
            if step[0] == "wm":
                self.watermark(step[1])
            else:
                self.batch(*step[1:])
        return self


def plain(steps, gap=GAP):
    """The sessions of a stream, by sort and cut."""
    wm = None
    kept = {}
    for step in steps:
        if step[0] == "wm":
            wm = step[1] if wm is None else max(wm, step[1])
            continue
        for k, t, v in zip(*step[1:]):
            if wm is None or t + gap > wm:
                kept.setdefault(k, []).append((int(t), int(v)))
    out = []
    for k, rows in kept.items():
        rows.sort()
        run = [rows[0]]
        for r in rows[1:] + [None]:
            if r is None or r[0] - run[-1][0] >= gap:
                vals = [v for _, v in run]
                out.append((k, len(run), min(vals), max(vals), sum(vals),
                            run[0][0], run[-1][0] + gap))
                run = []
            if r is not None:
                run.append(r)
    return out


END = ("wm", 10**15)


def stream(rng, n_batches, rows, n_keys, spread, step, out_of_order):
    """`n_batches` of `rows` rows: time advances `step` a batch, a row's
    time is the batch's base plus up to `spread`; a watermark behind each
    batch at its base (every row to come is at or past it)."""
    steps = []
    for b in range(n_batches):
        base = b * step
        keys = rng.integers(0, n_keys, rows)
        ts = base + rng.integers(0, spread, rows)
        if not out_of_order:
            ts = np.sort(ts)
        steps.append(("rows", keys.tolist(), ts.tolist(),
                      rng.integers(-50, 50, rows).tolist()))
        steps.append(("wm", base + (0 if out_of_order else step)))
    return steps + [END]


def in_order(rng):
    # 3,000 keys a few rows each: most sessions open, idle and close
    return stream(rng, 12, 700, 3_000, 400, 400, False)


def active_keys(rng):
    # NEXmark's shape: rows go to the newest sixty keys, the range moves on
    # by twenty a batch and no key comes back: one session a key
    steps = []
    for b in range(20):
        ts = np.sort(b * 400 + rng.integers(0, 400, 700))
        steps.append(("rows", (b * 20 + rng.integers(0, 60, 700)).tolist(),
                      ts.tolist(), rng.integers(0, 9, 700).tolist()))
        steps.append(("wm", b * 400))
    return steps + [END]


def out_of_order(rng):
    # a batch's rows span three batches' worth of time: a later batch
    # reaches back before an earlier one's last row, never before the
    # watermark; keys few enough that sessions extend backwards
    return stream(rng, 14, 500, 300, 1_300, 420, True)


def bridges(rng):
    # two sessions of a key stand open less than two gaps apart, then one
    # row lands between them within the gap of both; beside noise keys
    steps = [("rows", [1, 1, 2, 2, 3], [0, 1_500, 10, 1_700, 5], [1] * 5),
             ("rows", [1, 2, 9], [800, 950, 20], [7, 8, 9]),
             # 3's second session stands apart; 4 bridges inside one batch
             ("rows", [3, 4, 4, 4], [1_400, 0, 1_900, 950], [1, 2, 3, 4]),
             ("wm", 100)]
    for b in range(4):
        keys = rng.integers(100, 160, 200)
        ts = 200 + b * 900 + rng.integers(0, 2_600, 200)
        steps.append(("rows", keys.tolist(), ts.tolist(), [b] * 200))
        steps.append(("wm", 200 + b * 900))
    return steps + [END]


def exact_gap(rng):
    # exactly the gap apart: two sessions; one nanosecond under: one; in
    # one batch, across batches, and across a watermark that closes none
    return [("rows", [1, 1, 2, 2], [0, GAP, 0, GAP - 1], [1, 2, 3, 4]),
            ("rows", [5, 6], [100, 100], [5, 6]),
            ("wm", 50),
            ("rows", [5, 6, 7], [100 + GAP, 99 + GAP, 60], [7, 8, 9]),
            ("wm", 100 + GAP),       # closes 5's first and 1's first alone
            ("rows", [7], [60 + 2 * GAP], [1]), END]


def hot_key(rng):
    steps = []
    for b in range(6):
        hot = 7 if b < 4 else 8            # the hot key changes
        n = 4_000
        keys = np.where(rng.random(n) < 0.75, hot,
                        rng.integers(100, 400, n))
        ts = np.sort(b * 300 + rng.integers(0, 300, n))
        steps.append(("rows", keys.tolist(), ts.tolist(),
                      rng.integers(0, 9, n).tolist()))
        steps.append(("wm", (b + 1) * 300))
    return steps + [END]


def late_rows(rng):
    steps = stream(rng, 6, 300, 50, 500, 500, False)[:-1]
    # far behind the watermark: each its own would-be session, dropped
    steps.append(("rows", [1, 2, 900], [0, 5, 17], [1, 1, 1]))
    steps.append(("rows", [1, 901, 2], [3_100, 3, 3_050], [2, 2, 2]))
    return steps + [END]


def idle_watermarks(rng):
    steps = []
    for step in stream(rng, 5, 200, 40, 300, 300, False)[:-1]:
        steps.append(step)
        if step[0] == "wm":
            # again, unmoved, and behind: none of them closes anything
            steps += [("wm", step[1]), ("wm", step[1] - 10), ("wm", 0)]
    return [("wm", 0)] + steps + [END]


CASES = {f.__name__: f for f in (in_order, active_keys, out_of_order, bridges,
                                 exact_gap, hot_key, late_rows,
                                 idle_watermarks)}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", list(CASES))
def test_the_operator_equals_sort_and_cut(case, backend):
    steps = CASES[case](np.random.default_rng(sorted(CASES).index(case)))
    h = Harness(backend).play(steps)
    want = plain(steps)
    assert len(want) > 3 and Counter(h.rows) == Counter(want)
    t = h.op._table
    assert t.n_live == 0 and not len(t._codes) and not t._shared
    assert not h.op.sessions
    # every slot went back: the directory holds none that no session uses
    dirs = h.op.dir.dirs if backend == "mesh4" else [h.op.dir]
    assert sum(len(d.free) for d in dirs) == sum(d.next_slot for d in dirs)


def test_which_path_each_case_takes():
    """The in-order stream places no segment one at a time; the bridging
    stream folds sessions; an out-of-order one opens second sessions."""
    from arroyo_tpu import obs
    from arroyo_tpu.obs import timeline

    def booked(case):
        obs.reset()
        Harness("numpy").play(CASES[case](np.random.default_rng(3)))
        t = timeline.totals()
        obs.reset()
        return t

    t = booked("active_keys")
    assert t["sess.place"]["padded"] == 0 and "sess.merge" not in t
    assert t["sess.place"]["n"] > 1_000 and t["sess.open"]["n"] == 440
    assert t["sess.segment"]["n"] == t["sess.segment"]["padded"] == 14_000
    assert t["sess.expire"]["n"] == t["sess.open"]["n"]
    assert t["sess.expire"]["padded"] >= t["sess.expire"]["n"]
    assert t["close.build"]["count"] == t["sess.expire"]["count"]
    t = booked("hot_key")
    assert t["sess.place"]["padded"] == 0
    # a key that comes back the gap or more after its last row, before the
    # watermark has closed that session, holds two: one at a time
    t = booked("in_order")
    assert 0 < t["sess.place"]["padded"] < t["sess.place"]["n"] / 10
    assert "sess.merge" not in t
    t = booked("bridges")
    assert t["sess.merge"]["n"] >= 3 and t["sess.place"]["padded"] >= 6
    t = booked("late_rows")
    assert t["sess.segment"]["padded"] - t["sess.segment"]["n"] == 4


class ScalarTable(SessionTable):
    """Every segment one at a time: the path a key with several open
    sessions takes, here taken by all."""

    def place(self, code, key_cols, lo, hi, alloc, fold):
        self.opened = self.merged = 0
        self.scalar = len(code)
        rows = np.asarray([
            self._place_one(int(code[g]), [c[g:g + 1] for c in key_cols],
                            int(lo[g]), int(hi[g]), alloc, fold)
            for g in range(len(code))], dtype=np.int64)
        self._settle(rows, list(range(len(code))))
        return rows


@pytest.mark.parametrize("case", ["active_keys", "hot_key", "exact_gap"])
def test_the_scalar_path_agrees_where_the_array_path_suffices(
        case, monkeypatch):
    steps = CASES[case](np.random.default_rng(11))
    arrays = Harness("numpy").play(steps)
    monkeypatch.setattr("arroyo_tpu.operators.windows.SessionTable",
                        ScalarTable)
    scalar = Harness("numpy").play(steps)
    assert isinstance(scalar.op._table, ScalarTable)
    assert Counter(scalar.rows) == Counter(arrays.rows) == Counter(
        plain(steps))


@pytest.mark.parametrize("key_type, make", [
    (pa.string(), lambda k: f"user-{k % 97}"),
    (pa.uint64(), lambda k: (1 << 63) + k % 97)])
def test_keys_that_are_no_int64_come_out_as_they_went_in(key_type, make):
    """A string key is found by a hash of its values and held to the
    stored value; an unsigned one rides as its bit pattern."""
    steps = [(s[0], [make(k) for k in s[1]], *s[2:]) if s[0] == "rows"
             else s for s in out_of_order(np.random.default_rng(5))]
    h = Harness("numpy", key_type=key_type).play(steps)
    assert Counter(h.rows) == Counter(plain(steps))
    assert h.op._table.exact == (key_type == pa.uint64())


def test_two_keys_under_one_hash_stay_two_sessions(monkeypatch):
    monkeypatch.setattr(SessionTable, "codes_of",
                        lambda self, cols, n: np.zeros(n, dtype=np.int64))
    steps = [("rows", ["a", "b", "a", "c"], [0, 1, 2, 3], [1, 2, 3, 4]),
             ("rows", ["b", "a", "d"], [400, 500, 600], [5, 6, 7]),
             ("wm", 1_200),      # closes c alone (its end is 1,003)
             ("rows", ["c", "a"], [1_250, 1_300], [8, 9]), END]
    h = Harness("numpy", key_type=pa.string()).play(steps)
    assert Counter(h.rows) == Counter(plain(steps)) and len(h.rows) == 5


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_checkpoint_mid_stream_then_a_restore_gives_the_rest(backend):
    steps = out_of_order(np.random.default_rng(21))
    table = GlobalTable(global_table("sess"))
    first = Harness(backend, table).play(steps[:13])
    open_then = first.op.sessions
    assert first.checkpoint() == sum(len(v) for v in open_then.values())
    assert len(open_then) > 50 and any(
        len(v) > 1 for v in open_then.values())
    # the entries are the parent's: ("sk", key) -> {"s": ..., "v": ...}
    assert {k for k in table.data} == {("sk", k[0]) for k in open_then}
    some = next(k for k, v in open_then.items() if len(v) > 1)
    entry = table.get(("sk", *some))
    assert [s[:2] for s in entry["s"]] == [s[:2] for s in open_then[some]]
    assert len(entry["v"]) == 4 and len(entry["v"][0]) == len(entry["s"])
    second = Harness(backend, table)
    assert {k: [s[:2] for s in v] for k, v in second.op.sessions.items()
            } == {k: [s[:2] for s in v] for k, v in open_then.items()}
    second.wm = first.wm
    second.play(steps[13:])
    assert Counter(first.rows + second.rows) == Counter(plain(steps))


def test_a_table_the_parent_wrote_restores():
    """The `sess` table as the parent's code wrote it: one entry per key,
    `s` = [[start, last, slot], ...] (the slot is the old process's),
    `v` = one list per physical accumulator; and beside it a legacy
    per-subtask snapshot."""
    table = GlobalTable(global_table("sess"))
    table.put(("sk", 7), {"s": [[100, 400, 63]], "v": [[3], [-2], [9], [11]]})
    table.put(("sk", 8), {"s": [[0, 50, 2], [1_300, 1_350, 61]],
                          "v": [[2, 1], [5, 6], [7, 6], [12, 6]]})
    table.put(0, {"subtask": 0, "sessions": [[[9], [[20, 30, 5]]]],
                  "slots": [5], "values": [[4], [1], [2], [6]]})
    h = Harness("numpy", table)
    assert h.op.sessions.keys() == {(7,), (8,), (9,)}
    assert [s[:2] for s in h.op.sessions[(8,)]] == [[0, 50], [1_300, 1_350]]
    # the legacy snapshot is pruned; its key is written anew per key
    assert set(table.data) | set(table.restored) == {("sk", 7), ("sk", 8)}
    h.batch([7, 8], [1_000, 700], [100, -100])   # 8's row bridges its two
    assert h.checkpoint() == 3
    assert set(table.data) == {("sk", 7), ("sk", 8), ("sk", 9)}
    assert table.get(("sk", 8))["s"][0][:2] == [0, 1_350]
    h.watermark(10**9)
    assert Counter(h.rows) == Counter([
        (7, 4, -2, 100, 111, 100, 2_000), (8, 4, -100, 7, -82, 0, 2_350),
        (9, 4, 1, 2, 6, 20, 1_030)])


def test_a_key_that_opens_and_closes_between_barriers_leaves_nothing():
    table = GlobalTable(global_table("sess"))
    h = Harness("numpy", table)
    h.batch([1, 2], [0, 0], [1, 1])
    assert h.checkpoint() == 2
    table.serialize_delta(1)
    # 3 opens and closes before the next barrier; 2 closes too, 1 lives on
    h.batch([3, 1], [100, 900], [1, 1])
    h.watermark(1_200)
    assert sorted(r[0] for r in h.rows) == [2, 3]
    assert h.checkpoint() == 1
    assert table._dirty == {("sk", 1)} and set(table._dead) == {("sk", 2)}
    assert ("sk", 3) not in table.data
    table.serialize_delta(2)
    # a key that died, came back and died again before a barrier: one
    # tombstone, no entry; one that came back and lives: an entry
    h.watermark(2_000)
    h.batch([1, 2], [2_100, 2_100], [1, 1])
    h.watermark(3_200)
    h.batch([2], [3_300], [1])
    assert h.checkpoint() == 1
    assert table._dirty == {("sk", 2)} and set(table._dead) == {("sk", 1)}
    assert not h.op._table.dead_stored
