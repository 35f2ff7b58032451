"""StateServe — the queryable-state serving tier (ISSUE 12).

Fast-tier coverage of the read path's three load-bearing contracts:

  * epoch consistency — a read issued mid-checkpoint returns ONLY
    last-published-epoch values (unit: the view's stage/seal/fold
    layers; model: the reader actor explores clean with reads enabled);
  * routing exactness — the gateway routes every key to the subtask
    that actually owns it, for shard counts 2/4/8 and across a live
    1 -> 4 -> 2 controller-driven rescale (cross-checked against
    `MeshSlotDirectory.owners_for` and the job's assignment table);
  * degradation — a worker SIGKILL mid-read-load yields retriable
    errors or consistent values, never a torn one; a torn-down
    incarnation's route fences (`stale_route`) instead of serving.

Plus the serving-tier surfaces: REST point/bulk/table routes, the
read-through cache's epoch invalidation, per-tenant QPS admission with
the doctor's noisy-neighbor penalty, and GC on job stop.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from arroyo_tpu.config import config, update
from arroyo_tpu.controller.controller import ControllerServer
from arroyo_tpu.controller.scheduler import EmbeddedScheduler
from arroyo_tpu.controller.state_machine import JobState
from arroyo_tpu.serve import ServeView, owner_subtask
from arroyo_tpu.serve.gateway import _Bucket, _Cache
from arroyo_tpu.serve.store import canon_value
from arroyo_tpu.types import (
    hash_arrays,
    hash_column,
    server_for_hash_array,
)


def _view(**kw):
    base = dict(job_id="j", table="t", node_id=1, task_index=0,
                parallelism=1, key_names=["k"], key_kinds=("i",),
                value_names=["cnt"], kind="window", live_mode=False)
    base.update(kw)
    return ServeView(**base)


# -- epoch consistency (the acceptance unit test) ----------------------------


def test_read_mid_checkpoint_returns_last_published_only():
    """A read issued mid-checkpoint (state staged, sealed, or even
    sealed-at-a-later-epoch) must return only values of the last
    PUBLISHED epoch the gateway resolved."""
    v = _view()
    v.stage((1,), {"cnt": 10})
    # staged but not yet captured: invisible at every published level
    assert v.read((1,), 0) == (False, None)
    v.seal(1)  # captured at epoch 1's barrier
    # epoch 1 not published yet -> still invisible
    assert v.read((1,), 0) == (False, None)
    # epoch 1 published -> visible
    assert v.read((1,), 1) == (True, {"cnt": 10})
    # next interval: a newer value captured at epoch 2, published at 1:
    # the read must keep answering epoch 1's value (no torn/early read)
    v.stage((1,), {"cnt": 99})
    assert v.read((1,), 1) == (True, {"cnt": 10})
    v.seal(2)
    assert v.read((1,), 1) == (True, {"cnt": 10})
    assert v.read((1,), 2) == (True, {"cnt": 99})


def test_view_tombstones_and_pending_cap():
    v = _view()
    v.stage((7,), {"cnt": 1})
    v.seal(1)
    v.stage_tomb((7,))
    v.seal(2)
    assert v.read((7,), 1) == (True, {"cnt": 1})
    assert v.read((7,), 2) == (False, None)
    # pending cap: publication stalls for > max_pending_epochs — the
    # oldest epochs fold forward instead of growing without bound
    with update(serve={"max_pending_epochs": 4}):
        v2 = _view()
        for e in range(1, 10):
            v2.stage((e,), {"cnt": e})
            v2.seal(e)
        assert len(v2.pending) <= 4


def test_view_live_mode_serves_latest():
    """Jobs without durable state have no epochs: views serve live."""
    v = _view(live_mode=True)
    v.stage((1,), {"cnt": 5})
    assert v.read((1,), None) == (True, {"cnt": 5})
    v.stage_tomb((1,))
    assert v.read((1,), None) == (False, None)


# -- the oracle: the dict-based view as a plain model (ISSUE 25) -------------


class _ModelView:
    """The view as it was before ISSUE 25, kept plain: one dict per
    layer, one Python key tuple and value dict per emitted row. The
    columnar view must answer exactly as this does."""

    _TOMB = object()

    def __init__(self, key_names, key_kinds, value_names, live_mode,
                 max_pending):
        self.key_names, self.key_kinds = key_names, key_kinds
        self.value_names, self.live_mode = value_names, live_mode
        self.max_pending = max_pending
        self.served, self.pending, self._stage = {}, {}, {}

    def stage(self, key, value):
        (self.served if self.live_mode else self._stage)[key] = value

    def stage_tomb(self, key):
        if self.live_mode:
            self.served.pop(key, None)
        else:
            self._stage[key] = self._TOMB

    def stage_batch(self, batch, partial=False):
        from arroyo_tpu.serve.store import _plain

        staged = []
        for row in batch.to_pylist():
            key = tuple(canon_value(row[n], k)
                        for n, k in zip(self.key_names, self.key_kinds))
            value = {n: _plain(row[n]) for n in self.value_names
                     if n in row}
            if partial:
                value["partial"] = True
            self.stage(key, value)
            staged.append(key)
        return staged

    def _fold_one(self, epoch):
        for k, v in self.pending.pop(epoch).items():
            if v is self._TOMB:
                self.served.pop(k, None)
            else:
                self.served[k] = v

    def seal(self, epoch):
        if not self._stage:
            return
        self.pending.setdefault(epoch, {}).update(self._stage)
        self._stage = {}
        while len(self.pending) > self.max_pending:
            self._fold_one(min(self.pending))

    def read(self, key, epoch):
        if epoch is not None and not self.live_mode:
            for e in sorted(self.pending):
                if e > epoch:
                    break
                self._fold_one(e)
        if key in self.served:
            return True, self.served[key]
        return False, None


def _oracle_columns(case, rng, n):
    """(key_names, key_kinds, {name: arrow array}) of `n` rows for one
    case: few distinct keys, so rows collide within a batch, across
    batches and across epochs."""
    import pyarrow as pa

    small = rng.integers(0, 12, n)
    if case == "u":
        return ["k"], ("u",), {"k": pa.array(
            (small.astype(np.uint64) + np.uint64(2**63)), pa.uint64())}
    if case == "f":
        return ["k"], ("f",), {"k": pa.array(small / 4.0 - 1.0)}
    if case == "s":
        return ["k"], ("s",), {"k": pa.array([f"key-{x}" for x in small])}
    if case == "two":
        return ["k", "k2"], ("i", "s"), {
            "k": pa.array((small % 4).astype(np.int32)),
            "k2": pa.array([f"b{x // 4}" for x in small])}
    if case == "ts":
        return ["k"], ("i",), {"k": pa.array(
            small.astype(np.int64) * 1_000_000 + 1_700_000_000_000_000,
            pa.timestamp("us"))}
    if case == "struct":  # the window-only grouping's key
        st = pa.array(small.astype(np.int64) * 2_000_000,
                      pa.timestamp("us"))
        en = pa.array((small.astype(np.int64) + 5) * 2_000_000,
                      pa.timestamp("us"))
        return ["k"], ("o",), {"k": pa.StructArray.from_arrays(
            [st, en], ["start", "end"])}
    if case == "list":  # no per-column form: the row-wise entry
        return ["k"], ("o",), {"k": pa.array(
            [[int(x), 7] for x in small], pa.list_(pa.int64()))}
    return ["k"], ("i",), {"k": pa.array(small.astype(np.int64) - 3)}


_ORACLE_CASES = ["i", "u", "f", "s", "two", "ts", "window", "one_row",
                 "empty", "struct", "list"]


@pytest.mark.parametrize("live_mode", [False, True])
@pytest.mark.parametrize("case", _ORACLE_CASES)
def test_columnar_view_answers_as_the_dict_model(case, live_mode):
    """Random seeded sequences of stage_batch (finals and partials),
    stage, stage_tomb, seal (now and then twice under one epoch),
    reads at published epochs (which fold), pending-cap folding, the
    served compaction and live mode: the columnar view and the plain
    dict model answer identically, key for key, after every step."""
    import pyarrow as pa

    from arroyo_tpu.serve.store import stage_batch

    rng = np.random.default_rng(
        25_000 + 2 * _ORACLE_CASES.index(case) + live_mode)
    rows_of = {"one_row": lambda: 1, "empty": lambda: int(rng.integers(0, 2))}
    rows_of = rows_of.get(case, lambda: int(rng.integers(1, 30)))

    def batch():
        n = rows_of()
        key_names, key_kinds, cols = _oracle_columns(case, rng, n)
        start = pa.array(rng.integers(0, 50, n) * 2_000_000,
                         pa.timestamp("us"))
        cols["window"] = pa.StructArray.from_arrays(
            [start, start], ["start", "end"])
        cols["cnt"] = pa.array(rng.integers(0, 1000, n))
        cols["_timestamp"] = start  # not a value column
        return key_names, key_kinds, pa.RecordBatch.from_pydict(cols)

    key_names, key_kinds, _ = batch()
    value_names = ["window", "cnt"] if case == "window" else ["cnt"]
    with update(serve={"max_pending_epochs": 3}):
        view = _view(key_names=key_names, key_kinds=key_kinds,
                     value_names=value_names, live_mode=live_mode)
    model = _ModelView(key_names, key_kinds, value_names, live_mode, 3)
    universe, epoch, published = set(), 0, 0

    def check():
        at = None if live_mode else published
        for k in sorted(universe, key=repr):
            assert view.read(k, at) == model.read(k, at), (k, at)
        assert view.stats()["keys"] == len(model.served)

    for _step in range(160):
        op = rng.choice(["final", "final", "partial", "stage", "tomb",
                         "seal", "publish"])
        if op in ("final", "partial"):
            _, _, b = batch()
            got = stage_batch(view, b, partial=(op == "partial"))
            want = model.stage_batch(b, partial=(op == "partial"))
            universe.update(want)
            if op == "partial":
                assert got == want
        elif op in ("stage", "tomb") and universe:
            k = sorted(universe, key=repr)[
                int(rng.integers(0, len(universe)))]
            if op == "stage":
                v = {"cnt": int(rng.integers(0, 1000))}
                view.stage(k, v)
                model.stage(k, dict(v))
            else:
                view.stage_tomb(k)
                model.stage_tomb(k)
        elif op == "seal" and not live_mode:
            epoch += int(rng.integers(0, 4) > 0)  # 1 in 4: the same epoch
            if epoch:
                view.seal(epoch)
                model.seal(epoch)
        elif op == "publish":
            published = int(rng.integers(published, epoch + 1))
        check()
    assert universe or case == "empty"


def test_write_path_builds_no_python_rows():
    """ISSUE 25's guard, by count and not by clock: closes, a seal and
    a capture with no read turn no row into Python objects, the ledger
    never books `serve.materialize`, and `k` point reads materialise at
    most `k` rows (an int key: the index is two numpy arrays)."""
    import pyarrow as pa

    from arroyo_tpu.obs import timeline
    from arroyo_tpu.serve.store import SERVE_TABLE, seal_op, stage_batch
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.tables import GlobalTable

    timeline.clear()
    view = _view(key_names=["auction"], value_names=["window", "cnt"])
    op = type("Op", (), {"_serve_view": view})()
    tm = type("TM", (), {"tables": {
        SERVE_TABLE: GlobalTable(global_table(SERVE_TABLE))}})()
    n, closes = 50_000, 6
    for c in range(closes):
        end = pa.array(np.full(n, 2_000_000 * (c + 5)), pa.timestamp("us"))
        stage_batch(view, pa.RecordBatch.from_pydict({
            "auction": pa.array(np.arange(n, dtype=np.int64) + 1_000 * c),
            "window": pa.StructArray.from_arrays([end, end],
                                                 ["start", "end"]),
            "cnt": pa.array(np.full(n, c, dtype=np.int64)),
        }))
    seal_op(op, 1, tm)
    blob, is_base = tm.tables[SERVE_TABLE].serialize_delta(1)
    # segments alone: the blob is the flush's to pack (ISSUE 39)
    assert is_base and callable(blob) and len(blob()) > n * 8
    # one entry for the epoch beside the meta record, not one per key
    assert len(tm.tables[SERVE_TABLE].data) == 2
    totals = timeline.totals()
    assert view.staged_rows == n * closes
    assert view.materialized_rows == 0
    assert "serve.materialize" not in totals
    # both count the rows staged: the merge is not the barrier's any more
    assert totals["serve.seal"]["n"] == n * closes
    assert totals["serve.mirror"]["n"] == n * closes
    k = 7
    for key in range(2_000, 2_000 + k):
        found, value = view.read((key,), 1)
        assert found and value["cnt"] == min(key // 1_000, closes - 1)
        assert value["window"]["end"] == 2_000_000_000 * (value["cnt"] + 5)
    assert view.read((-1,), 1) == (False, None)
    st = view.stats()
    assert st["materialized_rows"] == k and st["staged_rows"] == n * closes
    assert st["keys"] == n + 1_000 * (closes - 1)
    assert timeline.totals()["serve.materialize"]["n"] == k


# -- ISSUE 39: what a barrier cuts, and what the flush resolves ---------------


def _hop_view(**kw):
    return _view(key_names=["auction"], value_names=["window", "count"],
                 **kw)


def _scripted_run(url, lag, epochs=20):
    """Twenty barriers of a viewed hop operator through a real
    `TableManager`: three closes an epoch of recurring and new keys,
    `seal_op`, `capture`, and `flush_captured` `lag` epochs later (0 =
    at the barrier's heels; 2 = two epochs late, while later epochs
    stage and capture). Returns (the reports, {path: blob}, the view, its
    last values, the batches staged by epoch)."""
    from types import SimpleNamespace

    from arroyo_tpu.serve.store import SERVE_TABLE, seal_op, stage_batch
    from arroyo_tpu.state.backend import StateBackend
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.table_manager import TableManager
    from arroyo_tpu.types import TaskInfo
    from test_replica import _hop_batch  # (it imports this module's)

    backend = StateBackend(url, "bi").initialize()
    tm = TableManager(backend, TaskInfo("bi", 3, "hop", 0, 1), 0)
    asyncio.run(tm.open({SERVE_TABLE: global_table(SERVE_TABLE)}))
    view = _hop_view()
    op = SimpleNamespace(_serve_view=view)
    rng = np.random.default_rng(39)
    inflight, reports, last, batches = [], {}, {}, {}

    def flush():
        epoch, staged = inflight.pop(0)
        reports[epoch] = tm.flush_captured(epoch, staged)

    for epoch in range(1, epochs + 1):
        for close in range(3):
            keys = np.concatenate([
                rng.choice(np.arange(200), 60, replace=False),
                np.arange(50) + 10_000 * epoch + 100 * close])
            b = _hop_batch(rng, keys, 3 * epoch + close)
            batches.setdefault(epoch, []).append(b)
            stage_batch(view, b)
            last.update(zip(keys.tolist(), b.column(2).to_pylist()))
        seal_op(op, epoch, tm)
        inflight.append((epoch, tm.capture(epoch, None)))
        while len(inflight) > lag:
            flush()
    while inflight:
        flush()
    paths = {f["path"] for r in reports.values()
             for f in r[SERVE_TABLE]["chain"]}
    blobs = {path: backend.read_blob(path) for path in sorted(paths)}
    return reports, blobs, view, last, batches


def test_a_deferred_capture_writes_the_same_bytes_two_epochs_late(
        tmp_path):
    """The byte identity of ISSUE 39: resolved at the barrier's heels or
    two epochs late, a capture writes the same blobs under the same paths
    and reports the same chain (every `bytes` filled in, one compaction
    of the mirror past `_MIRROR_SEGMENTS`, one rebase); the first two
    blobs are the parent's arithmetic spelled out; a follower seeded from
    the last chain serves what the worker serves."""
    import msgpack
    import pyarrow as pa

    from arroyo_tpu.obs import timeline
    from arroyo_tpu.serve.store import (
        _MIRROR_SEGMENTS,
        META_KEY,
        SEG_PREFIX,
        SERVE_TABLE,
        _ipc_bytes,
        _keep_last,
        _Segment,
        seed_from_mirror,
    )
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.tables import GlobalTable

    # the bytes rule would read an estimate while a flush is in flight
    # (its own test below): here the length rule alone decides
    with update(state={"rebase_bytes_factor": 1e9}):
        timeline.clear()
        at_once = _scripted_run(f"file://{tmp_path}/a", 0)
        totals = timeline.totals()
        late = _scripted_run(f"file://{tmp_path}/b", 2)
    reports, blobs, view, last, batches = at_once
    assert json.dumps(reports, sort_keys=True) == json.dumps(
        late[0], sort_keys=True)
    assert blobs == late[1] and len(blobs) == 20 and all(blobs.values())
    chains = [reports[e][SERVE_TABLE]["chain"] for e in range(1, 21)]
    for e, chain in enumerate(chains, 1):
        assert chain[-1]["epoch"] == e
        assert all(f["bytes"] == len(blobs[f["path"]]) for f in chain)
    # base at 1, sixteen deltas, a rebase at 18
    assert [len(c) for c in chains] == list(range(1, 18)) + [1, 2, 3]
    assert [c[0]["base"] for c in chains] == [True] * 20
    # every staged row was the flush's: the engagement share is 1.0
    assert totals["flush.resolve"]["count"] == 20
    assert totals["flush.resolve"]["n"] == totals["serve.seal"]["n"] == (
        view.deferred_rows) == view.staged_rows == 20 * 3 * 110
    # the parent's arithmetic for the first base and the first delta
    for e in (1, 2):
        seg = _Segment(table=_keep_last(pa.concat_tables(
            [_Segment(raw=b).table(view) for b in batches[e]]), 1))
        entries = [[f"{SEG_PREFIX}/0/{e}/{e - 1}",
                    _ipc_bytes(seg.table(view)), e]]
        if e == 1:
            entries.insert(0, [META_KEY, view.describe(), 1])
        assert blobs[chains[e - 1][-1]["path"]] == msgpack.packb(
            {"v": 2, "b": e == 1, "e": entries, "t": []}, use_bin_type=True)
    # the mirror compacted once (17 entries -> 9), through tombstones
    assert len(view._mirror_log) == _MIRROR_SEGMENTS // 2 + 1 + 3
    packed = msgpack.unpackb(blobs[chains[16][-1]["path"]], raw=False)
    assert len(packed["t"]) == _MIRROR_SEGMENTS // 2
    # and a follower over the last chain serves the worker's view
    table = GlobalTable(global_table(SERVE_TABLE))
    table.load_chain([blobs[f["path"]] for f in chains[-1]])
    fview = _hop_view()
    seed_from_mirror(fview, table)
    for k in list(range(200)) + [10_000, 200_149, 7]:
        want = (True, last[k]) if k in last else (False, None)
        got = view.read((k,), 20)
        assert (got[0], got[1] and got[1]["count"]) == want
        assert fview.read((k,), 20) == got == late[2].read((k,), 20)
    assert fview.stats()["keys"] == view.stats()["keys"] == len(last)


def test_a_flush_in_flight_counts_by_its_staged_inputs_and_a_failure_writes_nothing(
        tmp_path):
    """`_should_rebase` at a barrier that finds an epoch's `bytes` still
    unknown reckons with the staged inputs' size (else a chain nobody
    flushed would never rebase by bytes); a resolution that raises fails
    `flush_captured` before anything is written."""
    from types import SimpleNamespace

    from arroyo_tpu.serve.store import SERVE_TABLE, seal_op, stage_batch
    from arroyo_tpu.state.backend import StateBackend
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.table_manager import TableManager
    from arroyo_tpu.state.tables import Deferred
    from arroyo_tpu.types import TaskInfo
    from test_replica import _hop_batch

    backend = StateBackend(f"file://{tmp_path}/r", "rb").initialize()
    tm = TableManager(backend, TaskInfo("rb", 3, "hop", 0, 1), 0)
    asyncio.run(tm.open({SERVE_TABLE: global_table(SERVE_TABLE)}))
    view = _hop_view()
    op = SimpleNamespace(_serve_view=view)
    rng = np.random.default_rng(3)
    staged = {}
    for epoch in range(1, 6):
        stage_batch(view, _hop_batch(rng, np.arange(500) + 1_000 * epoch,
                                     epoch))
        seal_op(op, epoch, tm)
        staged[epoch] = tm.capture(epoch, None)
    chains = [staged[e][SERVE_TABLE]["chain"] for e in range(1, 6)]
    # equal epochs, factor 2.0: three deltas outweigh the base twice over
    assert [len(c) for c in chains] == [1, 2, 3, 4, 1]
    assert all(f["bytes"] is None for c in chains for f in c)
    for epoch in range(1, 6):
        meta = tm.flush_captured(epoch, staged[epoch])
        assert all(f["bytes"] == len(backend.read_blob(f["path"]))
                   for f in meta[SERVE_TABLE]["chain"])
    assert tm._chains[SERVE_TABLE][0]["bytes"] > 0
    assert list(tm._late_blobs[SERVE_TABLE]) == [
        tm._chains[SERVE_TABLE][0]["path"]]

    def boom():
        raise RuntimeError("resolution failed")

    tm.tables[SERVE_TABLE].put("__serve_seg__/0/6/5", Deferred(boom))
    bad = tm.capture(6, None)
    with pytest.raises(RuntimeError, match="resolution failed"):
        tm.flush_captured(6, bad)
    assert backend.read_blob(bad[SERVE_TABLE]["chain"][-1]["path"]) is None


def test_a_read_racing_the_flush_gets_one_table_merged_once(monkeypatch):
    """Once and only once: a `read` on the loop and the flush thread ask
    for the same unresolved layer at the same moment, fifty times: both
    get the one table, `_keep_last` ran once a round, and the read sees
    exactly the epoch's view."""
    import threading

    from arroyo_tpu.serve import store
    from arroyo_tpu.serve.store import stage_batch
    from test_replica import _hop_batch

    calls = []
    keep_last = store._keep_last

    def counted(table, n_keys):
        calls.append(table.num_rows)
        return keep_last(table, n_keys)

    monkeypatch.setattr(store, "_keep_last", counted)
    rng = np.random.default_rng(50)
    view = _hop_view()
    for epoch in range(1, 51):
        for close in range(4):
            stage_batch(view, _hop_batch(
                rng, rng.choice(np.arange(5_000), 2_000, replace=False),
                4 * epoch + close))
        marker = _hop_batch(rng, [7], 4 * epoch + 4)
        stage_batch(view, marker)
        sealed = view.seal(epoch)
        assert sealed.deferred and not calls
        gate, got = threading.Barrier(2), []

        def flush():
            gate.wait()
            got.append(sealed.table(view))

        t = threading.Thread(target=flush)
        t.start()
        gate.wait()
        found, value = view.read((7,), epoch)
        t.join()
        assert found and value["count"] == marker.column(2)[0].as_py()
        assert got[0] is sealed.table(view) and not sealed.deferred
        # (a fold past `_SERVED_SEGMENTS` layers compacts `served`: a
        # merge of its own, of more rows)
        assert [c for c in calls if c <= 8_001] == [4 * 2_000 + 1]
        calls.clear()


@pytest.mark.parametrize("case", ["segments", "partials", "updating",
                                  "rows_after_a_batch"])
def test_a_stage_with_a_dict_layer_seals_at_the_barrier(case, monkeypatch):
    """The rule, read from the stage's layers: segments alone are filed
    un-merged (`serve.seal` books their rows and `_keep_last` does not
    run before the flush asks); session partials and an updating
    aggregate's rows (a dict layer) seal at the barrier as ever."""
    from arroyo_tpu.obs import timeline
    from arroyo_tpu.serve import store
    from arroyo_tpu.serve.store import SERVE_TABLE, seal_op, stage_batch
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.tables import Deferred, GlobalTable
    from test_replica import _hop_batch

    calls = []
    keep_last = store._keep_last
    monkeypatch.setattr(store, "_keep_last", lambda t, n: (
        calls.append(t.num_rows), keep_last(t, n))[1])
    timeline.clear()
    rng = np.random.default_rng(4)
    view = _hop_view()
    op = type("Op", (), {"_serve_view": view})()
    mirror = GlobalTable(global_table(SERVE_TABLE))
    tm = type("TM", (), {"tables": {SERVE_TABLE: mirror}})()
    if case != "updating":
        stage_batch(view, _hop_batch(rng, np.arange(300), 1))
        stage_batch(view, _hop_batch(rng, np.arange(200, 500), 2))
    if case == "partials":
        stage_batch(view, _hop_batch(rng, [900, 901], 3), partial=True)
    elif case in ("updating", "rows_after_a_batch"):
        view.stage((900,), {"count": 5})
        view.stage_tomb((3,))
    seal_op(op, 1, tm)
    rows = {"segments": 600, "partials": 602, "updating": 2,
            "rows_after_a_batch": 602}[case]
    assert timeline.totals()["serve.seal"]["n"] == rows
    seg_key = "__serve_seg__/0/1/0"
    if case == "segments":
        assert not calls and view.pending[1].deferred
        assert view.deferred_rows == view.stats()["deferred_rows"] == 600
        assert isinstance(mirror.raw(seg_key), Deferred)
        blob, _ = mirror.serialize_delta(1)
        assert callable(blob) and not calls
        blob()
        assert calls == [600] and not view.pending[1].deferred
    else:
        # merged (and mirrored) at the barrier, nothing left for a flush
        assert view.deferred_rows == 0
        if case == "updating":
            assert isinstance(view.pending[1], dict) and not calls
            assert mirror.get((900,)) == {"count": 5}
        else:
            assert calls == [rows] and not view.pending[1].deferred
            assert isinstance(mirror.raw(seg_key), memoryview)
        assert isinstance(mirror.serialize_delta(1)[0], bytes)
    assert view.read((900,), 1)[0] == (case != "segments")
    assert view.read((3,), 1)[0] == (case in ("segments", "partials"))


def test_model_faithful_reader_clean_and_mutant_caught():
    """The PR 9 checker with the reader actor: faithful model explores
    exhaustively clean with reads enabled; the mutant's counterexample
    is exercised by the standard corpus tests (test_model_check.py
    parametrizes over every mutant, this one included)."""
    from arroyo_tpu.analysis.model import explore as explore_mod
    from arroyo_tpu.analysis.model import mutants as mutants_mod
    from arroyo_tpu.analysis.model.extract import (
        job_state_machine,
        load_project,
    )
    from arroyo_tpu.analysis.model.spec import Model, ModelConfig
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    _m, terminals, table = job_state_machine(
        load_project(repo, roots=("arroyo_tpu/controller",))
    )
    cfg = ModelConfig(workers=2, epochs=2, inflight=2, faults=1,
                      restarts=2, reads=2,
                      fault_kinds=("fault.kill",))
    res = explore_mod.explore(Model(cfg, table, terminals),
                              budget=400_000)
    assert res.exhaustive
    assert not res.violations, [t.violation for t in res.violations]
    assert "serve_reads_unpublished_epoch" in mutants_mod.MUTANTS


# -- routing exactness -------------------------------------------------------


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_owner_subtask_matches_engine_ownership(shards):
    """store.owner_subtask == the engine's shuffle partitioning == the
    mesh directory's owners_for, for int / unsigned / string / composite
    keys — the routing contract the gateway relies on."""
    from arroyo_tpu.parallel.sharded_state import MeshSlotDirectory

    msd = MeshSlotDirectory(shards)
    int_keys = list(range(200)) + [10**12 + 7, -5]
    for k in int_keys:
        got = owner_subtask((canon_value(k, "i"),), ("i",), shards)
        col = np.asarray([k], dtype=np.int64)
        want = int(server_for_hash_array(
            hash_arrays([hash_column(col)]), shards)[0])
        assert got == want, (k, shards)
        assert got == int(msd.owners_for([col], 1)[0]), (k, shards)
    for k in ["", "a", "auction-17", "x" * 40]:
        got = owner_subtask((canon_value(k, "s"),), ("s",), shards)
        col = np.array([k], dtype=object)
        want = int(server_for_hash_array(
            hash_arrays([hash_column(col)]), shards)[0])
        assert got == want, (k, shards)
    # composite (int, str) keys: per-column hash + seeded combine
    for k in [(1, "a"), (2, "bb"), (10**9, "ccc")]:
        got = owner_subtask(
            (canon_value(k[0], "i"), canon_value(k[1], "s")),
            ("i", "s"), shards,
        )
        cols = [hash_column(np.asarray([k[0]], dtype=np.int64)),
                hash_column(np.array([k[1]], dtype=object))]
        want = int(server_for_hash_array(hash_arrays(cols), shards)[0])
        assert got == want, (k, shards)


# -- gateway cache + admission ----------------------------------------------


def test_cache_epoch_and_incarnation_invalidation():
    c = _Cache()
    c.put(("j", "t", "1"), 3, 1, {"cnt": 5}, budget=1 << 20)
    assert c.get(("j", "t", "1"), 3, 1) == {"cnt": 5}
    # a newly published epoch silently invalidates
    assert c.get(("j", "t", "1"), 4, 1) is None
    c.put(("j", "t", "1"), 4, 1, {"cnt": 6}, budget=1 << 20)
    # a reschedule (new incarnation) invalidates too
    assert c.get(("j", "t", "1"), 4, 2) is None
    # byte budget: inserting past it evicts LRU-first
    small = _Cache()
    for i in range(100):
        small.put(("j", "t", str(i)), 1, 1, {"v": "x" * 50}, budget=2000)
    assert small.bytes <= 2000
    assert len(small.data) < 100
    # job GC empties every entry of that job
    c.drop_job("j")
    assert not c.data and c.bytes == 0


def test_tenant_bucket_throttles_and_noisy_penalty():
    b = _Bucket(100.0)
    # burst allows 2x rate up front, then sustained rate gates
    assert b.take(150, 100.0)
    assert not b.take(100, 100.0)
    # noisy penalty wiring: a flagged tenant gets a squeezed rate
    ctrl_stub = type("C", (), {"jobs": {}})()
    from arroyo_tpu.serve.gateway import StateGateway

    gw = StateGateway(ctrl_stub)
    with update(serve={"tenant_qps": 50.0, "noisy_penalty": 0.1}):
        assert gw._admit("quiet", 40)
        gw.flag_noisy("hot")
        # hot tenant's burst is 2 * 0.1 * 50 = 10 keys
        assert not gw._admit("hot", 40)
        assert gw._admit("hot", 5)
    # doctor-report wiring: a noisy-neighbor verdict flags the suspect
    # job's tenant
    job = type("J", (), {"tenant": "hogt"})()
    ctrl_stub.jobs["hog-job"] = job
    gw.note_doctor_report({"verdict": {"cause": "noisy-neighbor",
                                       "suspect": "hog-job"}})
    assert "hogt" in gw.status()["noisy_tenants"]
    # admission-quota wiring: a tenant at its COMPUTE slot quota gets
    # its read rate clamped by the same penalty
    class _Adm:
        def tenant_at_quota(self, tenant):
            return tenant == "satd"

    ctrl_stub.admission = _Adm()
    with update(serve={"tenant_qps": 50.0, "noisy_penalty": 0.1}):
        assert not gw._admit("satd", 40)  # burst is 10, not 100
        assert gw._admit("satd", 5)
        assert gw._admit("roomy", 40)


# -- end-to-end: embedded cluster, REST, rescale, kill -----------------------


def _serve_sql(wd, keys=8, rate=20000, count=2_000_000):
    return f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '{rate}',
      message_count = '{count}', start_time = '0',
      realtime = 'true', replay = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{wd}/out.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % {keys} as k,
             tumble(interval '100 millisecond') as w, count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """


async def _wait_published(job, epoch=1, timeout=30.0):
    deadline = time.monotonic() + timeout
    while job.published_epoch < epoch:
        assert time.monotonic() < deadline, (
            f"no published epoch >= {epoch} (at {job.published_epoch})"
        )
        await asyncio.sleep(0.1)


async def _wait_found(c, jid, table, key, timeout=30.0):
    deadline = time.monotonic() + timeout
    while True:
        out = await c.serve.read(jid, table, [key])
        r = out.get("results", [{}])[0]
        if r.get("found"):
            return out
        assert time.monotonic() < deadline, f"key {key} never served: {out}"
        await asyncio.sleep(0.2)


def test_e2e_point_bulk_rest_and_fencing(tmp_path):
    """The worked-example path: run a keyed windowed aggregation, read a
    point key and a bulk set through the REST routes at the published
    epoch, hit the cache on the second read, fence a stale-incarnation
    QueryState, and verify GC on stop."""
    from aiohttp import ClientSession, web

    from arroyo_tpu.api.rest import build_app
    from arroyo_tpu.metrics import REGISTRY

    wd = str(tmp_path)

    async def main():
        with update(pipeline={"checkpointing": {
                "interval": 0.5, "storage_url": f"{wd}/ck"}}):
            sched = EmbeddedScheduler()
            c = await ControllerServer(sched).start()
            job = await c.submit_job(
                "sv", sql=_serve_sql(wd), n_workers=2, parallelism=2,
                storage_url=f"{wd}/ck/sv",
            )
            app = build_app(c, db_path=f"{wd}/api.db")
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            base = f"http://127.0.0.1:{port}/api/v1"
            try:
                await c.wait_for_state("sv", JobState.RUNNING, timeout=30)
                await _wait_published(job, 1)
                await _wait_found(c, "sv", "tumbling_window", 0)
                async with ClientSession() as http:
                    # table listing
                    async with http.get(f"{base}/jobs/sv/state") as resp:
                        assert resp.status == 200
                        doc = await resp.json()
                    tables = {d["table"] for d in doc["data"]}
                    assert "tumbling_window" in tables
                    assert doc["publishedEpoch"] >= 1
                    # point read
                    async with http.get(
                        f"{base}/jobs/sv/state/tumbling_window?key=0"
                    ) as resp:
                        assert resp.status == 200
                        point = await resp.json()
                    r = point["results"][0]
                    assert r["found"], point
                    assert "__agg_out_1" in r["value"], r
                    # bulk read: all keys found, each exactly once
                    async with http.post(
                        f"{base}/jobs/sv/state/tumbling_window",
                        json={"keys": list(range(8))},
                    ) as resp:
                        assert resp.status == 200
                        bulk = await resp.json()
                    assert len(bulk["results"]) == 8
                    assert all(x["found"] for x in bulk["results"]), bulk
                    # cache: re-read the same keys at the same epoch
                    async with http.post(
                        f"{base}/jobs/sv/state/tumbling_window",
                        json={"keys": list(range(8))},
                    ) as resp:
                        bulk2 = await resp.json()
                    if bulk2.get("epoch") == bulk.get("epoch"):
                        assert bulk2["cache"]["hits"] > 0, bulk2
                    # unknown table 404s, missing key 400s
                    async with http.get(
                        f"{base}/jobs/sv/state/nope?key=1"
                    ) as resp:
                        assert resp.status == 404
                    async with http.get(
                        f"{base}/jobs/sv/state/tumbling_window"
                    ) as resp:
                        assert resp.status == 400
                # incarnation fencing: a QueryState carrying a stale
                # namespace answers stale_route (retriable), never data
                w = job.workers[0]
                resp = await w.client.call(
                    "WorkerGrpc", "QueryState",
                    {"job_id": "sv", "mode": "get",
                     "table": "tumbling_window", "keys": [0],
                     "epoch": job.published_epoch,
                     "data_ns": "sv@999"},
                )
                assert "stale_route" in resp.get("error", "")
                assert resp.get("retriable") is True
                # /debug/serve?job= (ISSUE 25): per-view occupancy with
                # the write side's two counts — every emitted row went
                # in as part of a batch, and only rows that reads
                # returned became Python objects
                views = await c.serve.view_stats("sv")
                assert views and all(
                    v["table"] == "tumbling_window" for v in views)
                staged = sum(v["staged_rows"] for v in views)
                made = sum(v["materialized_rows"] for v in views)
                assert staged >= 8 and 0 < made <= 64, views
                # GC on stop: cache + routing state expunged, serve
                # series dropped with the job's metrics
                assert c.serve.cache.data
                await c.stop_job("sv", "immediate")
                await c.wait_for_state(
                    "sv", JobState.STOPPED, JobState.FAILED,
                    JobState.FINISHED, timeout=30,
                )
                assert not any(
                    k[0] == "sv" for k in c.serve.cache.data
                )
                assert "sv" not in c.serve._tables
                REGISTRY.drop_job("sv")  # TTL path shortcut for the test
                assert 'job="sv"' not in REGISTRY.expose()
            finally:
                await runner.cleanup()
                await c.stop()

    asyncio.run(main())


def test_gateway_routing_is_engine_ownership_across_rescale(tmp_path):
    """ISSUE 12 satellite: for every key the gateway routes to worker W
    at subtask S, S actually owns the key (owners_for cross-check) and
    the job's assignment table maps (node, S) -> W — held at parallelism
    2 and re-held across a live controller-driven rescale to 4 and back
    to 2 (fresh assignments + fresh view parallelism each time)."""
    from arroyo_tpu.parallel.sharded_state import MeshSlotDirectory

    wd = str(tmp_path)

    async def assert_routing(c, sched, jid, table, keys):
        info = (await c.serve.tables(jid))[table]
        job = c.jobs[jid]
        par = int(info["parallelism"])
        kinds = tuple(info["key_kinds"])
        node = int(info["node_id"])
        msd = MeshSlotDirectory(par) if par >= 2 else None
        host = {}  # task_index -> worker_id actually hosting the view
        for w, _t in sched.pool:
            jr = w._jobs.get(jid)
            if jr is None:
                continue
            for sub in jr.program.subtasks:
                for op in sub.runner.ops:
                    v = getattr(op, "_serve_view", None)
                    if v is not None and v.table == table:
                        assert v.parallelism == par
                        host[v.task_index] = w.worker_id
        assert len(host) == par, (host, par)
        for k in keys:
            key = (canon_value(k, kinds[0]),)
            own = owner_subtask(key, kinds, par)
            if msd is not None:
                col = np.asarray([key[0]], dtype=np.int64)
                assert own == int(msd.owners_for([col], 1)[0]), (k, par)
            # the gateway's worker choice == the ownership map's
            assert job.assignments[(node, own)] == host[own], (k, par)
        # and the fanned-out read actually finds every key (no
        # mis-route ever answers not_owned)
        out = await c.serve.read(jid, table, keys)
        assert out["outcome"] == "ok", out
        assert all(r["found"] for r in out["results"]), out

    async def main():
        with update(pipeline={"checkpointing": {
                "interval": 0.4, "storage_url": f"{wd}/ck"}}):
            sched = EmbeddedScheduler()
            c = await ControllerServer(sched).start()
            job = await c.submit_job(
                "rs", sql=_serve_sql(wd, keys=16), n_workers=2,
                parallelism=2, storage_url=f"{wd}/ck/rs",
            )
            try:
                await c.wait_for_state("rs", JobState.RUNNING, timeout=30)
                await _wait_published(job, 1)
                await _wait_found(c, "rs", "tumbling_window", 0)
                info = (await c.serve.tables("rs"))["tumbling_window"]
                node = int(info["node_id"])
                keys = list(range(16))
                await assert_routing(c, sched, "rs", "tumbling_window",
                                     keys)
                for target in (4, 2):
                    await c.rescale_job("rs", {node: target})
                    deadline = time.monotonic() + 60
                    while not (job.state == JobState.RUNNING
                               and job.graph.nodes[node].parallelism
                               == target):
                        assert time.monotonic() < deadline, (
                            target, job.state)
                        await asyncio.sleep(0.2)
                    await _wait_published(job, job.published_epoch + 1)
                    await _wait_found(c, "rs", "tumbling_window", 0)
                    await assert_routing(c, sched, "rs",
                                         "tumbling_window", keys)
            finally:
                await c.stop_job("rs", "immediate")
                await c.wait_for_state(
                    "rs", JobState.STOPPED, JobState.FAILED,
                    JobState.FINISHED, timeout=30,
                )
                await c.stop()

    asyncio.run(main())


def test_reads_degrade_retriable_on_worker_kill(tmp_path):
    """Chaos shape (fast tier): SIGKILL one pool worker while reads
    run. Every read outcome is found-at-published-epoch, not-found, or
    a retriable error — never an exception, never a torn value (the
    full deterministic-value variant runs in the fleet harness's
    --serve-kill scenario). After recovery, reads serve again."""
    wd = str(tmp_path)

    async def main():
        with update(
            pipeline={"checkpointing": {
                "interval": 0.5, "storage_url": f"{wd}/ck"}},
            controller={"heartbeat_timeout": 6.0},
        ):
            sched = EmbeddedScheduler()
            c = await ControllerServer(sched).start()
            job = await c.submit_job(
                "kl", sql=_serve_sql(wd), n_workers=2, parallelism=2,
                storage_url=f"{wd}/ck/kl",
            )
            try:
                await c.wait_for_state("kl", JobState.RUNNING, timeout=30)
                await _wait_published(job, 1)
                await _wait_found(c, "kl", "tumbling_window", 0)
                live = [w for w, _t in sched.pool
                        if not getattr(w, "_shutdown_started", False)]
                kill_task = asyncio.ensure_future(live[0].shutdown())
                outcomes = set()
                deadline = time.monotonic() + 30
                recovered_found = False
                while time.monotonic() < deadline:
                    out = await c.serve.read(
                        "kl", "tumbling_window", [0, 1, 2, 3]
                    )
                    if out.get("error"):
                        assert out.get("retriable"), out
                        outcomes.add("req-error")
                    else:
                        for r in out["results"]:
                            if r.get("found"):
                                outcomes.add("found")
                            elif r.get("error"):
                                assert r.get("retriable", True), r
                                outcomes.add("key-error")
                            else:
                                outcomes.add("miss")
                        if (job.restarts > 0
                                and job.state == JobState.RUNNING
                                and all(r.get("found")
                                        for r in out["results"])):
                            recovered_found = True
                            break
                    await asyncio.sleep(0.2)
                await kill_task
                assert recovered_found, (
                    f"post-recovery reads never served: {outcomes}, "
                    f"restarts={job.restarts}, state={job.state}"
                )
            finally:
                await c.stop_job("kl", "immediate")
                await c.wait_for_state(
                    "kl", JobState.STOPPED, JobState.FAILED,
                    JobState.FINISHED, timeout=30,
                )
                await c.stop()

    asyncio.run(main())


def test_updating_aggregate_view_and_restore_seed(tmp_path):
    """Updating aggregates serve their emitted values; a checkpoint-
    stopped job's restart seeds the view from restored state, so reads
    work before the first post-restore flush."""
    wd = str(tmp_path)
    sql = f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '20000',
      message_count = '2000000', start_time = '0',
      realtime = 'true', replay = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{wd}/u.json',
      format = 'debezium_json', type = 'sink'
    );
    INSERT INTO out
    SELECT counter % 4 as k, count(*) as cnt FROM impulse GROUP BY 1;
    """

    async def main():
        with update(pipeline={"checkpointing": {
                "interval": 0.5, "storage_url": f"{wd}/ck"}}):
            c = await ControllerServer(EmbeddedScheduler()).start()
            job = await c.submit_job(
                "up", sql=sql, n_workers=2, parallelism=2,
                storage_url=f"{wd}/ck/up",
            )
            try:
                await c.wait_for_state("up", JobState.RUNNING, timeout=30)
                await _wait_published(job, 1)
                tables = await c.serve.tables("up")
                name = next(t for t in tables
                            if tables[t]["kind"] == "updating")
                vfield = tables[name]["value_fields"][0]
                out = await _wait_found(c, "up", name, 0)
                r = out["results"][0]
                assert r["value"].get(vfield, 0) > 0, out
                # checkpoint-stop, resubmit (same storage): the restored
                # incarnation must serve the key BEFORE any new flush
                await c.stop_job("up", "checkpoint")
                await c.wait_for_state("up", JobState.STOPPED,
                                       timeout=60)
                job2 = await c.submit_job(
                    "up2", sql=sql, n_workers=2, parallelism=2,
                    storage_url=f"{wd}/ck/up",
                )
                await c.wait_for_state("up2", JobState.RUNNING,
                                       timeout=30)
                out2 = await _wait_found(c, "up2", name, 0, timeout=20)
                assert vfield in out2["results"][0]["value"], out2
            finally:
                for jid in ("up", "up2"):
                    if jid in c.jobs and not c.jobs[jid].state.is_terminal():
                        await c.stop_job(jid, "immediate")
                        await c.wait_for_state(
                            jid, JobState.STOPPED, JobState.FAILED,
                            JobState.FINISHED, timeout=30,
                        )
                await c.stop()

    asyncio.run(main())
