"""Follower read replicas — the serving tier off the checkpoint stream
(ISSUE 20).

Coverage of the replica tier's load-bearing contracts:

  * the model: with a follower enabled and follower-death faults in
    the alphabet, the faithful protocol explores exhaustively clean
    (the `follower_serves_unpublished_epoch` mutant's counterexample is
    exercised by test_model_check.py's per-mutant parametrization);
  * cache-vs-staleness (satellite 3): the gateway's read-through cache
    keys on the SOURCE's epoch, so a lagging follower can never serve
    a cached entry newer than its own served epoch;
  * view plans (satellite 1): session windows serve open sessions as
    `partial: true` rows; updating joins serve per-key joined row sets
    (cross product / outer null-padding) and refuse residual joins;
  * end to end: a durable job's reads route follower-first with
    response-carried staleness <= replica.max_lag_epochs (one
    checkpoint interval) and ZERO further worker QueryState RPCs;
    killing the follower fails reads over worker-ward (no fatal, no
    wrong value) and the mount reattaches by re-resolving latest.json.
"""

import asyncio
import time

import pytest

from arroyo_tpu.config import config, update
from arroyo_tpu.controller.controller import ControllerServer
from arroyo_tpu.controller.scheduler import EmbeddedScheduler
from arroyo_tpu.controller.state_machine import JobState
from arroyo_tpu.serve import ServeView
from arroyo_tpu.serve.gateway import StateGateway

from test_serve import _serve_sql, _wait_found, _wait_published


# -- the model: faithful protocol clean with followers enabled ---------------


def test_model_faithful_with_followers_clean():
    """The PR 9 checker with the follower actor enabled and abrupt
    follower death in the fault alphabet: the faithful protocol
    explores exhaustively with no REPLICA violation — every reattach
    re-resolves latest.json, so no reachable interleaving serves an
    unpublished epoch. (The mutant that reattaches from the in-memory
    issued-epoch counter is caught with a replayable counterexample in
    test_model_check.py.)"""
    from pathlib import Path

    from arroyo_tpu.analysis.model import explore as explore_mod
    from arroyo_tpu.analysis.model import mutants as mutants_mod
    from arroyo_tpu.analysis.model.extract import (
        job_state_machine,
        load_project,
    )
    from arroyo_tpu.analysis.model.spec import Model, ModelConfig

    repo = Path(__file__).resolve().parents[1]
    _m, terminals, table = job_state_machine(
        load_project(repo, roots=("arroyo_tpu/controller",))
    )
    cfg = ModelConfig(workers=2, epochs=1, inflight=2, faults=1,
                      restarts=2, reads=1, followers=1,
                      fault_kinds=("fault.follower_die",))
    res = explore_mod.explore(Model(cfg, table, terminals),
                              budget=400_000)
    assert res.exhaustive
    assert not res.violations, [t.violation for t in res.violations]
    assert "follower_serves_unpublished_epoch" in mutants_mod.MUTANTS


# -- satellite 3: the cache can never outrun its source ----------------------


class _StubFollowerView:
    def __init__(self, served_epoch, values):
        self.served_epoch = served_epoch
        self.values = values


class _StubReplicas:
    """route()/read_one() shaped like ReplicaManager, pinned to one
    lagging follower view."""

    def __init__(self, view):
        self._view = view

    def route(self, job, table):
        return self._view

    def read_one(self, job_id, table, key_values):
        if self._view is None:
            return None
        found = key_values in self._view.values
        return {"found": found,
                "value": self._view.values.get(key_values),
                "epoch": self._view.served_epoch}

    def tables_meta(self, job_id):
        return None

    def lag_epochs(self, job):
        return None


def _stub_job(published_epoch=5):
    class _State:
        value = "Running"

        @staticmethod
        def is_terminal():
            return False

    return type("J", (), {
        "job_id": "j", "tenant": "t", "schedules": 1,
        "backend": object(), "published_epoch": published_epoch,
        "state": _State, "workers": [], "assignments": {},
        "mount": None, "stop_requested": False,
    })()


def test_cache_never_serves_newer_than_follower_epoch():
    """Satellite 3 regression: pre-seed the cache with a value cached
    at the PUBLISHED epoch (5) by a worker-routed read; a follower-
    routed read whose mount is one epoch behind (served_epoch 4) must
    NOT answer from that newer cache entry — it serves the follower's
    own (older) value and re-caches it at the follower's epoch."""
    job = _stub_job(published_epoch=5)
    ctrl = type("C", (), {})()
    ctrl.jobs = {"j": job}
    follower = _StubFollowerView(4, {(0,): {"cnt": "follower-old"}})
    ctrl.replicas = _StubReplicas(follower)
    gw = StateGateway(ctrl)
    info = {"table": "t", "node_id": 1, "parallelism": 1,
            "key_kinds": ["i"], "routable": True}
    gw._tables["j"] = (job.schedules, {"t": info})

    async def main():
        # a worker-routed read cached this key at epoch 5
        gw.cache.put(("j", "t", "0"), 5, job.schedules,
                     {"cnt": "worker-new"}, budget=1 << 20)
        out = await gw._routed_read(job, "t", [0])
        assert out["source"] == "follower"
        assert out["served_epoch"] == 4
        assert out["staleness"] == 1
        r = out["results"][0]
        assert r["found"] and not r.get("cached"), out
        # the follower's value won, never the newer cached one
        assert r["value"] == {"cnt": "follower-old"}, out
        # the entry is now keyed at the follower's epoch: a follower
        # re-read hits it, a worker-routed probe at 5 drops it
        out2 = await gw._routed_read(job, "t", [0])
        assert out2["results"][0].get("cached"), out2
        assert out2["served_epoch"] == 4
        ctrl.replicas._view = None  # follower detached -> worker probe
        assert gw.cache.get(("j", "t", "0"), 5, job.schedules) is None

    asyncio.run(main())


def test_follower_detach_between_route_and_read_is_retriable():
    """A follower dying between route() and the key lookup degrades
    those keys to retriable errors — never a fatal, never a value."""
    job = _stub_job(published_epoch=3)
    ctrl = type("C", (), {})()
    ctrl.jobs = {"j": job}

    class _Vanishing(_StubReplicas):
        def read_one(self, job_id, table, key_values):
            return None  # mount vanished after route()

    ctrl.replicas = _Vanishing(_StubFollowerView(3, {}))
    gw = StateGateway(ctrl)
    gw._tables["j"] = (job.schedules, {"t": {
        "table": "t", "node_id": 1, "parallelism": 1,
        "key_kinds": ["i"], "routable": True}})

    async def main():
        out = await gw._routed_read(job, "t", [0, 1])
        assert out["outcome"] == "partial"
        for r in out["results"]:
            assert not r["found"] and r["retriable"], out

    asyncio.run(main())


# -- satellite 1: view plans for session windows and updating joins ----------


def _plan_view(**kw):
    base = dict(job_id="j", table="t", node_id=1, task_index=0,
                parallelism=1, key_names=["__key0"], key_kinds=("i",),
                value_names=["rows"], kind="join", live_mode=False)
    base.update(kw)
    return ServeView(**base)


def test_join_view_plan_refuses_residual():
    """_view_plan gates which operators get views: a residual
    (non-equi) join is refused — its output rows are filtered AFTER
    the cross product, so the per-key row-set snapshot would overserve
    (a documented known limit)."""
    from arroyo_tpu.operators.updating_join import UpdatingJoinOperator
    from arroyo_tpu.serve.store import _view_plan
    from arroyo_tpu.types import TaskInfo

    op = UpdatingJoinOperator.__new__(UpdatingJoinOperator)
    op.n_keys = 1
    op.residual = None
    op.out_schema = type("S", (), {"schema": [
        type("F", (), {"name": "l_v", "type": None})(),
        type("F", (), {"name": "r_v", "type": None})(),
    ]})()
    ti = TaskInfo("j", 1, "join", 0, 1)
    plan = _view_plan(op, ti)
    assert plan is not None
    kind, key_names, _kinds, vals = plan
    assert kind == "join" and key_names == ["__key0"]
    assert vals == ["l_v", "r_v"]
    op.residual = lambda b: b
    assert _view_plan(op, ti) is None


def test_join_snapshot_cross_product_outer_padding_and_tombs():
    """The join's serve snapshot: cross product when both sides match,
    null-padding per outer semantics, lone-side inner keys invisible,
    vanished keys tombstoned on the next capture."""
    from arroyo_tpu.operators.updating_join import UpdatingJoinOperator

    op = type("Op", (), {})()
    op.join_type = "left"
    op.left_out = ["l_v"]
    op.right_out = ["r_v"]
    op.state = [
        {(1,): [("L1",), ("L2",)], (2,): [("Lonly",)]},
        {(1,): [("R1",)]},
    ]
    v = _plan_view()
    UpdatingJoinOperator.serve_stage_snapshot(op, v)
    v.seal(1)
    found, val = v.read((1,), 1)
    assert found
    assert val["rows"] == [{"l_v": "L1", "r_v": "R1"},
                           {"l_v": "L2", "r_v": "R1"}]
    # left outer: lone left side null-pads the right
    found, val = v.read((2,), 1)
    assert found and val["rows"] == [{"l_v": "Lonly", "r_v": None}]
    # inner join: a lone side serves nothing; retired keys tombstone
    op.join_type = "inner"
    op.state = [{(1,): [("L1",)]}, {}]
    UpdatingJoinOperator.serve_stage_snapshot(op, v)
    v.seal(2)
    assert v.read((1,), 2) == (False, None)
    assert v.read((2,), 2) == (False, None)


def test_a_closed_sessions_final_supersedes_its_partial():
    """A session is served as a partial from the barrier after it opened;
    the watermark that closes it stages its final in the same barrier
    interval, so no retraction is ever staged: the final is the key's
    newer row, and a key that has opened a new session since serves that
    one as a partial again."""
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
            [("k", pa.int64()), ("cnt", pa.int64())]),
        "gap_nanos": 1000, "key_cols": [0], "backend": "numpy"})
    v = op._serve_view = _plan_view(
        kind="window", key_names=["k"], value_names=["cnt"])
    ctx = types.SimpleNamespace(
        in_schemas=[in_schema], table_manager=None,
        watermarks=types.SimpleNamespace(current_nanos=lambda: None))

    class Sink:
        async def collect(self, b):
            pass

    def feed(keys, ts):
        asyncio.run(op.process_batch(pa.RecordBatch.from_arrays(
            [pa.array(keys, type=pa.int64()),
             pa.array(ts, type=pa.timestamp("ns"))],
            schema=in_schema.schema), ctx, None))

    def barrier(epoch):
        asyncio.run(op.handle_checkpoint(None, ctx, None))
        op.serve_stage_snapshot(v)
        v.seal(epoch)

    feed([7, 7, 8], [0, 10, 5])
    barrier(1)
    assert v.read((7,), 1) == (True, {"cnt": 2, "partial": True})
    assert v.read((8,), 1) == (True, {"cnt": 1, "partial": True})
    # both close; 8 comes back before the barrier, 9 is new
    asyncio.run(op.handle_watermark(types.SimpleNamespace(
        kind=WatermarkKind.EVENT_TIME, timestamp=1500), ctx, Sink()))
    feed([8, 9], [3000, 3000])
    barrier(2)
    assert v.read((7,), 2) == (True, {"cnt": 2})        # the final
    assert v.read((8,), 2) == (True, {"cnt": 1, "partial": True})
    assert v.read((9,), 2) == (True, {"cnt": 1, "partial": True})
    assert v.read((7,), 1) == (True, {"cnt": 2})  # (the view moved on)
    # an unchanged partial is not staged again
    barrier(3)
    assert not v._stage and v.read((9,), 3)[1]["partial"] is True


# -- the mirror: segments through a real chain (ISSUE 25) --------------------


def _hop_batch(rng, keys, stamp):
    import numpy as np
    import pyarrow as pa

    n = len(keys)
    end = pa.array(np.full(n, stamp * 2_000_000), pa.timestamp("us"))
    return pa.RecordBatch.from_pydict({
        "auction": pa.array(np.asarray(keys, dtype=np.int64)),
        "window": pa.StructArray.from_arrays([end, end], ["start", "end"]),
        "count": pa.array(rng.integers(1, 900, n)),
        "_timestamp": end,
    })


class _Worker:
    """One subtask of a viewed hop operator with its `__serve__` table
    and the chain of blobs its captures gave."""

    def __init__(self, task_index, parallelism, chains=()):
        from types import SimpleNamespace

        import pyarrow as pa

        from arroyo_tpu.operators.windows import WindowOperatorBase
        from arroyo_tpu.serve.store import SERVE_TABLE, register_op
        from arroyo_tpu.state.table_config import global_table
        from arroyo_tpu.state.tables import GlobalTable
        from arroyo_tpu.types import TaskInfo

        self.table = GlobalTable(global_table(SERVE_TABLE))
        for chain in chains:  # a restore unions every subtask's chain
            self.table.load_chain(chain)
        self.tm = SimpleNamespace(tables={SERVE_TABLE: self.table})
        self.op = WindowOperatorBase.__new__(WindowOperatorBase)
        self.op.name = "hop"
        self.op._key_names = ["auction"]
        self.op.out_schema = SimpleNamespace(schema=pa.schema([
            ("auction", pa.int64()),
            ("window", pa.struct([("start", pa.timestamp("us")),
                                  ("end", pa.timestamp("us"))])),
            ("count", pa.int64()),
            ("_timestamp", pa.timestamp("us")),
        ]))
        ctx = SimpleNamespace(
            task_info=TaskInfo("j", 3, "hop", task_index, parallelism),
            table_manager=self.tm)
        self.view = register_op(self.op, ctx)  # restore seeding runs here
        self.chain = []

    def capture(self, epoch):
        from arroyo_tpu.serve.store import seal_op
        from arroyo_tpu.state.tables import resolved

        seal_op(self.op, epoch, self.tm)
        blob, is_base = self.table.serialize_delta(epoch)
        if blob is not None:
            blob = resolved(blob)   # as `flush_captured` does (ISSUE 39)
            self.chain = [blob] if is_base else self.chain + [blob]


def _follower_view(chains, epoch):
    """The view `Follower._refresh_views` builds from the union of the
    subtasks' chains at a published epoch."""
    from types import SimpleNamespace

    from arroyo_tpu.replica.follower import Follower, _Mount
    from arroyo_tpu.serve.store import SERVE_TABLE
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.tables import GlobalTable

    table = GlobalTable(global_table(SERVE_TABLE))
    for chain in chains:
        table.load_chain(chain)
    mount = _Mount(None)
    mount.tms[(3, 0)] = SimpleNamespace(tables={SERVE_TABLE: table})
    mount.epoch = epoch
    Follower(0)._refresh_views("j", mount)
    return mount.views["hop"]


@pytest.mark.parametrize("restore_parallelism", [1, 4])
def test_mirror_round_trip_follower_and_restore(restore_parallelism):
    """Seal on two worker subtasks, capture through real GlobalTable
    chains, then a follower (`_refresh_views`) and a restore at another
    parallelism (`register_op`, the owner filter) read back exactly
    what the workers serve at that epoch; the restored subtasks go on,
    and a second follower over THEIR chains still agrees."""
    import numpy as np

    from arroyo_tpu.serve.store import owner_subtask, stage_batch

    rng = np.random.default_rng(25)
    keys = list(range(5_000, 5_400))

    def run_epochs(workers, epochs, stamp0):
        p = len(workers)
        for epoch in epochs:
            for close in range(3):
                hit = rng.choice(keys, 150, replace=False)
                for w in workers:
                    mine = [k for k in hit if owner_subtask(
                        (int(k),), ("i",), p) == w.view.task_index]
                    if mine:
                        stage_batch(w.view, _hop_batch(
                            rng, mine, stamp0 + epoch * 3 + close))
            for w in workers:
                w.capture(epoch)

    def served(workers, epoch):
        p = len(workers)
        return {k: workers[owner_subtask((k,), ("i",), p)].view.read(
            (k,), epoch) for k in keys + [1, 9_999]}

    old = [_Worker(i, 2) for i in range(2)]
    run_epochs(old, (1, 2, 3), 0)
    want = served(old, 3)
    assert sum(f for f, _ in want.values()) > 300
    # one entry per sealed epoch beside the meta record, not one per key
    assert all(len(w.table.data) == 4 for w in old)
    chains = [w.chain for w in old]
    fview = _follower_view(chains, 3)
    assert {k: fview.read((k,), 3) for k in want} == want

    new = [_Worker(i, restore_parallelism, chains)
           for i in range(restore_parallelism)]
    assert served(new, 3) == want
    for w in new:  # each holds what it owns, and only that
        owned = sum(f and owner_subtask((k,), ("i",), restore_parallelism)
                    == w.view.task_index for k, (f, _) in want.items())
        assert w.view.stats()["keys"] == owned
    run_epochs(new, (4, 5), 100)
    want5 = served(new, 5)
    assert want5 != want
    fview = _follower_view([w.chain for w in new], 5)
    assert {k: fview.read((k,), 5) for k in want5} == want5
    # a follower that had mounted the old chains and tails the new ones
    # applies their tombstones to what the restore replaced
    fview = _follower_view(chains + [w.chain for w in new], 5)
    assert {k: fview.read((k,), 5) for k in want5} == want5


def test_mirror_restores_a_chain_in_the_per_key_format():
    """`tests/data/serve_chain_pr24.msgpack` holds three blobs that PR
    24's code wrote (one `__serve__` entry per key: a hop view's finals
    through `stage_batch`, two retractions in epoch 3) and the reads
    PR 24's view answered at epoch 3. A follower and a restore rebuild
    the same reads from it; the restored worker's first sealed batch
    folds the entries into a segment, and wins over them."""
    import os

    import msgpack
    import numpy as np

    rec = msgpack.unpackb(
        open(os.path.join(os.path.dirname(__file__), "data",
                          "serve_chain_pr24.msgpack"), "rb").read(),
        raw=False, strict_map_key=False)
    want = {(int(k),): v for k, v in rec["reads"].items()}
    assert sum(v is not None for v in want.values()) > 100

    def reads(view, epoch):
        return {k: (view.read(k, epoch)[1] if view.read(k, epoch)[0]
                    else None) for k in want}

    assert reads(_follower_view([rec["blobs"]], 3), 3) == want
    w = _Worker(0, 1, [rec["blobs"]])
    assert reads(w.view, 3) == want
    # the entries per key stay as they are until the view is handed its
    # first batch; the capture that mirrors it folds them into the
    # oldest segment and deletes them through the table's tombstones
    from arroyo_tpu.serve.store import stage_batch
    assert sum(isinstance(k, tuple) for k, _ in w.table.items()) == 115
    stage_batch(w.view, _hop_batch(np.random.default_rng(1), [1001, 1003],
                                   77))
    w.capture(4)
    assert sorted(dict(w.table.items())) == [
        "__serve_meta__", "__serve_seg__/0/0/0", "__serve_seg__/0/4/0"]
    want4 = dict(reads(w.view, 4))
    assert want4[(1001,)]["window"]["end"] == 154_000_000_000
    assert {k: v for k, v in want4.items() if k not in ((1001,), (1003,))} \
        == {k: v for k, v in want.items() if k not in ((1001,), (1003,))}
    # a follower still holding the old blobs tails the new base
    fview = _follower_view([rec["blobs"], w.chain], 4)
    assert reads(fview, 4) == want4
    assert fview.describe()["table"] == "hop"


@pytest.mark.parametrize("first", ["rows", "batch"])
def test_mirror_orders_rows_batches_and_retractions(first):
    """A session-like view: partials and retractions staged a row at a
    time, finals as batches, in one sequence. Whatever shape came first
    (entries per key until the first batch, segments from then on), a
    follower over the chain answers as the worker does after every
    epoch, past the merge of the oldest segments (a retraction merged
    into the oldest segment must not bring back what it retracted)."""
    import numpy as np

    from arroyo_tpu.serve.store import stage_batch

    rng = np.random.default_rng(3 + (first == "rows"))
    w = _Worker(0, 1)
    keys = list(range(40))
    blobs = []
    for epoch in range(1, 61):
        shapes = ["row", "tomb", "batch", "partial"]
        if epoch <= 3:  # the first epochs hold one shape only
            shapes = ["row", "tomb"] if first == "rows" else ["batch"]
        for _ in range(int(rng.integers(1, 6))):
            shape = rng.choice(shapes)
            hit = [int(k) for k in rng.choice(keys, 5, replace=False)]
            if shape == "row":
                for k in hit:
                    w.view.stage((k,), {"count": epoch, "partial": True})
            elif shape == "tomb":
                for k in hit:
                    w.view.stage_tomb((k,))
            else:
                stage_batch(w.view, _hop_batch(rng, hit, epoch),
                            partial=(shape == "partial"))
        w.capture(epoch)
        blobs.append(w.chain[-1])
        fview = _follower_view([blobs], epoch)
        for k in keys:
            assert fview.read((k,), epoch) == w.view.read((k,), epoch), (
                epoch, k)
        assert fview.stats()["keys"] == w.view.stats()["keys"]
    assert not any(isinstance(k, tuple) for k, _ in w.table.items())


def test_mirror_segment_merging_deletes_what_it_merged():
    """200 epochs of recurring keys: the worker's `__serve__` table and
    a table that replays its chain both stay at a bounded number of
    entries (the oldest segments merge and the merged-away ones leave
    through the table's tombstones), and the reads stay exact."""
    import numpy as np

    from arroyo_tpu.serve.store import (
        _MIRROR_SEGMENTS,
        SERVE_TABLE,
        seal_op,
        stage_batch,
    )
    from arroyo_tpu.state.table_config import global_table
    from arroyo_tpu.state.tables import GlobalTable, resolved

    rng = np.random.default_rng(7)
    w = _Worker(0, 1)
    last = {}
    blobs = []
    for epoch in range(1, 201):
        hit = rng.choice(np.arange(300), 40, replace=False)
        b = _hop_batch(rng, hit, epoch)
        stage_batch(w.view, b)
        for k, c in zip(hit.tolist(), b.column(2).to_pylist()):
            last[k] = c
        seal_op(w.op, epoch, w.tm)
        # every delta of the chain, merged-away entries' tombstones too
        blobs.append(resolved(w.table.serialize_delta(epoch)[0]))
        assert len(w.table.data) <= _MIRROR_SEGMENTS + 1, epoch
    replay = GlobalTable(global_table(SERVE_TABLE))
    replay.load_chain(blobs)
    assert len(dict(replay.items())) <= _MIRROR_SEGMENTS + 1
    assert sorted(dict(replay.items())) == sorted(dict(w.table.items()))
    fview = _follower_view([blobs], 200)
    for k in range(300):
        want = (True, last[k]) if k in last else (False, None)
        got = w.view.read((k,), 200)
        assert (got[0], got[1] and got[1]["count"]) == want
        assert fview.read((k,), 200) == got
    assert fview.stats()["keys"] == w.view.stats()["keys"] == len(last)


# -- end to end: follower-first serving, kill, reattach ----------------------


def test_e2e_follower_serves_with_zero_worker_rpcs(tmp_path):
    """The acceptance path: a durable job's reads route to the
    follower mount (source=follower) with staleness <=
    replica.max_lag_epochs and ZERO further worker QueryState RPCs;
    killing the follower mid-serve fails over worker-ward (reads keep
    answering, nothing fatal, nothing wrong) and the mount reattaches
    from latest.json; stop detaches the mount and job-metric GC drops
    the arroyo_replica_* series."""
    from arroyo_tpu.metrics import (
        REGISTRY,
        REPLICA_LOOKUPS,
        SERVE_WORKER_RPCS,
    )

    wd = str(tmp_path)

    async def _wait_follower(c, jid, keys, timeout=40.0):
        deadline = time.monotonic() + timeout
        while True:
            out = await c.serve.read(jid, "tumbling_window", keys)
            if (out.get("source") == "follower"
                    and all(r.get("found") for r in out["results"])):
                return out
            assert time.monotonic() < deadline, (
                f"reads never went follower-routed: {out}, "
                f"replica={c.replicas.status()}"
            )
            await asyncio.sleep(0.3)

    async def main():
        with update(
            pipeline={"checkpointing": {
                "interval": 0.5, "storage_url": f"{wd}/ck"}},
            replica={"followers": 1, "reattach_backoff": 1.0},
        ):
            sched = EmbeddedScheduler()
            c = await ControllerServer(sched).start()
            job = await c.submit_job(
                "fl", sql=_serve_sql(wd), n_workers=2, parallelism=2,
                storage_url=f"{wd}/ck/fl",
            )
            try:
                await c.wait_for_state("fl", JobState.RUNNING,
                                       timeout=30)
                await _wait_published(job, 1)
                await _wait_found(c, "fl", "tumbling_window", 0)
                keys = list(range(8))
                out = await _wait_follower(c, "fl", keys)
                # response-carried staleness, bounded at one interval
                lag_cap = int(config().replica.max_lag_epochs)
                assert out["staleness"] <= lag_cap, out
                assert out["served_epoch"] <= job.published_epoch
                # zero worker QueryState RPCs on follower-routed reads:
                # epochs advance every 0.5 s, so these reads MISS the
                # cache and still never leave the controller (a
                # transiently lagging mount may route a read worker-
                # ward — those legs are allowed RPCs; follower-routed
                # ones get none)
                look0 = REPLICA_LOOKUPS.labels(job="fl").get()
                follower_reads = 0
                for _ in range(40):
                    before = SERVE_WORKER_RPCS.labels(job="fl").get()
                    out = await c.serve.read("fl", "tumbling_window",
                                             keys)
                    after = SERVE_WORKER_RPCS.labels(job="fl").get()
                    if out.get("source") == "follower":
                        assert after == before, out
                        assert out["staleness"] <= lag_cap, out
                        follower_reads += 1
                        if follower_reads >= 5:
                            break
                    await asyncio.sleep(0.3)
                assert follower_reads >= 5, c.replicas.status()
                assert REPLICA_LOOKUPS.labels(job="fl").get() > look0
                # REST surfaces the replica lag on the table listing
                lag = c.replicas.lag_epochs(job)
                assert lag is not None and lag <= lag_cap
                # follower death: reads fail over worker-ward with no
                # fatal and no wrong value, then the mount reattaches
                c.replicas.kill(0)
                out = await c.serve.read("fl", "tumbling_window", keys)
                assert out["source"] == "worker", out
                assert out["staleness"] == 0
                for r in out["results"]:
                    assert r.get("found") or r.get("retriable"), out
                assert c.replicas.kills == 1
                out = await _wait_follower(c, "fl", keys)
                assert out["source"] == "follower"
                # detach on stop: mount gone, replica series GC'd with
                # the job's metrics
                await c.stop_job("fl", "immediate")
                await c.wait_for_state(
                    "fl", JobState.STOPPED, JobState.FAILED,
                    JobState.FINISHED, timeout=30,
                )
                assert all("fl" not in f.mounts
                           for f in c.replicas.followers)
                assert "fl" not in c.replicas._assign
                REGISTRY.drop_job("fl")  # TTL path shortcut for the test
                text = REGISTRY.expose()
                assert 'arroyo_replica_lag_epochs{job="fl"}' not in text
            finally:
                if "fl" in c.jobs and not c.jobs["fl"].state.is_terminal():
                    await c.stop_job("fl", "immediate")
                    await c.wait_for_state(
                        "fl", JobState.STOPPED, JobState.FAILED,
                        JobState.FINISHED, timeout=30,
                    )
                await c.stop()

    asyncio.run(main())


def test_e2e_session_partials_served(tmp_path):
    """Satellite 1 end to end: a session-window job with sessions held
    open by a continuous impulse serves per-key partials (`partial:
    true`, count still growing) at the published epoch — worker-ward
    and, once the mount catches up, follower-routed off the mirrored
    checkpoint stream."""
    wd = str(tmp_path)
    sql = f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '20000',
      message_count = '2000000', start_time = '0',
      realtime = 'true', replay = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{wd}/out.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % 4 as k,
             session(interval '30 second') as w, count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """

    async def main():
        with update(
            pipeline={"checkpointing": {
                "interval": 0.5, "storage_url": f"{wd}/ck"}},
            replica={"followers": 1, "reattach_backoff": 1.0},
        ):
            c = await ControllerServer(EmbeddedScheduler()).start()
            job = await c.submit_job(
                "se", sql=sql, n_workers=2, parallelism=2,
                storage_url=f"{wd}/ck/se",
            )
            try:
                await c.wait_for_state("se", JobState.RUNNING,
                                       timeout=30)
                await _wait_published(job, 1)
                tables = await c.serve.tables("se")
                name = next(t for t in tables
                            if tables[t]["kind"] == "window")
                out = await _wait_found(c, "se", name, 0)
                r = out["results"][0]
                # the 30 s gap is far longer than the test: the session
                # is open, so this MUST be a partial with a live count
                assert r["value"].get("partial") is True, out
                num_fields = [f for f, v in r["value"].items()
                              if f != "partial"
                              and isinstance(v, (int, float))]
                assert num_fields, r
                # and the partial keeps growing across epochs (the
                # session count rises; start/end may shift too — any
                # numeric field strictly increasing proves re-staging)
                deadline = time.monotonic() + 30
                while True:
                    out2 = await _wait_found(c, "se", name, 0)
                    v2 = out2["results"][0]["value"]
                    if any(v2.get(f, 0) > r["value"][f]
                           for f in num_fields):
                        break
                    assert time.monotonic() < deadline, (r, out2)
                    await asyncio.sleep(0.5)
                assert v2.get("partial") is True, out2
                # follower-routed partials off the mirrored stream
                deadline = time.monotonic() + 40
                while True:
                    out3 = await c.serve.read("se", name, [0, 1, 2, 3])
                    if (out3.get("source") == "follower"
                            and all(x.get("found")
                                    for x in out3["results"])):
                        break
                    assert time.monotonic() < deadline, (
                        out3, c.replicas.status())
                    await asyncio.sleep(0.3)
                for x in out3["results"]:
                    assert x["value"].get("partial") is True, out3
            finally:
                await c.stop_job("se", "immediate")
                await c.wait_for_state(
                    "se", JobState.STOPPED, JobState.FAILED,
                    JobState.FINISHED, timeout=30,
                )
                await c.stop()

    asyncio.run(main())
