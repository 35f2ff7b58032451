"""State at scale (ISSUE 8): incremental global-table blob chains with
rebase + tombstones, multi-inflight off-barrier checkpoint flushes, and
the larger-than-RAM time-key spill tier.

Acceptance pins:
  * restore from a base+delta chain (tombstoned keys, post-rebase
    manifests, stale cross-subtask replicas) is byte-identical to a
    full-snapshot restore (property test);
  * multi-inflight flushes publish manifests strictly in epoch order and
    an in-flight flush failure routes TaskFailedResp with recovery from
    the last *published* epoch;
  * a session-window job round-trips byte-identically through the
    per-key incremental path, with delta bytes << full-snapshot bytes;
  * the spill tier bounds RAM at state.memory_budget_bytes while holding
    ~10x the budget, with identical drained output.
"""

import asyncio
import glob
import json
import os
import random

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu import chaos
from arroyo_tpu.chaos.plan import FaultPlan
from arroyo_tpu.config import update
from arroyo_tpu.engine import Engine
from arroyo_tpu.sql import plan_query
from arroyo_tpu.state.backend import StateBackend
from arroyo_tpu.state.table_config import global_table, time_key_table
from arroyo_tpu.state.tables import GlobalTable, TimeKeyTable

MS = 1_000_000


# -- incremental global tables: chain == full snapshot (property) ------------


def _apply_ops(table: GlobalTable, ops):
    for op, k, v in ops:
        if op == "put":
            table.put(k, v)
        else:
            table.delete(k)


def test_global_chain_restore_equals_full_snapshot_property():
    """Random put/delete streams across epochs, chained with random
    rebase points: replaying the chain must reconstruct exactly the
    final map — including tombstoned keys and post-rebase manifests."""
    rng = random.Random(7)
    for trial in range(20):
        src = GlobalTable(global_table("g"))
        expect = {}
        chain = []
        for epoch in range(1, rng.randint(2, 9)):
            ops = []
            for _ in range(rng.randint(0, 12)):
                k = rng.randint(0, 15)
                if rng.random() < 0.25:
                    ops.append(("del", k, None))
                    expect.pop(k, None)
                else:
                    v = rng.randint(0, 999)
                    ops.append(("put", k, v))
                    expect[k] = v
            _apply_ops(src, ops)
            force = rng.random() < 0.3
            blob, is_base = src.serialize_delta(epoch, force_base=force)
            if blob is None:
                continue
            if is_base:
                chain = [blob]
            else:
                chain.append(blob)
        dst = GlobalTable(global_table("g"))
        dst.load_chain(chain)
        got = dict(dst.items())
        assert got == expect, f"trial {trial}: {got} != {expect}"


def test_global_chain_stale_replica_loses_by_stamp():
    """Replication re-persists every subtask's union view; the restore
    merge must prefer the owner's fresher entry over a peer's stale copy
    REGARDLESS of chain load order (pre-stamp code let dict order win)."""
    owner = GlobalTable(global_table("g"))
    owner.put("k", "old")
    b1, _ = owner.serialize_delta(1)
    # the peer restored the owner's epoch-1 state (stamp rides along)
    peer = GlobalTable(global_table("g"))
    peer.load_chain([b1])
    peer.put("mine", 1)
    peer_blob, _ = peer.serialize_delta(5)
    # the owner then advanced k
    owner.put("k", "new")
    b2, _ = owner.serialize_delta(3)
    for order in ([[b1, b2], [peer_blob]], [[peer_blob], [b1, b2]]):
        t = GlobalTable(global_table("g"))
        for sub_chain in order:
            t.load_chain(sub_chain)
        merged = dict(t.items())
        assert merged["k"] == "new", f"stale replica won under {order}"
        assert merged["mine"] == 1
    # tombstones beat stale entries the same way: owner deletes k at 6
    owner.delete("k")
    b3, _ = owner.serialize_delta(6)
    t = GlobalTable(global_table("g"))
    t.load_chain([peer_blob])       # stale k@1 replica
    t.load_chain([b1, b2, b3])      # owner chain ends in tombstone@6
    assert "k" not in dict(t.items())


def test_global_capture_is_o_dirty():
    """After the base, an epoch's blob carries only the dirty entries —
    bytes scale with the delta, not total state."""
    t = GlobalTable(global_table("g"))
    for i in range(2000):
        t.put(i, "x" * 20)
    base, is_base = t.serialize_delta(1)
    assert is_base and len(base) > 20_000
    t.put(1, "y")
    delta, is_base = t.serialize_delta(2)
    assert not is_base and len(delta) < 200, len(delta)
    # untouched epoch: no blob at all
    blob, _ = t.serialize_delta(3)
    assert blob is None


@pytest.mark.parametrize("base", [True, False])
def test_the_flush_threads_packing_is_packb_byte_for_byte(base):
    """ISSUE 39: a blob whose entries were deferred is framed by msgpack
    and its large binary values copied in outside the GIL
    (`_packb_beside_the_loop`): the bytes are `msgpack.packb`'s, whatever
    the keys (strings, tuples), the values (bytes and bytes-like views on
    either side of `_BIG_VALUE`, dicts, None) and the tombstones."""
    import os

    import msgpack

    from arroyo_tpu.state.tables import _BIG_VALUE, _packb_beside_the_loop

    sizes = [0, 1, 255, 256, _BIG_VALUE - 1, _BIG_VALUE, 3 * _BIG_VALUE + 7]
    for n in (0, 1, 15, 16, 70):   # fixarray, array16
        entries = []
        for i in range(n):
            value = os.urandom(sizes[i % len(sizes)])
            if i % 5 == 1:
                value = memoryview(value)
            elif i % 5 == 2:
                value = {"table": "t", "keys": [1, 2, {"x": None}], "f": 0.5}
            entries.append([("seg", i) if i % 2 else f"seg/{i}", value, 7 * i])
        tombs = [[f"gone/{i}", i] for i in range(n // 3)]
        want = msgpack.packb(
            {"v": 2, "b": base,
             "e": [[k, bytes(v) if isinstance(v, memoryview) else v, st]
                   for k, v, st in entries], "t": tombs},
            use_bin_type=True)
        got = _packb_beside_the_loop(base, entries, tombs)
        assert bytes(got) == want and len(got) == len(want)
        assert msgpack.unpackb(got, raw=False)["t"] == tombs


def test_rebase_policy_truncates_chain(tmp_storage):
    """TableManager rebases once the chain carries state.rebase_epochs
    deltas (or delta bytes exceed the factor), and the manifest's chain
    shrinks back to one base; restore replays correctly before and
    after the rebase boundary."""
    from arroyo_tpu.operators.control import CheckpointCompletedResp
    from arroyo_tpu.state.table_manager import TableManager
    from arroyo_tpu.types import TaskInfo

    url = f"{tmp_storage}/rb"

    async def run():
        b = StateBackend(url, "rb").initialize()
        tm = TableManager(b, TaskInfo("rb", 5, "op", 0, 1), 0)
        await tm.open({"g": global_table("g")})
        table = await tm.get_table("g")
        chain_lens = []
        for epoch in range(1, 10):
            table.put(f"k{epoch}", epoch)
            meta = await tm.checkpoint(epoch, None)
            chain_lens.append(len(meta["g"]["chain"]))
            resp = CheckpointCompletedResp(
                "5-0", 5, 0, epoch, subtask_metadata={"op0": meta},
                watermark=None,
            )
            b.publish_checkpoint(epoch, {"5-0": resp})
            b.retire_unreferenced()
        return chain_lens

    with update(state={"rebase_epochs": 3, "rebase_bytes_factor": 100.0}):
        chain_lens = asyncio.run(run())
    # base, +1, +2, +3 deltas -> rebase to 1, ...
    assert chain_lens[0] == 1
    assert max(chain_lens) == 4 and chain_lens.count(1) >= 2, chain_lens

    async def restore():
        b2 = StateBackend(url, "rb").initialize()
        tm2 = TableManager(b2, TaskInfo("rb", 5, "op", 0, 1), 0)
        await tm2.open({"g": global_table("g")})
        t2 = await tm2.get_table("g")
        return dict(t2.items())

    got = asyncio.run(restore())
    assert got == {f"k{e}": e for e in range(1, 10)}


# -- spill tier ---------------------------------------------------------------


def _ts_batch(n, ts_base, key_base=0):
    return pa.RecordBatch.from_arrays(
        [pa.array(np.arange(n) + key_base),
         pa.array(np.full(n, ts_base, dtype=np.int64))],
        names=["v", "_timestamp"],
    )


def test_timekey_spill_bounds_memory_and_drains_identically():
    """Hold ~10x the budget: in-memory bytes stay <= budget, spilled rows
    come back byte-identical when the watermark drains them."""
    budget = 60_000
    with update(state={"memory_budget_bytes": budget}):
        spilling = TimeKeyTable(time_key_table("x"))
        plain = TimeKeyTable(time_key_table("x"))
    for i in range(60):
        spilling.insert(_ts_batch(1000, i * 10, i * 1000))
        plain.insert(_ts_batch(1000, i * 10, i * 1000))
    mem, spilled, rows, batches = spilling.entry_stats()
    assert rows == 60_000 and batches == 60
    assert mem <= budget, f"budget exceeded: {mem}"
    assert spilled > budget * 5, "held ~10x the budget without spilling"

    def drain(t):
        return [
            (ts, b.column(0).to_pylist())
            for ts, b in t.take_bins_upto(10**9)
        ]

    assert drain(spilling) == drain(plain)
    assert spilling.entry_stats()[:3] == (0, 0, 0)


def test_timekey_spill_restore_roundtrip():
    """load_batches beyond the budget spills like live inserts; the
    restored view is identical."""
    src = [_ts_batch(500, i * 7) for i in range(40)]
    with update(state={"memory_budget_bytes": 20_000}):
        t = TimeKeyTable(time_key_table("x"))
    t.load_batches(src)
    assert t.entry_stats()[0] <= 20_000
    got = [b.column(1).to_pylist() for b in t.all_batches()]
    want = [b.column(1).to_pylist() for b in src]
    assert got == want
    t.clear_batches()  # releases scratch files


def test_expire_row_level_compaction():
    """A batch pinned by one live row no longer keeps its dead rows in
    RAM: expire() compacts row-level past the configured fraction."""
    with update(state={"expire_compact_fraction": 0.5}):
        t = TimeKeyTable(time_key_table("y", retention_nanos=100))
        mixed = pa.RecordBatch.from_arrays(
            [pa.array(np.arange(100)),
             pa.array(np.r_[np.full(90, 0), np.full(10, 1000)])],
            names=["v", "_timestamp"],
        )
        t.insert(mixed)
        before = t.entry_stats()[0]
        t.expire(600)  # cutoff 500: 90% dead, max_ts live
        assert sum(b.num_rows for b in t.all_batches()) == 10
        assert t.entry_stats()[0] < before
        # below the fraction the batch survives whole (no copy churn)
        t2 = TimeKeyTable(time_key_table("y", retention_nanos=100))
        t2.insert(mixed)
        t2.expire(100)  # cutoff 0: nothing dead
        assert sum(b.num_rows for b in t2.all_batches()) == 100


# -- multi-inflight flushes ---------------------------------------------------


def _agg_sql(src, sink, throttle=None):
    th = f"throttle_per_sec = '{throttle}'," if throttle else ""
    return f"""
    CREATE TABLE src (timestamp TIMESTAMP, k BIGINT NOT NULL)
    WITH (connector = 'single_file', path = '{src}', format = 'json',
          type = 'source', {th} event_time_field = 'timestamp');
    CREATE TABLE out (k BIGINT NOT NULL, c BIGINT NOT NULL)
    WITH (connector = 'single_file', path = '{sink}', format = 'json',
          type = 'sink');
    INSERT INTO out SELECT k, count(*) as c FROM src
    GROUP BY 1, tumble(interval '1 hour');
    """


def _write_rows(path, n=3000, keys=64):
    with open(path, "w") as f:
        for i in range(n):
            mins, secs = (i // 60) % 60, i % 60
            f.write(json.dumps({
                "k": i % keys,
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}.000Z",
            }) + "\n")


def test_multi_inflight_flushes_publish_in_epoch_order(tmp_path):
    """Three barriers injected back-to-back under slow storage: flushes
    overlap (high-water mark > 1), completion reports stay epoch-ordered
    per subtask, and the manifests publish 1, 2, 3."""
    src = str(tmp_path / "in.json")
    _write_rows(src)
    sink = str(tmp_path / "out.json")
    storage = str(tmp_path / "ck")
    published = []

    plan = FaultPlan(seed=1)
    plan.add("storage.latency", at_hits=tuple(range(1, 200)),
             match={"key": "/data/"}, params={"delay": 0.05},
             max_fires=200)

    async def run():
        plan_q = plan_query(_agg_sql(src, sink, throttle=6000),
                            parallelism=1)
        eng = Engine(plan_q.graph, job_id="mi", storage_url=storage).start()
        await asyncio.sleep(0.15)
        epochs = [await eng.checkpoint() for _ in range(3)]
        for e in epochs:
            await eng.wait_checkpoint(e)
            published.append(
                eng.backend.latest_manifest()["epoch"]
            )
        hwm = max(
            s.runner._flush_hwm for s in eng.program.subtasks
        )
        await eng.checkpoint_and_wait(then_stop=True)
        await eng.join(60)
        return hwm

    chaos.install(plan)
    try:
        with update(state={"max_inflight_flushes": 3}):
            hwm = asyncio.run(run())
    finally:
        chaos.clear()
    assert published == [1, 2, 3], published
    assert hwm >= 2, f"flushes never overlapped (hwm={hwm})"
    # per-subtask reports arrived in epoch order -> every manifest's
    # chain references exist
    b = StateBackend(storage, "mi").initialize()
    manifest = b.latest_manifest()
    for task in manifest["tasks"].values():
        for tables in task["op_tables"].values():
            for meta in tables.values():
                for f in meta.get("chain", []):
                    assert b.read_blob(f["path"]) is not None, f["path"]


def test_inflight_flush_failure_recovers_from_published_epoch(tmp_path):
    """An injected storage failure inside a checkpoint flush routes
    TaskFailedResp (not a silent hang); the embedded cluster recovers
    from the last *published* epoch and the final output is identical
    to a fault-free run — exactly-once across a flush-path fault."""
    from arroyo_tpu.chaos.drill import _run_embedded

    src = str(tmp_path / "in.json")
    _write_rows(src, n=2500)
    clean, faulted = str(tmp_path / "clean.json"), str(tmp_path / "f.json")

    _run_embedded(
        _agg_sql(src, clean), "fl-clean", None, 2, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=90.0,
    )
    want = sorted(line.strip() for line in open(clean) if line.strip())
    assert want

    plan = FaultPlan(seed=3)
    # fail a checkpoint DATA file write (the async flush path), twice
    plan.add("storage.write_fail", at_hits=(2, 3), match={"key": "/data/"})
    chaos.install(plan)
    try:
        with update(state={"max_inflight_flushes": 2}):
            restarts = _run_embedded(
                _agg_sql(src, faulted, throttle=2500), "fl-faulted",
                str(tmp_path / "ck"), 2, 1, max_restarts=8,
                heartbeat_interval=0.1, heartbeat_timeout=2.0,
                checkpoint_interval=0.15, timeout=120.0,
            )
    finally:
        chaos.clear()
    assert not plan.unfired(), [s.describe() for s in plan.unfired()]
    assert restarts >= 1, "flush failure never surfaced"
    got = sorted(line.strip() for line in open(faulted) if line.strip())
    assert got == want


def test_capture_flush_overlap_exactly_once_under_storage_chaos(tmp_path):
    """The tier-1 storage faults (lost CAS race + injected latency) with
    multi-inflight flushes enabled: capture->flush overlap preserves
    byte-identical exactly-once output."""
    from arroyo_tpu.chaos.drill import _run_embedded

    src = str(tmp_path / "in.json")
    _write_rows(src, n=2500)
    clean, faulted = str(tmp_path / "clean.json"), str(tmp_path / "f.json")
    _run_embedded(
        _agg_sql(src, clean), "ov-clean", None, 2, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=90.0,
    )
    want = sorted(line.strip() for line in open(clean) if line.strip())

    plan = FaultPlan(seed=11)
    plan.add("storage.cas_conflict", at_hits=(1,),
             match={"key": "checkpoint-manifest"})
    plan.add("storage.latency", at_hits=(2, 5, 9),
             match={"key": "/data/"}, params={"delay": 0.2})
    chaos.install(plan)
    try:
        with update(state={"max_inflight_flushes": 3}):
            _run_embedded(
                _agg_sql(src, faulted, throttle=2500), "ov-faulted",
                str(tmp_path / "ck"), 2, 1, max_restarts=8,
                heartbeat_interval=0.1, heartbeat_timeout=2.0,
                checkpoint_interval=0.15, timeout=120.0,
            )
    finally:
        chaos.clear()
    assert not plan.unfired(), [s.describe() for s in plan.unfired()]
    got = sorted(line.strip() for line in open(faulted) if line.strip())
    assert got == want


# -- session windows: per-key incremental global state ------------------------


def _session_sql(src, sink, throttled):
    th = "throttle_per_sec = '8000'," if throttled else ""
    return f"""
    CREATE TABLE src (timestamp TIMESTAMP, k BIGINT NOT NULL)
    WITH (connector='single_file', path='{src}', format='json',
          type='source', {th} event_time_field='timestamp');
    CREATE TABLE out (k BIGINT NOT NULL, c BIGINT NOT NULL)
    WITH (connector='single_file', path='{sink}', format='json',
          type='sink');
    INSERT INTO out SELECT k, count(*) as c FROM src
    GROUP BY k, session(interval '30 second');
    """


def test_session_incremental_restore_identical(tmp_path):
    """Session state checkpoints per dirty key (base + deltas +
    tombstones for closed sessions); checkpoint -> stop -> restore ->
    finish equals an uninterrupted run, and no epoch after the base
    rewrites the whole session map."""
    src = str(tmp_path / "in.json")
    with open(src, "w") as f:
        for i in range(3600):
            mins, secs = (i // 60) % 60, i % 60
            f.write(json.dumps({
                "k": i % 200,
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}.000Z",
            }) + "\n")

    full = str(tmp_path / "full.json")

    async def run_full():
        eng = Engine(plan_query(_session_sql(src, full, False),
                                parallelism=1).graph).start()
        await eng.join(120)

    asyncio.run(run_full())

    rest = str(tmp_path / "rest.json")
    storage = str(tmp_path / "ck")

    async def p1():
        eng = Engine(plan_query(_session_sql(src, rest, True),
                                parallelism=1).graph,
                     job_id="s", storage_url=storage).start()
        for _ in range(3):
            await asyncio.sleep(0.1)
            await eng.checkpoint_and_wait()
        await eng.checkpoint_and_wait(then_stop=True)
        await eng.join(120)

    asyncio.run(p1())

    async def p2():
        eng = Engine(plan_query(_session_sql(src, rest, False),
                                parallelism=1).graph,
                     job_id="s", storage_url=storage).start()
        # state-size observability: the sess table's scrape-time gauges
        # are live while the job runs
        await asyncio.sleep(0.1)
        from arroyo_tpu.metrics import REGISTRY

        snap = REGISTRY.snapshot()
        sess_rows = [
            v for labels, v in snap.get("arroyo_state_rows", [])
            if labels.get("table") == "sess" and labels.get("job") == "s"
        ]
        assert sess_rows, "arroyo_state_rows gauge missing for sess"
        assert any(
            labels.get("table") == "sess"
            for labels, _v in snap.get("arroyo_state_delta_chain_len", [])
        ), "delta-chain gauge missing"
        await eng.join(120)

    asyncio.run(p2())

    read = lambda p: sorted(  # noqa: E731
        json.dumps(json.loads(x), sort_keys=True)
        for x in open(p) if x.strip()
    )
    assert read(rest) == read(full)
    # incremental evidence: several sess blobs exist and no post-base
    # blob rewrites the whole map
    blobs = sorted(glob.glob(
        os.path.join(storage, "**", "*-sess-*.bin"), recursive=True
    ))
    assert len(blobs) >= 2, blobs
    sizes = [os.path.getsize(b) for b in blobs]
    assert min(sizes) < max(sizes), sizes


def test_session_restore_at_higher_parallelism(tmp_path):
    """Per-key session entries re-partition on rescale: each new subtask
    keeps only its key range (retain prunes the rest) and the union of
    the final outputs is exactly-once."""
    src = str(tmp_path / "in.json")
    with open(src, "w") as f:
        for i in range(2400):
            mins, secs = (i // 60) % 60, i % 60
            f.write(json.dumps({
                "k": i % 100,
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}.000Z",
            }) + "\n")

    full = str(tmp_path / "full.json")

    async def run_full():
        eng = Engine(plan_query(_session_sql(src, full, False),
                                parallelism=1).graph).start()
        await eng.join(120)

    asyncio.run(run_full())

    rest = str(tmp_path / "rest.json")
    storage = str(tmp_path / "ck")

    async def p1():
        eng = Engine(plan_query(_session_sql(src, rest, True),
                                parallelism=1).graph,
                     job_id="sp", storage_url=storage).start()
        await asyncio.sleep(0.15)
        await eng.checkpoint_and_wait()
        await eng.checkpoint_and_wait(then_stop=True)
        await eng.join(120)

    asyncio.run(p1())

    async def p2():
        eng = Engine(plan_query(_session_sql(src, rest, False),
                                parallelism=2).graph,
                     job_id="sp", storage_url=storage).start()
        await eng.join(120)

    asyncio.run(p2())

    read = lambda p: sorted(  # noqa: E731
        json.dumps(json.loads(x), sort_keys=True)
        for x in open(p) if x.strip()
    )
    assert read(rest) == read(full)
