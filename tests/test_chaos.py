"""Chaos subsystem: deterministic fault plans, injector seams, registry
coverage, and the fast exactly-once smoke drill (the full acceptance
drill — worker SIGKILL across 3 goldens — is in test_chaos_drill.py,
marked slow)."""

import asyncio
import json
import os
import re

import pytest

from arroyo_tpu import chaos
from arroyo_tpu.chaos import FAULT_POINTS, FaultPlan, UnknownFaultPoint

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(os.path.dirname(HERE), "arroyo_tpu")


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    chaos.clear()
    yield
    chaos.clear()


# -- plan determinism --------------------------------------------------------


def test_plan_seeded_is_deterministic():
    points = ["network.drop_connection", "worker.kill", "storage.cas_conflict"]
    a = FaultPlan.seeded(77, points)
    b = FaultPlan.seeded(77, points)
    assert a.to_json() == b.to_json()
    assert FaultPlan.seeded(78, points).to_json() != a.to_json()


def test_plan_fires_at_hit_and_only_max_fires():
    plan = FaultPlan(1).add("storage.write_fail", at_hits=(3,))
    chaos.install(plan)
    fires = [bool(chaos.fire("storage.write_fail", key="k")) for _ in range(6)]
    assert fires == [False, False, True, False, False, False]
    assert plan.comparable_log() == plan.expected_log()
    assert not plan.unfired()


def test_plan_match_filters_hit_counting():
    plan = FaultPlan(1).add(
        "storage.cas_conflict", at_hits=(2,), match={"key": "manifest"}
    )
    chaos.install(plan)
    # non-matching hits don't advance the spec's counter
    assert not chaos.fire("storage.cas_conflict", key="gen-00001.json")
    assert not chaos.fire("storage.cas_conflict", key="a/manifest.json")
    assert not chaos.fire("storage.cas_conflict", key="gen-00002.json")
    assert chaos.fire("storage.cas_conflict", key="b/manifest.json")


def test_plan_json_roundtrip_and_unknown_point():
    plan = FaultPlan(5).add("worker.kill", at_hits=(4,), params={"x": 1})
    assert FaultPlan.from_json(plan.to_json()).to_json() == plan.to_json()
    with pytest.raises(UnknownFaultPoint):
        FaultPlan(0).add("worker.explode")
    with pytest.raises(UnknownFaultPoint):
        chaos.install(FaultPlan(0))
        chaos.fire("not.a.point")


def test_fire_is_noop_without_plan():
    assert chaos.installed() is None
    assert chaos.fire("worker.kill") is None


def test_install_from_config(tmp_path):
    from arroyo_tpu.config import update

    plan_file = tmp_path / "plan.json"
    plan_file.write_text(
        json.dumps({"faults": [{"point": "worker.kill", "at_hits": [2]}]})
    )
    with update(chaos={"plan": str(plan_file), "seed": 9}):
        plan = chaos.install_from_config()
    assert plan is not None and plan.seed == 9
    assert plan.specs[0].point == "worker.kill"
    chaos.clear()
    # inline JSON form
    with update(chaos={"plan": plan.to_json()}):
        plan2 = chaos.install_from_config()
    assert plan2.specs[0].at_hits == (2,)
    chaos.clear()
    # unset -> no plan
    assert chaos.install_from_config() is None


def test_install_from_config_dedupes_across_incarnations(tmp_path, monkeypatch):
    """Carried robustness bug (ISSUE 15 satellite): a RESPAWNED worker
    (spawn generation > 0, stamped by the process scheduler) must NOT
    re-arm a config-installed plan — re-arming gave every incarnation
    fresh hit counters and turned a heartbeat-hit worker.kill into a
    kill loop. Plans opt back in with "rearm": true."""
    from arroyo_tpu.config import update

    plan_json = json.dumps(
        {"faults": [{"point": "worker.kill", "at_hits": [2]}]}
    )
    # a respawned incarnation: the plan stays un-armed
    monkeypatch.setenv("ARROYO_CHAOS_SPAWN_GEN", "3")
    with update(chaos={"plan": plan_json}):
        assert chaos.install_from_config() is None
        assert chaos.installed() is None
    # explicit opt-in re-arms
    rearm_json = json.dumps(
        {"rearm": True,
         "faults": [{"point": "worker.kill", "at_hits": [2]}]}
    )
    with update(chaos={"plan": rearm_json}):
        assert chaos.install_from_config() is not None
    chaos.clear()
    # first incarnation (gen 0) arms as always
    monkeypatch.setenv("ARROYO_CHAOS_SPAWN_GEN", "0")
    with update(chaos={"plan": plan_json}):
        assert chaos.install_from_config() is not None
    chaos.clear()


def test_process_scheduler_stamps_spawn_generations(monkeypatch):
    """The process scheduler marks pool REPLACEMENTS (and per-job respawn
    rounds) with an increasing spawn generation, which is what suppresses
    chaos-plan re-arming across incarnations."""
    from arroyo_tpu.config import update
    from arroyo_tpu.controller import scheduler as sched_mod

    spawns = []

    class FakeProc:
        def __init__(self, gen):
            self.gen = gen
            self.dead = False

        def poll(self):
            return 1 if self.dead else None

    def fake_spawn(addr, wid, extra_env=None, spawn_generation=0):
        p = FakeProc(spawn_generation)
        spawns.append(p)
        return p

    monkeypatch.setattr(sched_mod, "spawn_worker", fake_spawn)

    async def go():
        s = sched_mod.ProcessScheduler()
        with update(cluster={"multiplexing": "on",
                             "worker_pool_size": 2}):
            await s.start_workers("127.0.0.1:1", 2, "j1")
            assert [p.gen for p in spawns] == [0, 0]
            # a pool worker dies; the replacement is generation 1
            spawns[0].dead = True
            await s.start_workers("127.0.0.1:1", 2, "j2")
            assert [p.gen for p in spawns] == [0, 0, 1]
        spawns.clear()
        with update(cluster={"multiplexing": "off"}):
            s2 = sched_mod.ProcessScheduler()
            await s2.start_workers("127.0.0.1:1", 1, "j3")
            # recovery reschedule of the same job: respawn round 1
            await s2.start_workers("127.0.0.1:1", 1, "j3")
            assert [p.gen for p in spawns] == [0, 1]

    asyncio.run(go())


# -- registry coverage: every seam is listed, every listing has a seam ------


def test_fault_point_registry_matches_call_sites():
    """`tools/chaos_drill.py --list` (FAULT_POINTS) must enumerate exactly
    the fault points the code injects: a new chaos.fire() seam without a
    registry entry — or a registry entry whose seam was deleted — fails
    here, so coverage can't silently rot."""
    called = set()
    for root, _dirs, files in os.walk(PKG):
        if os.path.basename(root) == "chaos":
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(root, fn)).read()
            called.update(re.findall(r'chaos\.fire\(\s*"([^"]+)"', src))
    assert called == set(FAULT_POINTS), (
        f"registry drift: seams without registry entry: "
        f"{sorted(called - set(FAULT_POINTS))}; registry entries without "
        f"a seam: {sorted(set(FAULT_POINTS) - called)}"
    )


def test_drill_tool_lists_fault_points():
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(PKG), "tools",
                                      "chaos_drill.py"), "--list"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    for name in FAULT_POINTS:
        assert name in out.stdout


# -- injector seams (unit level) --------------------------------------------


def test_storage_injectors(tmp_path):
    from arroyo_tpu.state.storage import CasConflict, StorageProvider

    sp = StorageProvider(str(tmp_path / "s"))
    chaos.install(
        FaultPlan(0)
        .add("storage.write_fail", at_hits=(1,))
        .add("storage.cas_conflict", at_hits=(1,))
    )
    with pytest.raises(IOError, match="chaos\\[storage.write_fail\\]"):
        sp.put("a", b"x")
    sp.put("a", b"x")  # transient: second attempt succeeds
    assert sp.get("a") == b"x"
    with pytest.raises(CasConflict):
        sp.put_if_not_exists("b", b"y")
    # the injected conflict must NOT have created the key
    assert not sp.exists("b")
    sp.put_if_not_exists("b", b"y")
    assert sp.get("b") == b"y"


def test_protocol_zombie_fencing(tmp_path):
    from arroyo_tpu.state import protocol
    from arroyo_tpu.state.protocol import Fenced, ProtocolPaths
    from arroyo_tpu.state.storage import StorageProvider

    storage = StorageProvider(str(tmp_path / "s"))
    paths = ProtocolPaths("job")
    gen = protocol.initialize_generation(storage, paths)
    chaos.install(FaultPlan(0).add("protocol.fenced_zombie", at_hits=(1,)))
    with pytest.raises(Fenced, match="zombie"):
        protocol.publish_checkpoint(storage, paths, gen, 1, {"tasks": {}})
    # the fenced publish must not have produced a manifest or moved latest
    assert protocol.load_manifest(storage, paths, 1) is None
    assert protocol.resolve_latest(storage, paths) is None
    # next attempt (fault exhausted) publishes fine
    protocol.publish_checkpoint(storage, paths, gen, 1, {"tasks": {}})
    assert protocol.resolve_latest(storage, paths)["epoch"] == 1


def test_network_partial_frame_never_delivers():
    """A torn frame injected at the sender must surface as a pump failure
    and the receiver must deliver nothing."""
    from arroyo_tpu.engine.network import DataPlaneServer, RemoteEdgeSender
    from arroyo_tpu.operators.queues import BatchQueue

    import pyarrow as pa

    async def go():
        server = DataPlaneServer()
        port = await server.start()
        inbox = BatchQueue(8, 1 << 20)
        quad = (1, 0, 2, 0)
        server.register(quad, inbox)
        outbox = BatchQueue(8, 1 << 20)
        errors = []
        sender = RemoteEdgeSender(
            f"127.0.0.1:{port}", quad, outbox,
            on_error=lambda q, e: errors.append((q, e)),
        )
        chaos.install(
            FaultPlan(0).add("network.partial_frame", at_hits=(2,))
        )
        await sender.start()
        batch = pa.record_batch([pa.array([1, 2, 3])], names=["n"])
        await outbox.send(batch)   # frame 1: delivered
        await outbox.send(batch)   # frame 2: torn, connection dropped
        await asyncio.gather(sender.task, return_exceptions=True)
        await asyncio.sleep(0.1)
        got = [await inbox.recv() for _ in range(inbox.qsize())]
        await server.stop()
        return got, errors

    got, errors = asyncio.run(go())
    assert len(got) == 1  # the torn frame was never delivered
    assert len(errors) == 1 and isinstance(errors[0][1], ConnectionResetError)


def test_multihost_init_failure_names_coordinator(monkeypatch):
    """ADVICE r5: a lost pick_coordinator bind-then-close race must raise
    an error naming the coordinator address and the tpu.mesh_coordinator
    pin, not jax's bare connect failure."""
    from arroyo_tpu import parallel
    from arroyo_tpu.config import update
    from arroyo_tpu.parallel import multihost

    import jax

    monkeypatch.setattr(multihost, "_initialized", None)

    def boom(**kw):
        raise RuntimeError("DEADLINE_EXCEEDED: connect failed")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with update(tpu={"mesh_coordinator": "10.0.0.7:4612",
                     "mesh_processes": 2, "mesh_process_id": 1}):
        with pytest.raises(RuntimeError) as err:
            multihost.ensure_initialized()
    msg = str(err.value)
    assert "10.0.0.7:4612" in msg
    assert "tpu.mesh_coordinator" in msg
    assert "ARROYO__TPU__MESH_COORDINATOR" in msg


# -- the fast smoke drill (default suite) -----------------------------------


def test_stall_extreme_hold_never_double_emits(tmp_path):
    """An extreme `runner.stall` hold — an operator wedged for seconds
    mid-stream with barriers still flowing, and NO restart — must delay
    window emission, never repeat it: every (key, window) pair emits
    exactly once and the output is byte-identical to the unstalled run
    (ISSUE 16: the shared-plan gate reasons about stalled tenants, so
    the stall seam itself must be emission-safe without recovery)."""
    from arroyo_tpu.chaos import drill

    def sql(out):
        return f"""
        CREATE TABLE impulse WITH (
          connector = 'impulse', event_rate = '5000',
          message_count = '1500', start_time = '0'
        );
        CREATE TABLE out (k BIGINT UNSIGNED, start TIMESTAMP, cnt BIGINT)
        WITH (
          connector = 'single_file', path = '{out}', format = 'json',
          type = 'sink'
        );
        INSERT INTO out
        SELECT k, window.start as start, cnt FROM (
          SELECT counter % 4 as k,
                 tumble(interval '100 millisecond') as window,
                 count(*) as cnt
          FROM impulse GROUP BY 1, 2
        );
        """

    clean = str(tmp_path / "clean.json")
    drill._run_embedded(sql(clean), "stall-clean", None, 1, 1,
                        max_restarts=0, heartbeat_interval=0.1,
                        heartbeat_timeout=30.0, checkpoint_interval=60.0,
                        timeout=60.0)

    stalled = str(tmp_path / "stalled.json")
    plan = FaultPlan(7).add(
        "runner.stall", at_hits=(2, 3, 4), match={"job": "stall-hold"},
        params={"delay": 1.5}, max_fires=3,
    )
    chaos.install(plan)
    try:
        restarts = drill._run_embedded(
            sql(stalled), "stall-hold", str(tmp_path / "ck"), 1, 1,
            max_restarts=0, heartbeat_interval=0.1,
            heartbeat_timeout=30.0, checkpoint_interval=0.2,
            timeout=60.0,
        )
    finally:
        chaos.clear()
    assert restarts == 0  # the hold is a delay, never a recovery path
    assert not plan.unfired()

    def rows(path):
        return sorted(open(path).read().splitlines())

    got = rows(stalled)
    assert got and got == rows(clean)
    keys = [(json.loads(r)["k"], json.loads(r)["start"]) for r in got]
    assert len(keys) == len(set(keys)), "a window emitted twice"


def test_fast_smoke_drill(tmp_path):
    """1 golden, 2 faults (data-plane drop + manifest CAS loss) through
    the real embedded cluster: output identical to the fault-free run,
    the fired-fault log equals the seed's deterministic schedule, and
    every fired fault lands in the flight recorder as a span event
    (ISSUE 4: drill timelines show fault -> detection -> recovery)."""
    from arroyo_tpu import obs
    from arroyo_tpu.chaos import drill

    obs.reset()
    res = drill.run_drill(
        drill.DEFAULT_DRILL_QUERIES[0], seed=1234, workdir=str(tmp_path),
        plan_factory=drill.fast_plan, throttle=400.0,
    )
    assert res.passed, res.error
    assert res.restarts >= 1  # at least one fault forced a recovery
    assert res.comparable_log == res.expected_log
    # reproducibility: the schedule is a pure function of the seed
    assert res.expected_log == drill.fast_plan(1234).expected_log()
    assert res.expected_log != drill.fast_plan(4321).expected_log()
    # every fired fault is a chaos.fire:<point> instant in the recorder
    fired_points = {e["point"] for e in res.fired}
    recorded = {
        s["name"].removeprefix("chaos.fire:")
        for s in obs.recorder().snapshot()
        if s["name"].startswith("chaos.fire:")
    }
    assert fired_points <= recorded, (fired_points, recorded)
    # the CAS-conflict fire happens INSIDE the manifest publish: it must
    # attach to the live checkpoint trace, not float free
    cas_events = [
        s for s in obs.recorder().snapshot()
        if s["name"] == "chaos.fire:storage.cas_conflict"
    ]
    assert any("/ck-" in s["trace_id"] for s in cas_events), cas_events
    obs.reset()


@pytest.mark.parametrize("flushes_lost", [1, 2])
def test_a_worker_killed_between_a_barrier_and_its_flush_restores_the_published_view(
        tmp_path, flushes_lost):
    """ISSUE 39 moved a capture's merge, bytes and blob behind the
    barrier, onto the flush: a worker that dies after the barrier of
    epoch N (N + 1 too: `state.max_inflight_flushes` 2) and before its
    flush has written nothing of it, so the next incarnation restores
    from the last PUBLISHED epoch, and its serve view (seeded from the
    `__serve__` chain) equals the reference at that epoch: none of the
    unflushed epochs' rows, all of the published ones'."""
    from types import SimpleNamespace

    import numpy as np
    import pyarrow as pa

    from arroyo_tpu.operators.control import CheckpointCompletedResp
    from arroyo_tpu.operators.windows import WindowOperatorBase
    from arroyo_tpu.serve.store import (
        SERVE_TABLE,
        register_op,
        seal_op,
        serve_mirror_tables,
        stage_batch,
    )
    from arroyo_tpu.state.backend import StateBackend
    from arroyo_tpu.state.table_manager import TableManager
    from arroyo_tpu.types import TaskInfo

    url = f"file://{tmp_path}/kill"
    ti = TaskInfo("kill", 3, "hop", 0, 1)
    rng = np.random.default_rng(39 + flushes_lost)

    def incarnation():
        backend = StateBackend(url, "kill").initialize()
        tm = TableManager(backend, ti, 0)
        op = WindowOperatorBase.__new__(WindowOperatorBase)
        op.name, op._key_names, op.key_cols = "hop", ["auction"], [0]
        op.out_schema = SimpleNamespace(schema=pa.schema([
            ("auction", pa.int64()), ("count", pa.int64())]))
        asyncio.run(tm.open(serve_mirror_tables(op, ti)))
        view = register_op(op, SimpleNamespace(task_info=ti,
                                               table_manager=tm))
        return backend, tm, op, view

    backend, tm, op, view = incarnation()
    reference, captured = {}, {}
    last_epoch, published = 6, 6 - flushes_lost
    for epoch in range(1, last_epoch + 1):
        for _close in range(3):
            keys = np.concatenate([rng.choice(100, 30, replace=False),
                                   np.arange(20) + 1_000 * epoch])
            counts = rng.integers(1, 900, len(keys))
            stage_batch(view, pa.RecordBatch.from_pydict({
                "auction": pa.array(keys.astype(np.int64)),
                "count": pa.array(counts)}))
            if epoch <= published:
                reference.update(zip(keys.tolist(), counts.tolist()))
        seal_op(op, epoch, tm)
        captured[epoch] = tm.capture(epoch, None)   # the barrier passes
        if epoch <= published:
            meta = tm.flush_captured(epoch, captured[epoch])
            backend.publish_checkpoint(epoch, {"3-0": CheckpointCompletedResp(
                "3-0", 3, 0, epoch, subtask_metadata={"op0": meta},
                watermark=None)})
    # killed here: the unflushed epochs are captured, un-merged, unwritten
    for epoch in range(published + 1, last_epoch + 1):
        staged = captured[epoch][SERVE_TABLE]
        assert callable(staged["blob"]) and view.pending[epoch].deferred
        assert backend.read_blob(staged["chain"][-1]["path"]) is None
    assert view.read((1_000 * last_epoch,), last_epoch)[0]

    backend2, _tm2, _op2, restored = incarnation()
    assert backend2.restore_epoch == published
    for k in list(range(100)) + [1_000 * e + 7 for e in range(1, 8)]:
        want = ((True, {"count": reference[k]}) if k in reference
                else (False, None))
        assert restored.read((k,), published) == want, k
    assert restored.stats()["keys"] == len(reference)
