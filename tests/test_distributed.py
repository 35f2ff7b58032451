"""Distributed execution: controller + workers over the gRPC control plane
and TCP data plane; embedded (in-process) and real multi-process runs;
failure recovery from checkpoints."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from arroyo_tpu.controller.controller import ControllerServer
from arroyo_tpu.controller.scheduler import EmbeddedScheduler
from arroyo_tpu.controller.state_machine import (
    IllegalTransition,
    JobState,
    check_transition,
)


def sql_pipeline(tmp, n=2000, out="out.json", throttle=None):
    throttle_opt = (
        f",\n  throttle_per_sec = '{throttle}'" if throttle else ""
    )
    return f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '1000000',
      message_count = '{n}', start_time = '0'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp}/{out}',
      format = 'json', type = 'sink'{throttle_opt}
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % 8 as k, tumble(interval '1 millisecond') as w,
             count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """


def read_counts(path):
    from collections import Counter

    c = Counter()
    with open(path) as f:
        for line in f:
            if line.strip():
                r = json.loads(line)
                c[r["k"]] += r["cnt"]
    return dict(c)


def test_state_machine_transitions():
    check_transition(JobState.CREATED, JobState.SCHEDULING)
    check_transition(JobState.RUNNING, JobState.RECOVERING)
    with pytest.raises(IllegalTransition):
        check_transition(JobState.STOPPED, JobState.RUNNING)
    assert JobState.FAILED.is_terminal()


def test_embedded_cluster_two_workers(tmp_path):
    """Controller + 2 embedded workers: keyed shuffle crosses the TCP data
    plane (subtasks round-robin across workers)."""

    async def go():
        c = await ControllerServer(EmbeddedScheduler()).start()
        await c.submit_job(
            "d1", sql=sql_pipeline(tmp_path), n_workers=2, parallelism=2
        )
        state = await c.wait_for_state(
            "d1", JobState.FINISHED, JobState.FAILED, timeout=60
        )
        await c.stop()
        return state

    state = asyncio.run(go())
    assert state == JobState.FINISHED
    counts = read_counts(tmp_path / "out.json")
    assert counts == {k: 250 for k in range(8)}


def test_embedded_cluster_with_checkpoints_and_stop(tmp_path):
    async def go():
        c = await ControllerServer(EmbeddedScheduler()).start()
        from arroyo_tpu.config import update

        with update(pipeline={"checkpointing": {"interval": 0.1}}):
            await c.submit_job(
                "d2",
                sql=sql_pipeline(tmp_path, n=100000, throttle=None).replace(
                    "'1000000'", "'200000'"
                ).replace("start_time = '0'",
                          "start_time = '0', realtime = 'true'"),
                storage_url=str(tmp_path / "ck"),
                n_workers=2,
                parallelism=2,
            )
            await c.wait_for_state("d2", JobState.RUNNING, timeout=30)
            # let at least one checkpoint land, then checkpoint-stop
            await asyncio.sleep(0.4)
            await c.stop_job("d2", "checkpoint")
            state = await c.wait_for_state(
                "d2", JobState.STOPPED, JobState.FAILED, timeout=60
            )
        job = c.jobs["d2"]
        await c.stop()
        return state, job.epoch

    state, epoch = asyncio.run(go())
    assert state == JobState.STOPPED
    assert epoch >= 1  # at least the stopping checkpoint published


def test_recovery_after_task_failure(tmp_path):
    """A task failure mid-run sends the job through Recovering and it
    completes from the latest checkpoint with exact output."""
    fail_flag = tmp_path / "fail_once"
    fail_flag.write_text("1")

    from arroyo_tpu.udf import udf
    import pyarrow as pa

    flag_path = str(fail_flag)

    @udf(pa.int64(), [pa.int64()], name="maybe_boom")
    def maybe_boom(xs):
        import numpy as np
        import os as _os

        if _os.path.exists(flag_path) and (xs > 60000).any():
            _os.unlink(flag_path)
            raise RuntimeError("injected failure")
        return xs

    sql = f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '150000',
      message_count = '100000', start_time = '0', realtime = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp_path}/out.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT maybe_boom(counter) % 8 as k,
             tumble(interval '100 millisecond') as w, count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """

    async def go():
        from arroyo_tpu.config import update

        c = await ControllerServer(EmbeddedScheduler()).start()
        with update(pipeline={"checkpointing": {"interval": 0.1}}):
            await c.submit_job(
                "d3", sql=sql, storage_url=str(tmp_path / "ck"), n_workers=1
            )
            state = await c.wait_for_state(
                "d3", JobState.FINISHED, JobState.FAILED, timeout=120
            )
        job = c.jobs["d3"]
        await c.stop()
        return state, job.restarts

    state, restarts = asyncio.run(go())
    assert state == JobState.FINISHED
    assert restarts >= 1  # went through Recovering
    counts = read_counts(tmp_path / "out.json")
    assert sum(counts.values()) == 100000
    assert counts == {k: 12500 for k in range(8)}


@pytest.mark.slow
def test_multiprocess_cluster(tmp_path):
    """Real separate worker processes via `python -m arroyo_tpu run`."""
    sql_path = tmp_path / "q.sql"
    sql_path.write_text(sql_pipeline(tmp_path, n=4000))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "-m", "arroyo_tpu", "run", str(sql_path),
         "--parallelism", "2", "--workers", "2", "--scheduler", "process"],
        cwd="/root/repo",
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert "job finished" in out.stdout, out.stdout + out.stderr
    counts = read_counts(tmp_path / "out.json")
    assert counts == {k: 500 for k in range(8)}


@pytest.mark.slow
def test_process_scheduler_kill_restore(tmp_path):
    """ROADMAP open item (PR-3 verify): process-scheduler restore after a
    worker kill reportedly failed with an IndexError reading the
    timestamp column of a restored batch (subtask 1-0). A ~25-run sweep
    (chaos kills at varied heartbeat hits, external SIGKILLs, injected
    storage latency, parallelism 1/2) could NOT reproduce it on this
    tree; this regression pins the exact scenario — worker subprocess
    killed mid-stream, job recovers from durable checkpoints, output
    stays exactly-once. If the IndexError recurs, the restore spans
    (state.restore_table events per file/stage) in the job.schedule trace
    name the failing table and stage: dump /debug/trace or re-run with
    tools/trace_report.py."""
    sql_path = tmp_path / "q.sql"
    sql_path.write_text(
        sql_pipeline(tmp_path, n=200000).replace("'1000000'", "'120000'")
        .replace("start_time = '0'", "start_time = '0', realtime = 'true'")
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    # kill the first worker subprocess ~2.5s in (heartbeat hit 25 at
    # 0.1s/beat), after several 0.15s-cadence checkpoints have landed
    env["ARROYO__CHAOS__PLAN"] = json.dumps({
        "seed": 1,
        "faults": [{"point": "worker.kill", "at_hits": [25],
                    "match": {"worker_id": "2000"}}],
    })
    env["ARROYO__PIPELINE__CHECKPOINTING__INTERVAL"] = "0.15"
    env["ARROYO__WORKER__HEARTBEAT_INTERVAL"] = "0.1"
    env["ARROYO__CONTROLLER__HEARTBEAT_TIMEOUT"] = "1.2"
    out = subprocess.run(
        [sys.executable, "-m", "arroyo_tpu", "run", str(sql_path),
         "--parallelism", "2", "--workers", "2", "--scheduler", "process",
         "--state-dir", str(tmp_path / "ck")],
        cwd="/root/repo",
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert "IndexError" not in out.stderr, out.stderr
    assert "job finished" in out.stdout, out.stdout + out.stderr
    assert "Recovering" in out.stderr  # the kill actually forced recovery
    counts = read_counts(tmp_path / "out.json")
    assert counts == {k: 25000 for k in range(8)}


def test_finish_racing_inflight_checkpoint(tmp_path):
    """A checkpoint issued just before the stream ends can never complete
    (finished tasks don't report); the controller must see the finish and
    NOT misread the cleanly-stopped worker's silence as a heartbeat
    timeout (regression: endless recover/re-finish loop)."""

    async def go():
        from arroyo_tpu.config import update

        c = await ControllerServer(EmbeddedScheduler()).start()
        # heartbeat_timeout must exceed the worker's 2s heartbeat period or
        # the timeout itself fires spuriously mid-run
        with update(pipeline={"checkpointing": {"interval": 0.01}},
                    controller={"heartbeat_timeout": 5.0}):
            await c.submit_job(
                "d5", sql=sql_pipeline(tmp_path, n=20000),
                storage_url=str(tmp_path / "ck"), n_workers=1,
            )
            state = await c.wait_for_state(
                "d5", JobState.FINISHED, JobState.FAILED, timeout=30
            )
        job = c.jobs["d5"]
        await c.stop()
        return state, job.restarts

    state, restarts = asyncio.run(go())
    assert state == JobState.FINISHED
    assert restarts == 0
    counts = read_counts(tmp_path / "out.json")
    assert sum(counts.values()) == 20000


def test_worker_leader_mode(tmp_path):
    """job_controller_mode=worker: the first worker runs the checkpoint
    cadence and manifest publish (the controller's checkpoint collection
    stays empty), checkpoint-stop is delegated to the leader, and a
    restart resumes from the leader-published manifest with exact output."""
    from arroyo_tpu.config import update

    url = str(tmp_path / "ck")
    # ~1.7s of realtime stream so the mid-run checkpoint-stop lands well
    # before the source drains
    sql = sql_pipeline(tmp_path, n=200000).replace(
        "'1000000'", "'120000'"
    ).replace("start_time = '0'", "start_time = '0', realtime = 'true'")

    async def phase1():
        c = await ControllerServer(EmbeddedScheduler()).start()
        with update(controller={"job_controller_mode": "worker"},
                    pipeline={"checkpointing": {"interval": 0.1}}):
            await c.submit_job("wl", sql=sql, storage_url=url,
                               n_workers=2, parallelism=2)
            await c.wait_for_state("wl", JobState.RUNNING, timeout=30)
            await asyncio.sleep(0.3)  # let leader checkpoints land
            await c.stop_job("wl", "checkpoint")
            state = await c.wait_for_state(
                "wl", JobState.STOPPED, JobState.FAILED, timeout=60
            )
        job = c.jobs["wl"]
        await c.stop()
        return state, job.epoch, dict(job.checkpoints)

    state, epoch, controller_ckpts = asyncio.run(phase1())
    assert state == JobState.STOPPED
    assert epoch >= 1  # leader published + reported at least one epoch
    # reports went to the leader, not the controller
    assert controller_ckpts == {}

    async def phase2():
        c = await ControllerServer(EmbeddedScheduler()).start()
        with update(controller={"job_controller_mode": "worker"},
                    pipeline={"checkpointing": {"interval": 0.1}}):
            await c.submit_job("wl", sql=sql, storage_url=url,
                               n_workers=2, parallelism=2)
            state = await c.wait_for_state(
                "wl", JobState.FINISHED, JobState.FAILED, timeout=60
            )
        await c.stop()
        return state

    assert asyncio.run(phase2()) == JobState.FINISHED
    counts = read_counts(tmp_path / "out.json")
    assert counts == {k: 25000 for k in range(8)}


def test_node_scheduler(tmp_path):
    """A node daemon offers slots; the controller's node scheduler places
    real worker subprocesses on it (reference arroyo-node + node
    scheduler)."""
    from arroyo_tpu.config import update
    from arroyo_tpu.controller.node import NodeServer
    from arroyo_tpu.controller.scheduler import NodeScheduler

    async def go():
        c = await ControllerServer(NodeScheduler()).start()
        node = await NodeServer(
            c.addr, slots=4,
            extra_env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": "/root/repo"},
        ).start()
        with update(controller={"scheduler": "node"}):
            await c.submit_job(
                "nd1", sql=sql_pipeline(tmp_path, n=4000),
                n_workers=2, parallelism=2,
            )
            state = await c.wait_for_state(
                "nd1", JobState.FINISHED, JobState.FAILED, timeout=90
            )
        # stop_workers runs just after the FINISHED transition; let it land
        for _ in range(100):
            used = [n.used for n in c.nodes.values()]
            if used == [0]:
                break
            await asyncio.sleep(0.05)
        await node.stop()
        await c.stop()
        return state, used

    state, used = asyncio.run(go())
    assert state == JobState.FINISHED
    assert used == [0]  # slots returned after the job
    counts = read_counts(tmp_path / "out.json")
    assert counts == {k: 500 for k in range(8)}
