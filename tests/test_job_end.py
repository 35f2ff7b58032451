"""A finite job ends when its last task does (ISSUE 26, ROADMAP R13).

`JobHandle.kick()` used to resolve only the waits parked at that instant:
a last `TaskFinished` that arrived while the run loop was inside
`_publish_epoch` found nobody parked, and the loop then parked until the
heartbeat horizon (`controller.heartbeat_timeout`, 30 s). `kicks` is the
generation of those events; `wait_kick(..., seen)` returns at once when it
moved since the caller read it."""

import asyncio
import time

from arroyo_tpu.config import update
from arroyo_tpu.controller.controller import (
    ControllerServer,
    JobHandle,
    TimerWheel,
)
from arroyo_tpu.controller.scheduler import EmbeddedScheduler
from arroyo_tpu.controller.state_machine import JobState
from arroyo_tpu.sql import plan_query


def bounded_sql(tmp, n=4000, rate=2000):
    """~2 s of wall: long enough that a 0.3 s cadence publishes epochs
    while it runs."""
    return f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '{rate}',
      message_count = '{n}', start_time = '0', realtime = 'true',
      replay = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp}/out.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % 8 as k, tumble(interval '100 millisecond') as w,
             count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """


def a_job():
    graph = plan_query(
        "CREATE TABLE impulse WITH (connector = 'impulse', event_rate = "
        "'10', message_count = '10', start_time = '0'); "
        "SELECT counter FROM impulse;", parallelism=1).graph
    return JobHandle("j", graph, None)


def test_a_kick_with_no_waiter_parked_is_seen_by_the_next_wait():
    async def go():
        wheel = TimerWheel()
        wheel.start()
        job = a_job()
        seen = job.kicks
        job.kick()                       # nobody is parked
        t0 = time.monotonic()
        kicked = await job.wait_kick(wheel, 30.0, seen)
        first = time.monotonic() - t0
        # the same event wakes a caller once: with the generation read
        # anew the next wait parks until its deadline
        t0 = time.monotonic()
        again = await job.wait_kick(wheel, 0.2, job.kicks)
        second = time.monotonic() - t0
        await wheel.stop()
        return kicked, first, again, second, job.wakeups

    kicked, first, again, second, wakeups = asyncio.run(go())
    assert kicked is True and first < 0.05
    assert again is False and second >= 0.15
    assert wakeups == 2


def test_a_parked_wait_is_still_woken_by_a_kick_and_by_its_deadline():
    async def go():
        wheel = TimerWheel()
        wheel.start()
        job = a_job()
        waits = [asyncio.ensure_future(job.wait_kick(wheel, 30.0, job.kicks))
                 for _ in range(2)]
        await asyncio.sleep(0.05)
        assert not any(w.done() for w in waits) and job.wakeups == 0
        job.kick()
        got = await asyncio.wait_for(asyncio.gather(*waits), 1.0)
        late = await job.wait_kick(wheel, 0.1, job.kicks)
        await wheel.stop()
        return got, late

    got, late = asyncio.run(go())
    assert got == [True, True] and late is False


def test_a_task_finished_during_a_publish_ends_the_job_within_a_second(
        tmp_path, monkeypatch):
    """The last `TaskFinished` arrives while `_publish_epoch` is awaited
    (gated on an event here): once the publish returns the job must be
    FINISHED within 1 s, at the default 30 s heartbeat timeout."""
    state = {}
    real = ControllerServer._publish_epoch

    async def gated(self, job, epoch, reports):
        if "gate" not in state:         # the first publish is the one held
            state["gate"] = asyncio.Event()
            await state["gate"].wait()
        await real(self, job, epoch, reports)

    monkeypatch.setattr(ControllerServer, "_publish_epoch", gated)

    async def go():
        with update(cluster={"worker_pool_size": 1},
                    pipeline={"checkpointing": {"interval": 0.3}},
                    controller={"heartbeat_timeout": 30.0}):
            c = await ControllerServer(EmbeddedScheduler()).start()
            job = await c.submit_job(
                "ends", sql=bounded_sql(tmp_path), n_workers=1,
                storage_url=str(tmp_path / "state"))
            # every task finishes while the first publish is held
            deadline = time.monotonic() + 60
            while not (len(job.finished_tasks) >= job.n_subtasks > 0
                       and "gate" in state):
                assert time.monotonic() < deadline, (
                    job.state, len(job.finished_tasks), state)
                await asyncio.sleep(0.02)
            assert job.state == JobState.RUNNING
            t0 = time.monotonic()
            state["gate"].set()
            await c.wait_for_state("ends", JobState.FINISHED, timeout=45)
            took = time.monotonic() - t0
            await c.stop()
            return took

    assert asyncio.run(go()) < 1.0


def test_the_data_plane_stops_over_a_connection_blocked_on_a_full_queue():
    """After a recovery a straggler connection of the torn-down incarnation
    can sit in `queue.send` on a queue nobody drains; `Server.wait_closed`
    waits for every handler (Python 3.12), so `DataPlaneServer.stop` has to
    end them itself. It used to wait forever: the tier-1 runs that hung in
    `tests/test_state_scale.py` after `job ... recovering` were this."""
    import pyarrow as pa

    from arroyo_tpu.engine.network import (
        DataPlaneServer,
        write_frame,
        write_hello,
    )
    from arroyo_tpu.operators.queues import BatchQueue

    quad = (1, 0, 2, 0)
    batch = pa.RecordBatch.from_arrays([pa.array([1, 2, 3])], names=["k"])

    async def go():
        server = DataPlaneServer()
        port = await server.start()
        queue = BatchQueue(1, 1 << 20)          # room for one batch
        server.register(quad, queue, ns="j@1")
        _, writer = await asyncio.open_connection("127.0.0.1", port)
        write_hello(writer, "j@1")
        for _ in range(3):
            write_frame(writer, quad, batch)
        await writer.drain()
        deadline = time.monotonic() + 10
        while not (server._handlers and queue.fullness() >= 1.0):
            assert time.monotonic() < deadline
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)                # the handler is in `send`
        t0 = time.monotonic()
        await asyncio.wait_for(server.stop(), 5.0)
        took = time.monotonic() - t0
        writer.close()
        return took, len(server._handlers)

    took, left = asyncio.run(go())
    assert took < 1.0 and left == 0
