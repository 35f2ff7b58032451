"""Multi-tenant control plane (ISSUE 10): worker multiplexing, the
event-driven controller, admission control, and metrics cardinality GC.

The fast tier proves the tentpole invariants at small scale: ~25
concurrent tiny impulse pipelines multiplexed onto a 2-worker shared
pool with create/stop churn and one mid-run worker SIGKILL, every
surviving job's output byte-identical to its solo run; a parked RUNNING
job costs ZERO controller wakeups over a poll interval; terminal jobs'
metric series are dropped so churn can't grow /metrics unboundedly; the
admission queue grants fair-share across tenants. The slow tier scales
the churn harness to 200 jobs."""

import asyncio
import json
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from arroyo_tpu.config import update
from arroyo_tpu.controller.controller import ControllerServer, TimerWheel
from arroyo_tpu.controller.scheduler import (
    EmbeddedScheduler,
    multiplexing_active,
)
from arroyo_tpu.controller.state_machine import JobState


def bounded_sql(tmp, tag, j, n=3000, rate=1_000_000, realtime=False,
                window_ms=1):
    """Deterministic event-time pipeline (byte-identical across runs).
    `realtime` uses the impulse REPLAY mode (wall-paced arrival,
    synthetic timestamps): a slow wall-paced fleet run and a fast solo
    run produce the same bytes, so churn/kills can land mid-run."""
    rt = ", realtime = 'true', replay = 'true'" if realtime else ""
    return f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '{rate}',
      message_count = '{n}', start_time = '0'{rt}
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp}/{tag}-{j}.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % 8 as k,
             tumble(interval '{window_ms} millisecond') as w,
             count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """


def parked_sql(tmp, j):
    return f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '0.05',
      message_count = '1000000', start_time = '0', realtime = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp}/parked-{j}.json',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, cnt FROM (
      SELECT counter % 4 as k, tumble(interval '1 second') as w,
             count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """


def canonical(path):
    import os

    if not os.path.exists(path):
        return []
    with open(path) as f:
        return sorted(
            json.dumps(json.loads(line), sort_keys=True)
            for line in f if line.strip()
        )


def test_multiplexing_gates():
    """Multiplexing engages for embedded/process under the controller-
    resident job control loop, and falls back for worker-leader mode,
    multi-process meshes, other schedulers, and the off switch."""
    assert multiplexing_active("embedded")
    assert multiplexing_active("process")
    assert not multiplexing_active("node")
    assert not multiplexing_active("manual")
    with update(cluster={"multiplexing": "off"}):
        assert not multiplexing_active("embedded")
    with update(controller={"job_controller_mode": "worker"}):
        assert not multiplexing_active("embedded")
    with update(tpu={"mesh_processes": 2}):
        assert not multiplexing_active("process")


def test_multiplexed_fleet_exactly_once(tmp_path):
    """~25 tiny durable pipelines share a 2-worker pool under create/stop
    churn and one mid-run worker SIGKILL; every job that ran to
    completion produces output byte-identical to its solo run (the
    exactly-once machinery holds per job while co-scheduled)."""
    N = 25
    # 20 ms windows: at 700 events/s a 1 ms window closes once per event,
    # and 25 jobs' 17,500 one-row closes a second are more than the one
    # event loop (controller, both workers, every runner) can turn; the
    # heartbeats then age past the timeout, every job recovers at once,
    # replays at the same rate, and no job ever finishes
    sql = dict(n=3000, rate=700, realtime=True, window_ms=20)

    async def until(cond, what, timeout=30.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, f"never saw: {what}"
            await asyncio.sleep(0.02)

    async def fleet():
        with update(
            # 1s cadence: 25 durable jobs checkpoint 25x/s at 0.5s on this
            # one-core host, which saturates the loop into heartbeat noise
            pipeline={"checkpointing": {"interval": 1.0}},
            cluster={"worker_pool_size": 2, "metrics_ttl": 0.0},
            # generous heartbeat window: 25 starting jobs can stall the
            # shared event loop for seconds on this host, and spurious
            # timeouts would burn restart budget (the registry self-heals
            # either way, but churn is noise here)
            controller={"heartbeat_timeout": 6.0},
            # slots sized for tiny-job density: 25 one-slot jobs need 13
            # slots per pool worker to all be admitted CONCURRENTLY
            worker={"heartbeat_interval": 0.2, "task_slots": 16},
        ):
            sched = EmbeddedScheduler()
            c = await ControllerServer(sched, max_restarts=8).start()
            # replay-mode impulse stretches each job past the kill while
            # event time stays deterministic (byte-identical output)
            for j in range(N):
                await c.submit_job(
                    f"fl{j}",
                    sql=bounded_sql(tmp_path, "fleet", j, **sql),
                    storage_url=str(tmp_path / f"ck-{j}"),
                    n_workers=2, parallelism=1,
                    tenant=f"t{j % 3}",
                )
            # every job multiplexed onto the same 2 pool workers
            await until(lambda: len(sched.pool) == 2, "a pool of two")
            for jid in (f"fl{j}" for j in range(N)):
                await c.wait_for_state(jid, JobState.RUNNING,
                                       JobState.FINISHED, JobState.FAILED,
                                       timeout=60)
            hosted = {
                w.worker_id: len(w._jobs)
                for w, _t in sched.pool
            }
            # churn: stop a few jobs mid-run (their partial output is not
            # compared; the point is that co-resident jobs don't notice)
            stopped = {f"fl{j}" for j in range(0, N, 7)}
            for jid in stopped:
                await c.stop_job(jid, "immediate")
            # one mid-run SIGKILL-equivalent on a pool worker: every job
            # with subtasks there recovers independently from checkpoints.
            # Mid-run is a condition, not a time: a job that is running
            # and has a published checkpoint to recover from
            def mid_run():
                return [j for j in c.jobs.values()
                        if j.job_id not in stopped
                        and j.state == JobState.RUNNING
                        and j.published_epoch >= 1]

            await until(mid_run, "a running job with a checkpoint")
            victim = next(
                w for w, _t in sched.pool
                if not getattr(w, "_shutdown_started", False)
            )
            await victim.shutdown()
            for j in range(N):
                state = await c.wait_for_state(
                    f"fl{j}", JobState.FINISHED, JobState.STOPPED,
                    JobState.FAILED, timeout=120,
                )
                if f"fl{j}" not in stopped:
                    assert state == JobState.FINISHED, (
                        f"fl{j}: {state} ({c.jobs[f'fl{j}'].failure})"
                    )
            await c.stop()
            return hosted, stopped

    hosted, stopped = asyncio.run(fleet())
    # multiplexing really happened: each pool worker hosted many jobs
    assert all(n >= N // 2 for n in hosted.values()), hosted

    async def solo(j):
        with update(pipeline={"checkpointing": {"interval": 0.5}},
                    cluster={"worker_pool_size": 2}):
            c = await ControllerServer(EmbeddedScheduler()).start()
            # IDENTICAL query to the fleet run (event_rate shapes the
            # synthetic timestamps, so it must match): replay mode makes
            # the solo bytes independent of wall-clock conditions
            await c.submit_job(
                f"solo{j}",
                sql=bounded_sql(tmp_path, "solo", j, **sql),
                storage_url=str(tmp_path / f"solo-ck-{j}"),
                n_workers=2, parallelism=1,
            )
            state = await c.wait_for_state(
                f"solo{j}", JobState.FINISHED, JobState.FAILED, timeout=60
            )
            await c.stop()
            return state

    # byte-identical vs solo for a sample of the completed jobs (every
    # job ran the same deterministic impulse; three cover the placement
    # spread without tripling fast-tier runtime)
    for j in (1, 2, 3):
        assert f"fl{j}" not in stopped
        assert asyncio.run(solo(j)) == JobState.FINISHED
        fleet_rows = canonical(tmp_path / f"fleet-{j}.json")
        solo_rows = canonical(tmp_path / f"solo-{j}.json")
        assert fleet_rows and fleet_rows == solo_rows, f"job fl{j} differs"


def test_parked_running_job_zero_wakeups(tmp_path):
    """Satellite regression: a parked RUNNING job (trickle source, no
    cadence due, nothing finishing) must cost ZERO controller driver
    wakeups over a poll interval — the old loops burned one per 20 ms
    per caller. A wait_for_state watcher parks alongside without
    polling either."""

    async def go():
        with update(cluster={"worker_pool_size": 1}):
            c = await ControllerServer(EmbeddedScheduler()).start()
            await c.submit_job(
                "parked", sql=parked_sql(tmp_path, 0), n_workers=1
            )
            await c.wait_for_state("parked", JobState.RUNNING, timeout=30)
            # a state watcher parks on the same per-job kick list
            watcher = asyncio.ensure_future(
                c.wait_for_state("parked", JobState.STOPPED, timeout=30)
            )
            await asyncio.sleep(0.5)  # let startup events settle
            job = c.jobs["parked"]
            before = job.wakeups
            await asyncio.sleep(1.0)  # 50 wakeups under the old 50 Hz loop
            delta = job.wakeups - before
            await c.stop_job("parked", "immediate")
            await c.wait_for_state("parked", JobState.STOPPED, timeout=30)
            await watcher
            await c.stop()
            return delta

    assert asyncio.run(go()) == 0


def test_metrics_cardinality_gc(tmp_path):
    """Satellite: churning N jobs must return /metrics exposition to
    ~baseline — per-job series (task counters, queue gauges with weakref
    refreshers, latency histograms, and the ISSUE 11
    arroyo_job_attributed_* attribution families) are dropped at
    terminal states, and the observatory side state (trace-ring spans,
    timeline phase instants, attribution accumulators) is expunged on
    the same path."""
    from arroyo_tpu import obs
    from arroyo_tpu.metrics import REGISTRY
    from arroyo_tpu.obs import attribution, timeline

    async def churn(tag, n):
        with update(cluster={"worker_pool_size": 2, "metrics_ttl": 0.0}):
            c = await ControllerServer(EmbeddedScheduler()).start()
            for j in range(n):
                await c.submit_job(
                    f"{tag}{j}",
                    sql=bounded_sql(tmp_path, tag, j, n=1500),
                    n_workers=2,
                )
            # serving-tier GC (ISSUE 12): mint job-labeled
            # arroyo_serve_* series + gateway routing/cache state for
            # every churned job so the assertions below prove the serve
            # tier rides the same expunge path as the rest
            for j in range(n):
                await c.serve.read(f"{tag}{j}", "tumbling_window", [0])
            # conservation-ledger GC (ISSUE 19): mint a reconciler and
            # its job-labeled arroyo_audit_* series per churned job —
            # expunged with the job, same path
            from arroyo_tpu.obs import audit
            for j in range(n):
                audit.reconciler(f"{tag}{j}").reconcile(
                    1, {"t": {"tx": {"e": [1, 2]}, "rx": {"e": [1, 2]},
                              "ops": {}, "flow": {}}},
                )
            # replica-tier GC (ISSUE 20): mint the job-labeled
            # arroyo_replica_* families (tail counts, served-epoch /
            # lag gauges) per churned job — Registry.drop_job on the
            # expunge path must take them with the rest (these bounded
            # jobs finish before a follower could mount, so the series
            # are minted directly like the audit ones above)
            from arroyo_tpu.metrics import (
                REPLICA_LAG_EPOCHS,
                REPLICA_SERVED_EPOCH,
                REPLICA_TAILS,
            )
            for j in range(n):
                REPLICA_TAILS.labels(job=f"{tag}{j}").inc()
                REPLICA_SERVED_EPOCH.labels(job=f"{tag}{j}").set(1.0)
                REPLICA_LAG_EPOCHS.labels(job=f"{tag}{j}").set(0.0)
            for j in range(n):
                await c.wait_for_state(
                    f"{tag}{j}", JobState.FINISHED, JobState.FAILED,
                    timeout=60,
                )
            await c.stop()

    asyncio.run(churn("warm", 1))  # register every family once
    # the warm job actually exercised the attribution families (they are
    # part of the baseline length being asserted below), and the serve
    # read minted job-labeled arroyo_serve_* series
    assert "arroyo_job_attributed_busy_seconds" in REGISTRY.expose()
    assert "arroyo_serve_requests_total" in REGISTRY.expose()
    assert "arroyo_audit_epochs_reconciled_total" in REGISTRY.expose()
    assert "arroyo_replica_tails_total" in REGISTRY.expose()
    baseline = len(REGISTRY.expose())
    asyncio.run(churn("gc", 6))
    after = len(REGISTRY.expose())
    # families/help text persist; per-job series must not accumulate
    assert after <= baseline * 1.25 + 2000, (baseline, after)
    # and the dropped jobs are really gone from the exposition — the
    # attributed families included
    text = REGISTRY.expose()
    assert 'job="gc0"' not in text and 'job="gc5"' not in text
    # the serve families are job-labeled too: Registry.drop_job took the
    # per-job serve series (request counts, cache hits) with the rest
    from arroyo_tpu.obs import audit
    for j in range(6):
        # spans of torn-down jobs no longer linger until ring overwrite
        assert obs.recorder().snapshot(trace_prefix=f"gc{j}/") == []
        assert timeline.snapshot(f"gc{j}") == []
        assert f"gc{j}" not in attribution.ACCOUNTING.summary()["jobs"]
        # the job's conservation reconciler went with it too
        assert audit.peek(f"gc{j}") is None


def _stub_admission(slots_per_worker=2, n_workers=2):
    from arroyo_tpu.controller.admission import AdmissionController

    workers = {
        i: SimpleNamespace(worker_id=i, slots=slots_per_worker,
                           pooled=True, last_heartbeat=time.monotonic())
        for i in range(n_workers)
    }
    ctl = SimpleNamespace(
        workers=workers,
        wheel=TimerWheel(),
        _pool_mode=lambda: True,
        _worker_stale=lambda w: False,
    )
    return AdmissionController(ctl), ctl


def _job(jid, tenant, par=2):
    return SimpleNamespace(
        job_id=jid, tenant=tenant,
        graph=SimpleNamespace(nodes={0: SimpleNamespace(parallelism=par)}),
    )


def test_admission_fair_share_and_quota():
    """Fair slot scheduling: grants go to the tenant holding the least,
    not to the longest-queued; a tenant at quota waits while others are
    admitted; queue timeouts surface as TimeoutError."""

    async def go():
        adm, ctl = _stub_admission()  # capacity 4
        ctl.wheel.start()
        try:
            await adm.acquire(_job("a1", "a"))   # holds 2
            await adm.acquire(_job("a2", "a"))   # holds 4 -> full
            assert adm.free_slots() == 0
            # tenant a queues FIRST, tenant b second
            qa = asyncio.ensure_future(adm.acquire(_job("a3", "a")))
            await asyncio.sleep(0.05)
            qb = asyncio.ensure_future(adm.acquire(_job("b1", "b")))
            await asyncio.sleep(0.05)
            assert not qa.done() and not qb.done()
            adm.release(_job("a1", "a"))  # 2 slots free
            await asyncio.sleep(0.05)
            # fair share: b (holding 0) wins over the earlier-queued a
            assert qb.done() and not qa.done()
            adm.release(_job("b1", "b"))
            await asyncio.sleep(0.05)
            assert qa.done()
            # quota: a tenant at tenant_quota_slots queues despite free
            with update(admission={"tenant_quota_slots": 2}):
                adm2, ctl2 = _stub_admission(slots_per_worker=4)
                ctl2.wheel.start()
                try:
                    await adm2.acquire(_job("q1", "a"))
                    assert adm2.free_slots() >= 2
                    blocked = asyncio.ensure_future(
                        adm2.acquire(_job("q2", "a"))
                    )
                    await asyncio.sleep(0.05)
                    assert not blocked.done()  # at quota
                    await adm2.acquire(_job("q3", "b"))  # other tenant ok
                    adm2.release(_job("q1", "a"))
                    await asyncio.sleep(0.05)
                    assert blocked.done()
                finally:
                    await ctl2.wheel.stop()
            # timeout: a job that never fits fails with TimeoutError
            with update(admission={"queue_timeout": 0.2}):
                adm3, ctl3 = _stub_admission()
                ctl3.wheel.start()
                try:
                    await adm3.acquire(_job("t1", "a", par=4))  # all slots
                    with pytest.raises(TimeoutError):
                        await adm3.acquire(_job("t2", "b", par=4))
                finally:
                    await ctl3.wheel.stop()
        finally:
            await ctl.wheel.stop()

    asyncio.run(go())


def test_admission_bootstrap_and_oversized():
    """Progress guarantees: the first job is admitted before any worker
    registered (capacity 0 — acquire precedes pool spawn), and a job
    larger than total capacity runs alone rather than wedging."""

    async def go():
        adm, ctl = _stub_admission(n_workers=0)
        ctl.wheel.start()
        try:
            await adm.acquire(_job("boot", "a", par=8))  # capacity 0
            assert "boot" in adm.held
        finally:
            await ctl.wheel.stop()
        adm2, ctl2 = _stub_admission()  # capacity 4
        ctl2.wheel.start()
        try:
            await adm2.acquire(_job("big", "a", par=64))
            assert adm2.held["big"][1] <= adm2.capacity()
        finally:
            await ctl2.wheel.stop()

    asyncio.run(go())


@pytest.mark.slow
def test_fleet_harness_200_jobs(tmp_path):
    """Slow tier: the churn harness at 200 concurrent jobs on one
    controller + 2-worker pool, exactly-once sample intact."""
    out = subprocess.run(
        [sys.executable, "tools/fleet_harness.py", "--jobs", "200",
         "--pool", "2", "--sample", "4", "--churn", "20",
         "--idle-seconds", "8", "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=800, cwd="/root/repo",
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["fleet_jobs_per_controller"] >= 200
    assert report["fleet_exactly_once_ok"] == 1
