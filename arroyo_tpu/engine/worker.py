"""Worker server: runs partitions of one or MANY jobs' subtasks.

Capability parity with the reference's WorkerServer
(/root/reference/crates/arroyo-worker/src/lib.rs:666-1197): registers with
the controller (RegisterWorkerReq), serves WorkerGrpc (StartExecution,
Checkpoint, Commit, StopExecution), heartbeats, streams task events
(checkpoint progress, finish/failure) back to the controller, and hosts the
TCP data plane endpoint for cross-worker edges.

Multi-tenancy (ROADMAP item 3): one worker process multiplexes subtasks
from MANY jobs onto one event loop and one JAX runtime — the Flink
slot-sharing shape (Carbone et al., 2015). Every job lives in its own
`_JobRuntime` namespace (program, runner tasks, response pump, control
queues, data-plane route namespace, leader state), so per-job teardown
(`StopJob`) cancels exactly that job's work and co-resident jobs never
notice. All WorkerGrpc methods are job-scoped via a `job_id` field; a
request without one resolves against a sole hosted job (dedicated-worker
compatibility).
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Dict, Optional

from .. import chaos, obs
from ..analysis.races import shared_state
from ..analysis.races.sanitizer import set_task_root
from ..config import config
from ..graph.logical import LogicalGraph
from ..operators.control import (
    CheckpointCompletedResp,
    CheckpointReport,
    CheckpointEventResp,
    CheckpointMsg,
    CommitMsg,
    StopMsg,
    TaskFailedResp,
    TaskFinishedResp,
)
from ..types import CheckpointBarrier, StopMode, now_nanos
from ..utils.logging import get_logger
from .network import DataPlaneServer
from .program import Program
from .rpc import RpcClient, RpcServer

logger = get_logger("worker")


# the runtime namespace is shared between the response pump, the
# leader cadence loop, RPC handlers (stage/tail/promote/stop), and
# teardown; the multi_writer entries are counters/latches whose
# individual updates are atomic between yields — RACE002 still polices
# stale read-modify-write across awaits on all of them
@shared_state(
    "lead_active", "leader_reports", "leader_epoch", "leader_published",
    "leader_durable", "standby_epoch", "torn_down", "resigned",
    # leader_epoch is written by the lead loop's checkpoint cadence and
    # by StartExecution's restore ("main" root) by design: the restore
    # happens before the lead loop is spawned for that generation.
    multi_writer=("lead_active", "leader_reports", "leader_published",
                  "leader_durable", "torn_down", "leader_epoch"),
)
class _JobRuntime:
    """One job's execution namespace inside a (possibly multiplexed)
    worker: the physical program, its runner tasks and response pump,
    the data-plane route namespace, and — in worker-leader mode — the
    job-control (cadence/manifest/2PC) state."""

    def __init__(self, job_id: str, program: Program, data_ns: str):
        self.job_id = job_id
        self.program = program
        self.data_ns = data_ns
        # generation-overlap rescale (ISSUE 15): a STAGED incarnation's
        # runners start immediately (building state, restoring from the
        # durable rescale checkpoint) but its sources park on this gate
        # until the controller promotes the incarnation — so restore
        # overlaps the old generation's drain without double emission
        self.release: Optional[asyncio.Event] = None
        # hot-standby failover (ISSUE 17): a standby incarnation restores
        # at arm time and is kept warm by tailing each published epoch's
        # delta chains; `standby_epoch` is the highest manifest epoch
        # applied so far
        self.standby = False
        self.standby_epoch = 0
        self.tasks: list = []
        self.pump_task: Optional[asyncio.Task] = None
        self.n_running = 0
        self.finished = asyncio.Event()
        self.torn_down = False
        self.assignments: Dict[tuple, int] = {}
        # worker-leader mode (reference job_controller/: the elected worker
        # runs the job-control loop — checkpoint cadence, manifest
        # assembly, 2PC — and peers forward checkpoint events to it)
        self.is_leader = False
        self.leader_client: Optional[RpcClient] = None
        self.worker_rpc_addrs: Dict[int, str] = {}
        self.leader_reports: Dict[int, Dict[str, dict]] = {}
        self.leader_epoch = 0
        self.lead_interval: Optional[float] = None
        self.lead_task = None
        self.n_total_subtasks = 0
        # set while no leader checkpoint is in flight: teardown must not
        # close the rpc server under an active leadership duty (peers are
        # still delivering reports, the manifest isn't published yet).
        # Counted, because a cancelled cadence checkpoint's cleanup must
        # not mark idle while a stop checkpoint is still running.
        self.lead_active = 0
        self.lead_idle = asyncio.Event()
        self.lead_idle.set()
        self.current_ck = None  # in-flight cadence checkpoint task
        self.leader_published = 0  # highest epoch published or abandoned
        self.leader_durable = 0  # highest epoch with a published manifest
        self.resigned = False


# staged incarnations are installed by the StageJob RPC, tailed by
# TailStaged, consumed by promote/stop/teardown paths running under
# other roots; dict ops are atomic between yields (multi_writer)
@shared_state("_staged", multi_writer=("_staged",))
class WorkerServer:
    def __init__(self, controller_addr: str, worker_id: Optional[int] = None,
                 bind: str = "127.0.0.1", pooled: bool = False):
        self.controller_addr = controller_addr
        if worker_id is None:
            worker_id = int(os.environ.get("ARROYO_WORKER_ID", os.getpid()))
        self.worker_id = worker_id
        self.bind = bind
        self.pooled = pooled
        self.rpc = RpcServer(bind)
        self.data = DataPlaneServer(bind)
        self.controller: Optional[RpcClient] = None
        self._jobs: Dict[str, _JobRuntime] = {}
        # staged incarnations awaiting promotion (generation-overlap
        # rescale): keyed by job id, coexisting with the live runtime of
        # the SAME job while the old generation drains its final epoch
        self._staged: Dict[str, _JobRuntime] = {}
        self._finished = asyncio.Event()  # worker-level shutdown signal
        self._peer_clients: Dict[int, RpcClient] = {}
        self._shutdown_task = None  # retained chaos-kill teardown task

    # -- job resolution ------------------------------------------------------

    def _job(self, req: dict) -> _JobRuntime:
        jid = req.get("job_id")
        if jid is not None:
            jr = self._jobs.get(jid)
            if jr is None:
                raise KeyError(
                    f"worker {self.worker_id} hosts no job {jid!r}"
                )
            return jr
        if len(self._jobs) == 1:  # dedicated-worker compatibility
            return next(iter(self._jobs.values()))
        raise KeyError(
            f"job_id required: worker {self.worker_id} hosts "
            f"{len(self._jobs)} jobs"
        )

    @property
    def program(self) -> Optional[Program]:
        """Sole hosted job's program (dedicated-worker compatibility)."""
        if len(self._jobs) == 1:
            return next(iter(self._jobs.values())).program
        return None

    # -- lifecycle ----------------------------------------------------------

    async def start(self):
        # honor a config-installed fault plan (ARROYO__CHAOS__PLAN reaches
        # spawned worker subprocesses through the config env layer)
        chaos.install_from_config()
        obs.set_role(f"worker-{self.worker_id}")
        # fleet observatory: the accounting pump rolls per-job attributed
        # cost into the arroyo_job_attributed_* families and samples
        # event-loop lag (refcounted — embedded workers share one loop).
        # The watchtower's PER-WORKER scrape rides the same cadence: each
        # pump interval offers this process's registry to the retained
        # metric-history tier (obs/history.py), so a worker's windowed
        # rates are inspectable locally via /debug/history even when the
        # controller runs in another process.
        obs.attribution.ensure_pump()
        self._pump_held = True
        self.rpc.add_service(
            "WorkerGrpc",
            {
                "StartExecution": self.start_execution,
                "StartProcessing": self.start_processing,
                "TailCheckpoint": self.tail_checkpoint,
                "Checkpoint": self.checkpoint,
                "Commit": self.commit,
                "LoadCompacted": self.load_compacted,
                "TaskCheckpointCompleted": self.task_checkpoint_completed,
                "CheckpointStop": self.checkpoint_stop,
                "StopExecution": self.stop_execution,
                "StopJob": self.stop_job_rpc,
                "GetMetrics": self.get_metrics,
                "QueryState": self.query_state,
            },
        )
        rpc_port = await self.rpc.start()
        data_port = await self.data.start()
        self.rpc_addr = f"{self.bind}:{rpc_port}"
        self.data_addr = f"{self.bind}:{data_port}"
        self.controller = RpcClient(self.controller_addr)
        await self.controller.call(
            "ControllerGrpc",
            "RegisterWorker",
            {
                "worker_id": self.worker_id,
                "rpc_addr": self.rpc_addr,
                "data_addr": self.data_addr,
                "slots": config().worker.task_slots,
                "pooled": self.pooled,
            },
        )
        from ..utils.admin import serve_admin

        self._admin, self.admin_port = await serve_admin(
            "worker",
            lambda: {
                "worker_id": self.worker_id,
                "pooled": self.pooled,
                "jobs": {
                    jid: jr.n_running for jid, jr in self._jobs.items()
                },
                "running_subtasks": sum(
                    jr.n_running for jr in self._jobs.values()
                ),
            },
        )
        self._hb = asyncio.ensure_future(self._heartbeat())
        logger.info(
            "worker %s up (rpc %s, data %s%s)", self.worker_id,
            self.rpc_addr, self.data_addr, ", pooled" if self.pooled else "",
        )
        return self

    async def _heartbeat(self):
        set_task_root("worker-heartbeat")
        while not self._finished.is_set():
            if chaos.fire("worker.kill", worker_id=self.worker_id):
                # SIGKILL-equivalent: tear everything down abruptly, no
                # goodbye to the controller — it must detect the death via
                # heartbeat timeout and recover from the last checkpoint.
                # In a shared pool this is the shared-fate mode: EVERY
                # job with subtasks here fails and recovers independently.
                logger.warning(
                    "chaos[worker.kill]: abrupt teardown of worker %s",
                    self.worker_id,
                )
                # retained on self: the loop holds only a weak reference,
                # and a GC'd shutdown task would leave the worker half-dead
                self._shutdown_task = asyncio.ensure_future(self.shutdown())
                return
            spec = chaos.fire("worker.heartbeat_blackout",
                              worker_id=self.worker_id)
            if spec is not None:
                logger.warning(
                    "chaos[worker.heartbeat_blackout]: worker %s silent "
                    "for %.1fs", self.worker_id, spec.param("duration", 3.0),
                )
                await asyncio.sleep(float(spec.param("duration", 3.0)))
            try:
                resp = await self.controller.call(
                    "ControllerGrpc", "Heartbeat",
                    {"worker_id": self.worker_id, "time": now_nanos()},
                )
                if resp.get("known") is False:
                    # the controller pruned us (stalled heartbeats read
                    # as death): re-register so the pool registry heals
                    logger.warning(
                        "worker %s unknown to controller; re-registering",
                        self.worker_id,
                    )
                    await self.controller.call(
                        "ControllerGrpc", "RegisterWorker",
                        {
                            "worker_id": self.worker_id,
                            "rpc_addr": self.rpc_addr,
                            "data_addr": self.data_addr,
                            "slots": config().worker.task_slots,
                            "pooled": self.pooled,
                        },
                    )
            except Exception as e:  # noqa: BLE001
                logger.warning("heartbeat failed: %s", e)
            await asyncio.sleep(config().worker.heartbeat_interval)

    # -- WorkerGrpc ---------------------------------------------------------

    async def start_execution(self, req: dict) -> dict:
        # nested under the rpc span of the controller's job.schedule trace
        # (when tracing is active): plan/build/restore stages become
        # visible, and a restore failure pinpoints its stage in the dump
        with obs.span("worker.start_execution", cat="worker",
                      worker=self.worker_id):
            return await self._start_execution_inner(req)

    async def _start_execution_inner(self, req: dict) -> dict:
        if req.get("sql"):
            from ..sql import plan_query

            graph = plan_query(
                req["sql"], parallelism=req.get("parallelism", 1)
            ).graph
            # rescale overrides: the controller's graph carries per-node
            # parallelism on top of the base plan; apply the same ones or
            # the shipped assignments won't match this worker's expansion
            overrides = req.get("parallelism_overrides") or {}
            if overrides:
                graph.update_parallelism(
                    {int(n): int(p) for n, p in overrides.items()}
                )
        else:
            graph = LogicalGraph.from_json(req["graph"])
        if req.get("mount"):
            # shared-plan tenant (ISSUE 16): swap the source op for the
            # `mounted` connector reading the shared bus — after the
            # re-plan, so the rewrite lands on the controller's node
            from ..sql.fingerprint import apply_mount

            apply_mount(graph, req["mount"])
        assignments = {
            (a["node_id"], a["subtask"]): a["worker_id"]
            for a in req["assignments"]
        }
        worker_addrs = {
            int(w): addr for w, addr in req["worker_data_addrs"].items()
        }
        job_id = req["job_id"]
        staged = bool(req.get("staged"))
        if staged:
            # generation-overlap rescale: the NEW incarnation builds and
            # restores beside the still-draining live runtime of the same
            # job (distinct data_ns — routes never collide). Only a
            # previous staged attempt is torn down.
            prev = self._staged.pop(job_id, None)
            if prev is not None:
                await self._teardown_job(prev, force=True)
        else:
            # a stale incarnation of the same job (recovery rescheduling
            # onto the same pool worker) must be gone before fresh routes
            # register
            stale = self._jobs.pop(job_id, None)
            if stale is not None:
                await self._teardown_job(stale, force=True)
        program = Program(graph, job_id)
        if req.get("storage_url"):
            from ..state.backend import StateBackend

            backend = StateBackend(req["storage_url"], job_id)
            backend.generation = req.get("generation")
            if req.get("restore_epoch") is not None:
                from ..state import protocol

                backend.restore_manifest = protocol.load_manifest(
                    backend.storage, backend.paths, req["restore_epoch"]
                )
            program.with_state(backend)
        data_ns = req.get("data_ns") or f"{job_id}@0"
        program.build(
            assignments=assignments,
            my_worker=self.worker_id,
            worker_addrs=worker_addrs,
            data_server=self.data,
            data_ns=data_ns,
        )
        jr = _JobRuntime(job_id, program, data_ns)
        jr.assignments = assignments
        jr.is_leader = bool(req.get("is_leader"))
        jr.worker_rpc_addrs = {
            int(w): a for w, a in (req.get("worker_rpc_addrs") or {}).items()
        }
        jr.lead_interval = req.get("checkpoint_interval")
        jr.n_total_subtasks = req.get("n_subtasks") or len(
            req["assignments"]
        )
        jr.leader_epoch = req.get("restore_epoch") or 0
        leader_addr = req.get("leader_addr")
        if leader_addr and not jr.is_leader:
            jr.leader_client = RpcClient(leader_addr)

        def pump_failed(quad, exc):
            program.control_resp.put_nowait(
                TaskFailedResp(
                    f"net-{quad[0]}-{quad[1]}", quad[0], quad[1],
                    f"data plane edge {quad} failed: {exc!r}",
                )
            )

        for rs in program.remote_senders:
            rs.on_error = pump_failed
            await rs.start()
        if staged:
            # staged start: runners spawn NOW — state tables open and the
            # restore from the durable rescale checkpoint runs while the
            # old generation drains — but every source parks on the
            # release gate until promotion, so nothing is emitted twice.
            # (Safe single-phase: no data can flow anywhere until the
            # gate opens, so peers' route registration cannot be raced.)
            jr.release = asyncio.Event()
            jr.standby = bool(req.get("standby"))
            jr.standby_epoch = int(req.get("restore_epoch") or 0)
            for sub in jr.program.subtasks:
                sub.runner.source_gate = jr.release
                if jr.standby:
                    # hot standby (ISSUE 17): restore runs at arm time but
                    # ALL on_start calls defer to promotion — the tables
                    # keep being tailed forward until then
                    sub.runner.standby_gate = jr.release
            self._staged[job_id] = jr
            for sub in jr.program.subtasks:
                jr.tasks.append(asyncio.ensure_future(sub.runner.run()))
            jr.n_running = len(jr.program.subtasks)
            jr.pump_task = asyncio.ensure_future(self._pump_responses(jr))
            return {"subtasks": len(program.subtasks), "staged": True}
        self._jobs[job_id] = jr
        return {"subtasks": len(program.subtasks)}

    async def tail_checkpoint(self, req: dict) -> dict:
        """Hot-standby tailing (ISSUE 17): replay a newly published
        epoch's delta-chain suffix onto the staged standby's open tables,
        keeping its restore within one epoch of the primary without a
        full re-restore."""
        jid = req.get("job_id")
        jr = self._staged.get(jid)
        if jr is None or not jr.standby:
            return {"tailed": False,
                    "error": f"no standby incarnation of job {jid!r}"}
        applied = await self._tail_staged(jr, int(req["epoch"]))
        return {"tailed": True, "epoch": jr.standby_epoch,
                "applied": applied}

    async def _tail_staged(self, jr: _JobRuntime, epoch: int) -> int:
        backend = jr.program._state_backend
        if backend is None or epoch <= jr.standby_epoch:
            return 0
        from ..state import protocol

        manifest = await asyncio.to_thread(
            protocol.load_manifest, backend.storage, backend.paths, epoch
        )
        if manifest is None:
            raise ValueError(f"no manifest at epoch {epoch} to tail")
        backend.restore_manifest = manifest
        applied = 0
        for sub in jr.program.subtasks:
            for ctx in sub.runner.ctxs:
                tm = getattr(ctx, "table_manager", None)
                if tm is not None and tm.tables:
                    applied += await asyncio.to_thread(tm.tail_chains)
        # concurrent tails (a TailStaged RPC racing a promote's final
        # tail) both pass the entry guard during the to_thread awaits: a
        # slower, older tail must not regress the high-water mark
        jr.standby_epoch = max(jr.standby_epoch, epoch)
        return applied

    async def start_processing(self, req: dict) -> dict:
        """Phase 2 of the barrier-synchronized start (reference
        Engine::start, engine.rs:525): runners only spawn once every worker
        has built its partition and registered its data-plane routes, so a
        fast source can't race peers' route registration.

        With `promote` (generation-overlap rescale), the staged
        incarnation — already running, restored, sources parked — replaces
        the live runtime of the job and its sources are released. A
        failover promotion (ISSUE 17) additionally ships the freshly
        claimed generation and a final tail target: the standby restored
        read-only under the PRIMARY's generation, so its backend must
        adopt the new one before any of its state writes land."""
        if req.get("promote"):
            jid = req.get("job_id")
            jr = self._staged.pop(jid, None)
            if jr is None:
                raise KeyError(
                    f"worker {self.worker_id} has no staged incarnation "
                    f"of job {jid!r} to promote"
                )
            backend = jr.program._state_backend
            if req.get("generation") is not None and backend is not None:
                backend.generation = req["generation"]
            if req.get("tail_epoch") is not None:
                # catch-up tail to the last published manifest; failure
                # here must leave the standby discardable, not half-live
                try:
                    await self._tail_staged(jr, int(req["tail_epoch"]))
                except Exception:
                    self._staged[jid] = jr
                    raise
            old = self._jobs.pop(jid, None)
            if old is not None:
                # the old generation should be drained by now; force for
                # stragglers — generation fencing makes that safe
                await self._teardown_job(old, force=True)
            self._jobs[jid] = jr
            jr.release.set()
            return {"promoted": True, "epoch": jr.standby_epoch}
        jr = self._job(req)
        for sub in jr.program.subtasks:
            jr.tasks.append(asyncio.ensure_future(sub.runner.run()))
        jr.n_running = len(jr.program.subtasks)
        jr.pump_task = asyncio.ensure_future(self._pump_responses(jr))
        if jr.is_leader and jr.lead_interval is not None:
            jr.lead_task = asyncio.ensure_future(self._lead_loop(jr))
        return {}

    async def checkpoint(self, req: dict) -> dict:
        spec = chaos.fire("worker.slow_barrier_ack",
                          worker_id=self.worker_id, epoch=req.get("epoch"))
        if spec is not None:
            # stretch barrier alignment: peers' barriers race ahead while
            # this worker's sources delay injecting theirs
            await asyncio.sleep(float(spec.param("delay", 0.5)))
        jr = self._job(req)
        # flight recorder: the barrier inherits the epoch trace from the
        # controller's rpc (ambient context) and carries it in-band
        with obs.span("worker.checkpoint", cat="worker",
                      worker=self.worker_id, epoch=req["epoch"]) as sp:
            barrier = CheckpointBarrier(
                epoch=req["epoch"], min_epoch=req.get("min_epoch", 0),
                timestamp=now_nanos(), then_stop=req.get("then_stop", False),
                trace_id=sp.trace_id, span_id=sp.span_id,
            )
            for sub in jr.program.source_subtasks():
                sub.control_rx.put_nowait(CheckpointMsg(barrier))
        return {}

    async def commit(self, req: dict) -> dict:
        jr = self._job(req)
        data: Dict[int, dict] = {}
        for node_id, subs in (req.get("committing") or {}).items():
            data[int(node_id)] = {"data": {int(s): v for s, v in subs.items()}}
        ctx = obs.current()
        msg = CommitMsg(req["epoch"], data)
        if ctx is not None:
            # phase-2 commits ride the control queue; attach the rpc's
            # trace so sink commit spans join the epoch tree
            msg.trace_id, msg.span_id = ctx
        for sub in jr.program.subtasks:
            sub.control_rx.put_nowait(msg)
        return {}

    async def load_compacted(self, req: dict) -> dict:
        """Swap an operator table's file references for a compacted file
        (controller-driven compaction; reference LoadCompacted control)."""
        jr = self._jobs.get(req.get("job_id")) if req.get("job_id") else (
            next(iter(self._jobs.values())) if len(self._jobs) == 1 else None
        )
        if jr is not None:
            jr.program.send_load_compacted(req)
        return {}

    async def stop_execution(self, req: dict) -> dict:
        jr = self._job(req)
        mode = StopMode(req.get("mode", "graceful"))
        targets = (
            jr.program.source_subtasks()
            if mode == StopMode.GRACEFUL
            else jr.program.subtasks
        )
        for sub in targets:
            sub.control_rx.put_nowait(StopMsg(mode))
        return {}

    async def stop_job_rpc(self, req: dict) -> dict:
        """Per-job teardown on a shared worker: cancel exactly this job's
        runners/pump/senders, unregister its data-plane routes, and (on
        `expunge` — terminal job states) drop its metric series. Jobs
        co-resident on this worker are untouched. Idempotent."""
        jid = req.get("job_id")
        if req.get("staged_only"):
            # discard a standby/staged incarnation WITHOUT touching the
            # live runtime of the same job (failover discard on a worker
            # hosting both)
            staged = self._staged.pop(jid, None)
            if staged is not None:
                await self._teardown_job(staged, force=True)
            return {"hosted": staged is not None}
        jr = self._jobs.pop(jid, None)
        if jr is not None:
            await self._teardown_job(jr, force=bool(req.get("force", True)))
        staged = self._staged.pop(jid, None)
        if staged is not None:
            # an un-promoted staged incarnation dies with the job: it
            # restored read-only and claimed nothing durable
            await self._teardown_job(staged, force=True)
        if req.get("expunge"):
            from ..metrics import REGISTRY

            ttl = float(config().cluster.metrics_ttl or 0)
            if ttl <= 0:
                REGISTRY.drop_job(jid)
                obs.expunge_job(jid)
            else:
                # grace window: UIs read a just-finished job's metric
                # groups; the series drop lands after they could have.
                # The observatory expunge (trace ring, timeline ledger,
                # attribution accumulators) rides the same deadline —
                # the attributed families carry a job label and fall to
                # drop_job, the span/phase rings need their own sweep.
                loop = asyncio.get_event_loop()
                loop.call_later(ttl, REGISTRY.drop_job, jid)
                loop.call_later(ttl, obs.expunge_job, jid)
        return {"hosted": jr is not None}

    async def _teardown_job(self, jr: _JobRuntime, force: bool = True):
        """Cancel one job runtime's work and release its resources. The
        route namespace is unregistered FIRST so a straggler frame of
        this incarnation can never land in queues a restarted incarnation
        is about to register."""
        if jr.torn_down:
            return
        jr.torn_down = True
        self.data.unregister_ns(jr.data_ns)
        for t in jr.tasks:
            t.cancel()
        for attr in ("pump_task", "lead_task", "current_ck"):
            t = getattr(jr, attr, None)
            if t is not None:
                t.cancel()
        await asyncio.gather(*jr.tasks, return_exceptions=True)
        if jr.pump_task is not None:
            await asyncio.gather(jr.pump_task, return_exceptions=True)
        for rs in jr.program.remote_senders:
            if rs.task is not None:
                rs.task.cancel()
            if rs.writer is not None:
                rs.writer.close()
        if jr.leader_client is not None:
            await jr.leader_client.close()
        jr.finished.set()

    async def query_state(self, req: dict) -> dict:
        """StateServe read handler (ISSUE 12): answer point / bulk /
        table-listing lookups against this worker's live serve views —
        synchronous dict work on the event loop, nothing blocks the
        batch path. Incarnation-fenced: a request carrying a data_ns of
        a torn-down incarnation (rescale/recovery raced the gateway's
        routing) answers `stale_route` instead of serving state a fresh
        generation may be superseding."""
        jid = req.get("job_id")
        jr = self._jobs.get(jid) if jid is not None else (
            next(iter(self._jobs.values())) if len(self._jobs) == 1
            else None
        )
        if jr is None or jr.torn_down:
            return {"error": f"stale_route: worker {self.worker_id} "
                             f"hosts no live job {jid!r}",
                    "retriable": True}
        ns = req.get("data_ns")
        if ns and ns != jr.data_ns:
            return {"error": f"stale_route: {ns} != {jr.data_ns}",
                    "retriable": True}
        from ..serve import worker_read

        return worker_read(jr.program, req)

    async def get_metrics(self, req: dict) -> dict:
        from ..metrics import REGISTRY

        # `snapshot` is the structured view the autoscaler samples each
        # control period (msgpack-clean: dicts/lists/numbers); the
        # prometheus text stays for scrapers and debugging
        return {
            "prometheus": REGISTRY.expose(),
            "snapshot": REGISTRY.snapshot(),
        }

    # -- worker-leader job control ------------------------------------------

    async def task_checkpoint_completed(self, req: dict) -> dict:
        """Leader intake: a peer subtask finished its checkpoint. A
        resigned leader relays to the controller (which took the cadence)
        instead of swallowing the report."""
        jr = self._job(req)
        if jr.resigned:
            await self.controller.call(
                "ControllerGrpc", "TaskCheckpointCompleted", req
            )
        else:
            self._leader_intake(jr, req)
        return {}

    async def checkpoint_stop(self, req: dict) -> dict:
        """Leader: run a stop-with-checkpoint cadence (controller's stop
        path in worker-leader mode). An in-flight cadence checkpoint runs
        to completion first — cancelling it mid barrier fan-out would
        interleave two epochs' barriers in the pipeline."""
        jr = self._job(req)
        if jr.lead_task is not None:
            jr.lead_task.cancel()
        ck = jr.current_ck
        if ck is not None:
            await asyncio.gather(ck, return_exceptions=True)
        await self._lead_checkpoint(jr, then_stop=True)
        # report only durable progress: an incomplete/timed-out stop
        # checkpoint must not advance the controller's epoch bookkeeping
        return {"epoch": jr.leader_durable}

    def _leader_intake(self, jr: _JobRuntime, d: dict):
        # conservation ledger: recovery checks run BEFORE the stale drop —
        # a re-emitted epoch behind the published one is exactly what the
        # drop would silently discard, and silence is what we're auditing
        if d.get("audit") is not None and obs.audit.reconciler(
            jr.job_id
        ).intake(
            d["task_id"], d["epoch"], d["audit"],
            jr.leader_published or None,
        ):
            return
        # late reports for epochs already published/abandoned would leak
        if d["epoch"] <= jr.leader_published:
            return
        jr.leader_reports.setdefault(d["epoch"], {})[d["task_id"]] = d

    def _evict_reports(self, jr: _JobRuntime, up_to_epoch: int):
        """Drop report state for epochs <= up_to_epoch (published, timed
        out, or abandoned) so stragglers can't grow memory unboundedly."""
        jr.leader_published = max(jr.leader_published, up_to_epoch)
        for e in [e for e in jr.leader_reports if e <= up_to_epoch]:
            del jr.leader_reports[e]

    def _peer(self, jr: _JobRuntime, wid: int) -> RpcClient:
        if wid not in self._peer_clients:
            self._peer_clients[wid] = RpcClient(jr.worker_rpc_addrs[wid])
        return self._peer_clients[wid]

    async def _lead_loop(self, jr: _JobRuntime):
        set_task_root(f"lead:{jr.job_id}")
        try:
            while not jr.finished.is_set():
                await asyncio.sleep(jr.lead_interval)
                if jr.finished.is_set() or jr.n_running <= 0:
                    return
                # shielded: a CheckpointStop cancels THIS loop but must let
                # the in-flight checkpoint finish (it reaps current_ck)
                jr.current_ck = asyncio.ensure_future(
                    self._lead_checkpoint(jr, then_stop=False)
                )
                try:
                    await asyncio.shield(jr.current_ck)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001
                    # one failed checkpoint (peer rpc blip, publish error)
                    # must not kill the cadence; the next tick retries
                    logger.exception("leader checkpoint failed; continuing")
        except asyncio.CancelledError:
            pass
        except Exception:  # noqa: BLE001
            logger.exception("leader checkpoint loop failed")

    async def _lead_checkpoint(self, jr: _JobRuntime, then_stop: bool) -> int:
        """One full checkpoint driven by the leader worker: barrier fan-out,
        report collection, manifest publish, 2PC commit, compaction + GC
        (reference WorkerJobController, job_controller/controller.rs)."""
        backend = jr.program._state_backend
        if backend is None:
            return 0
        jr.lead_active += 1
        jr.lead_idle.clear()
        try:
            return await self._lead_checkpoint_inner(jr, then_stop, backend)
        finally:
            jr.lead_active -= 1
            if jr.lead_active == 0:
                jr.lead_idle.set()

    async def _lead_checkpoint_inner(self, jr: _JobRuntime, then_stop: bool,
                                     backend) -> int:
        jr.leader_epoch += 1
        epoch = jr.leader_epoch
        # worker-leader mode mints the epoch trace here — same tree shape
        # as the controller-driven cadence, rooted in the leader's process
        with obs.span(
            "checkpoint", trace=obs.new_trace(jr.job_id, f"ck-{epoch}"),
            cat="controller", job=jr.job_id, epoch=epoch,
            leader=self.worker_id, then_stop=then_stop,
        ):
            return await self._lead_checkpoint_run(jr, epoch, then_stop,
                                                   backend)

    async def _lead_checkpoint_run(self, jr: _JobRuntime, epoch: int,
                                   then_stop: bool, backend) -> int:
        for wid in jr.worker_rpc_addrs:
            payload = {"job_id": jr.job_id, "epoch": epoch,
                       "then_stop": then_stop}
            if wid == self.worker_id:
                await self.checkpoint(payload)
            else:
                await self._peer(jr, wid).call(
                    "WorkerGrpc", "Checkpoint", payload
                )
        deadline = time.monotonic() + 60
        last_progress = time.monotonic()
        seen = 0
        while len(jr.leader_reports.get(epoch, {})) < jr.n_total_subtasks:
            n = len(jr.leader_reports.get(epoch, {}))
            if n > seen:
                seen, last_progress = n, time.monotonic()
            if time.monotonic() > deadline:
                logger.warning("leader: checkpoint %d incomplete", epoch)
                self._evict_reports(jr, epoch)
                return epoch
            if jr.n_running <= 0 and not then_stop:
                logger.info("leader: checkpoint %d abandoned (job finished)",
                            epoch)
                self._evict_reports(jr, epoch)
                return epoch
            if (then_stop and jr.finished.is_set()
                    and time.monotonic() - last_progress > 5.0):
                # leader's own tasks finished and can't report; remaining
                # peers stalled too — don't hold the stop for 60s
                logger.warning(
                    "leader: stop checkpoint %d abandoned (no report "
                    "progress after local finish)", epoch,
                )
                self._evict_reports(jr, epoch)
                return epoch
            await asyncio.sleep(0.02)
        reports = jr.leader_reports.pop(epoch)
        self._evict_reports(jr, epoch)
        manifest = backend.publish_checkpoint(
            epoch, {tid: CheckpointReport(r) for tid, r in reports.items()}
        )
        # conservation ledger: join the epoch's sealed attestations now
        # that every task reported — same point the controller path uses
        audits = {tid: r.get("audit") for tid, r in reports.items()}
        if any(a is not None for a in audits.values()):
            obs.audit.reconciler(jr.job_id).reconcile(epoch, audits)
        jr.leader_durable = epoch
        committing = manifest.get("committing")
        if committing and backend.claim_commit(epoch):
            # same worker targeting as the controller path: only peers
            # hosting committing subtasks get the phase-2 fan-out
            commit_workers = {
                wid for (nid, _sub), wid in jr.assignments.items()
                if str(nid) in committing
            }
            for wid in jr.worker_rpc_addrs:
                if wid not in commit_workers:
                    continue
                payload = {"job_id": jr.job_id, "epoch": epoch,
                           "committing": committing}
                if wid == self.worker_id:
                    await self.commit(payload)
                else:
                    await self._peer(jr, wid).call(
                        "WorkerGrpc", "Commit", payload
                    )
        swaps = await asyncio.to_thread(backend.compact_epoch, epoch, manifest)
        for swap in swaps:
            for wid in jr.worker_rpc_addrs:
                if wid == self.worker_id:
                    jr.program.send_load_compacted(swap)
                else:
                    try:
                        await self._peer(jr, wid).call(
                            "WorkerGrpc", "LoadCompacted",
                            {**swap, "job_id": jr.job_id},
                        )
                    except Exception as e:  # noqa: BLE001
                        logger.warning("LoadCompacted to %s failed: %s",
                                       wid, e)
        await asyncio.to_thread(backend.retire_unreferenced)
        try:
            await self.controller.call(
                "ControllerGrpc", "LeaderCheckpointFinished",
                {"worker_id": self.worker_id, "job_id": jr.job_id,
                 "epoch": epoch},
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("leader checkpoint report failed: %s", e)
        return epoch

    # -- task event forwarding ---------------------------------------------

    async def _pump_responses(self, jr: _JobRuntime):
        set_task_root(f"pump:{jr.job_id}")
        q = jr.program.control_resp
        while jr.n_running > 0:
            resp = await q.get()
            try:
                await self._forward(jr, resp)
            except Exception as e:  # noqa: BLE001
                logger.warning("event forward failed: %s", e)
        jr.finished.set()
        if not self.pooled and all(
            j.finished.is_set() for j in self._jobs.values()
        ):
            self._finished.set()
        if jr.is_leader:
            # local work ended; resign leadership so the controller takes
            # over the checkpoint cadence for any still-running peers. Wait
            # out an in-flight leader checkpoint first: resigning mid-epoch
            # would let the controller drive the same epoch concurrently.
            if jr.lead_task is not None:
                jr.lead_task.cancel()
            await jr.lead_idle.wait()
            jr.resigned = True
            try:
                await self.controller.call(
                    "ControllerGrpc", "LeaderResigned",
                    {"worker_id": self.worker_id, "job_id": jr.job_id,
                     "epoch": jr.leader_epoch},
                )
            except Exception as e:  # noqa: BLE001
                logger.warning("leader resignation failed: %s", e)
        await self.controller.call(
            "ControllerGrpc", "WorkerFinished",
            {"worker_id": self.worker_id, "job_id": jr.job_id},
        )

    async def _forward(self, jr: _JobRuntime, resp):
        c = self.controller
        wid = self.worker_id
        if jr.standby and not jr.release.is_set():
            # a PARKED standby's task events must never reach the primary
            # incarnation's controller bookkeeping (same job id!): a
            # standby restore failure is a failover-manager concern, not
            # a job failure
            if isinstance(resp, TaskFailedResp):
                jr.n_running -= 1
                await c.call(
                    "ControllerGrpc", "StandbyTaskFailed",
                    {"worker_id": wid, "job_id": jr.job_id,
                     "task_id": resp.task_id, "error": resp.error},
                )
            else:
                logger.warning(
                    "dropping %s from parked standby of job %s",
                    type(resp).__name__, jr.job_id,
                )
            return
        if isinstance(resp, CheckpointCompletedResp):
            # conservation ledger: stamp the report's attestations with
            # this runtime's data-plane generation — the reconciler's
            # zombie check compares incarnations across reports
            audit_payload = (
                dict(resp.audit, gen=jr.data_ns)
                if resp.audit is not None else None
            )
            payload = {
                "worker_id": wid,
                "job_id": jr.job_id,
                "task_id": resp.task_id,
                "node_id": resp.node_id,
                "subtask": resp.subtask_index,
                "epoch": resp.epoch,
                "metadata": resp.subtask_metadata,
                "watermark": resp.watermark,
                "commit_data": resp.commit_data,
                "audit": audit_payload,
            }
            reports = [payload]
            # mutation seams (tests/test_audit_mutations.py): re-emit a
            # strictly-stale epoch's report (a source rewound behind
            # committed output)...
            spec = chaos.fire("audit.rewind_epoch", job=jr.job_id,
                              task=resp.task_id, epoch=resp.epoch)
            if spec is not None and resp.epoch > 1:
                back = max(1, int(spec.param("back", 2)))
                reports.append(
                    dict(payload, epoch=max(1, resp.epoch - back))
                )
            # ...or append a report stamped with an already-fenced
            # generation for the NEXT epoch — a zombie incarnation
            # appending a new epoch past its fencing. (An old-generation
            # straggler redelivering an already-published epoch is benign
            # and fenced silently; writing an epoch it does not own is
            # the breach.) The real report stays intact so the epoch
            # still assembles.
            spec = chaos.fire("audit.zombie_append", job=jr.job_id,
                              task=resp.task_id, epoch=resp.epoch)
            if spec is not None and audit_payload is not None:
                try:
                    cur = int(jr.data_ns.rsplit("@", 1)[1])
                except (IndexError, ValueError):
                    cur = 0
                stale_gen = str(spec.param("gen", f"{jr.job_id}@{cur - 1}"))
                reports.append(
                    dict(payload, epoch=resp.epoch + 1,
                         audit=dict(audit_payload, gen=stale_gen))
                )
            # worker-leader mode: checkpoint reports go to the job leader
            # (who assembles the manifest), not the controller. If the
            # leader resigned (its local work ended), fall back to the
            # controller, which takes over the cadence. Known degradation:
            # a TRANSIENT leader rpc failure also diverts this report, so
            # that epoch waits out its deadline unpublished — the next
            # cadence tick retries with a fresh epoch.
            for report in reports:
                if jr.is_leader:
                    self._leader_intake(jr, report)
                elif jr.leader_client is not None:
                    try:
                        await jr.leader_client.call(
                            "WorkerGrpc", "TaskCheckpointCompleted", report
                        )
                    except Exception:  # noqa: BLE001
                        await c.call(
                            "ControllerGrpc", "TaskCheckpointCompleted",
                            report,
                        )
                else:
                    await c.call(
                        "ControllerGrpc", "TaskCheckpointCompleted", report
                    )
        elif isinstance(resp, CheckpointEventResp):
            await c.call(
                "ControllerGrpc", "TaskCheckpointEvent",
                {
                    "worker_id": wid, "job_id": jr.job_id,
                    "task_id": resp.task_id,
                    "epoch": resp.epoch, "event": resp.event,
                },
            )
        elif isinstance(resp, TaskFinishedResp):
            jr.n_running -= 1
            await c.call(
                "ControllerGrpc", "TaskFinished",
                {"worker_id": wid, "job_id": jr.job_id,
                 "task_id": resp.task_id,
                 "source_drained": getattr(resp, "source_drained", None),
                 "source_drain_detail": getattr(
                     resp, "source_drain_detail", ""),
                },
            )
        elif isinstance(resp, TaskFailedResp):
            jr.n_running -= 1
            await c.call(
                "ControllerGrpc", "TaskFailed",
                {"worker_id": wid, "job_id": jr.job_id,
                 "task_id": resp.task_id, "error": resp.error},
            )

    async def shutdown(self):
        """Force teardown: cancel every job's tasks and close
        servers/clients so a force-stopped embedded worker leaves no
        heartbeats or runners behind. Idempotent: a chaos-killed worker is
        shut down again by the recovery teardown."""
        if getattr(self, "_shutdown_started", False):
            return
        self._shutdown_started = True
        self._finished.set()
        if getattr(self, "_pump_held", False):
            self._pump_held = False
            obs.attribution.release_pump()
        for jr in list(self._jobs.values()):
            await self._teardown_job(jr, force=True)
        self._jobs.clear()
        for jr in list(self._staged.values()):
            await self._teardown_job(jr, force=True)
        self._staged.clear()
        t = getattr(self, "_hb", None)
        if t is not None:
            t.cancel()
        if self.controller is not None:
            await self.controller.close()
        for c in self._peer_clients.values():
            await c.close()
        if getattr(self, "_admin", None) is not None:
            await self._admin.cleanup()
        await self.rpc.stop(grace=0.1)
        await self.data.stop()

    async def run_until_finished(self):
        """Dedicated-worker lifecycle: serve until the hosted job's local
        work ends, then tear down (the process/embedded per-job mode)."""
        await self._finished.wait()
        for jr in self._jobs.values():
            await asyncio.gather(*jr.tasks, return_exceptions=True)
            # a leader must finish its in-flight checkpoint (peer reports
            # are still arriving over this worker's rpc server) first
            await jr.lead_idle.wait()
        if getattr(self, "_pump_held", False):
            self._pump_held = False
            obs.attribution.release_pump()
        self._hb.cancel()
        await asyncio.gather(self._hb, return_exceptions=True)
        await self.controller.close()
        await self.rpc.stop()
        await self.data.stop()

    async def serve_forever(self):
        """Pooled-worker lifecycle: serve jobs until shut down (the pool
        owner — scheduler or process signal — ends the worker, never job
        completion)."""
        await self._finished.wait()


async def worker_main(controller_addr: str):
    pooled = os.environ.get("ARROYO_WORKER_POOLED") == "1"
    w = WorkerServer(controller_addr, pooled=pooled)
    await w.start()
    if pooled:
        await w.serve_forever()
    else:
        await w.run_until_finished()
