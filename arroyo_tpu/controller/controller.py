"""Controller server: job lifecycle, scheduling, checkpoint cadence, 2PC.

Capability parity with the reference's controller
(/root/reference/crates/arroyo-controller/src/lib.rs:547-706 +
src/job_controller/): hosts ControllerGrpc (worker registration,
heartbeats, task/checkpoint events), drives each job's state machine
(Scheduling: compute slots, round-robin TaskAssignments, StartExecution to
every worker — scheduling.rs:65-100; Running: periodic checkpoints,
manifest assembly + publication through the generation protocol, phase-2
commits — job_controller/controller.rs; failure handling: task errors and
heartbeat timeouts escalate to Recovering, which tears the job down and
reschedules from the latest durable checkpoint — states/recovering.rs).

Multi-tenant control plane (ROADMAP item 3): the per-job drivers are
EVENT-DRIVEN — every wait (cadence, report sets, task finishes, state
watches) parks on the job's kick list and is woken by the RPC arrival
that changes its predicate, with ONE coarse `TimerWheel` arming the
deadline side (checkpoint cadence, heartbeat expiry horizons, epoch
deadlines). Idle controller cost is therefore ~O(changed jobs), not
O(jobs) x 50 Hz poll loops. Jobs schedule onto a SHARED pooled worker
set (scheduler.multiplexing_active) through admission control + fair
slot scheduling (controller/admission.py), and RPC dispatch is
job-id-keyed (O(1) per event, not an O(jobs) ownership scan).
"""

from __future__ import annotations

import asyncio
import heapq
import json
import time
from typing import Dict, List, Optional

from .. import chaos, obs
from ..obs import audit, timeline
from ..analysis.model.effects import protocol_effect
from ..analysis.races import shared_state
from ..analysis.races.sanitizer import set_task_root
from ..config import config
from ..graph.logical import LogicalGraph
from ..state.backend import StateBackend
from ..types import now_nanos
from ..utils.logging import get_logger
from ..engine.rpc import RpcClient, RpcServer
from ..operators.control import CheckpointReport
from .admission import AdmissionController
from .scheduler import Scheduler, make_scheduler, multiplexing_active
from .state_machine import JobState, check_transition

logger = get_logger("controller")


class TimerWheel:
    """The controller's single coarse deadline scheduler: every parked
    wait registers its absolute deadline here and ONE task sleeps until
    the earliest, so a thousand parked jobs cost one pending timer
    instead of a thousand 50 Hz poll loops. Deadlines are quantized up to
    `granularity` so near-simultaneous deadlines coalesce into one
    wakeup."""

    def __init__(self, granularity: float = 0.05):
        self.granularity = granularity
        self._heap: list = []  # (deadline, seq, future)
        self._seq = 0
        self._dirty: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None

    def start(self):
        self._dirty = asyncio.Event()
        self._task = asyncio.ensure_future(self._loop())

    async def stop(self):
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None

    def at(self, deadline: float, fut: asyncio.Future):
        g = self.granularity
        deadline = ((deadline // g) + 1) * g  # quantize up: coalesce
        heapq.heappush(self._heap, (deadline, self._seq, fut))
        self._seq += 1
        if len(self._heap) > 4096:
            # futures resolved by kicks before their deadline linger in
            # the heap; sweep once it grows past any plausible live set
            self._heap = [e for e in self._heap if not e[2].done()]
            heapq.heapify(self._heap)
        if self._dirty is not None:
            self._dirty.set()

    async def _loop(self):
        set_task_root("timer-wheel")
        while True:
            now = time.monotonic()
            while self._heap and (self._heap[0][0] <= now
                                  or self._heap[0][2].done()):
                _, _, fut = heapq.heappop(self._heap)
                if not fut.done():
                    fut.set_result(False)  # deadline wake (vs kick=True)
            if self._heap:
                delay = max(self._heap[0][0] - time.monotonic(), 0.0)
                try:
                    await asyncio.wait_for(self._dirty.wait(), delay)
                except asyncio.TimeoutError:
                    pass
                self._dirty.clear()
            else:
                await self._dirty.wait()
                self._dirty.clear()


class NodeHandle:
    """A registered node daemon offering worker slots."""

    def __init__(self, node_id: str, addr: str, slots: int):
        self.node_id = node_id
        self.addr = addr
        self.slots = slots
        self.used = 0
        self.client = RpcClient(addr)


# last_heartbeat is a mailbox: the heartbeat RPC handler stamps it, the
# failover manager's monitor loop reads it, and recovery paths reset it —
# last-writer-wins is the design (multi_writer), but RACE002 still
# forbids restoring a stale copy across an await (PR 10's stampede bug)
@shared_state("last_heartbeat", multi_writer=("last_heartbeat",))
class WorkerHandle:
    def __init__(self, worker_id: int, rpc_addr: str, data_addr: str,
                 slots: int, pooled: bool = False):
        self.worker_id = worker_id
        self.rpc_addr = rpc_addr
        self.data_addr = data_addr
        self.slots = slots
        self.pooled = pooled
        self.last_heartbeat = time.monotonic()
        self.client = RpcClient(rpc_addr)
        self.job_id: Optional[str] = None  # dedicated-worker assignment
        # pooled placement bookkeeping: job_id -> subtasks hosted here
        self.assigned: Dict[str, int] = {}


# The job handle is the rendezvous of every control-plane task root: the
# per-job drive loop, RPC handlers (stop/rescale/report arrivals), the
# failover manager, the checkpoint flush chain, and the sharing manager
# all mutate it between each other's awaits. Fields declared here are
# what the RACE00x rules and the interleaving sanitizer police; the
# multi_writer list is the explicit last-writer-wins policy (RACE001) —
# it does NOT license stale read-modify-write across awaits (RACE002).
@shared_state(
    "stop_requested", "failure", "pending_epochs", "finished_tasks",
    "undrained_sources", "published_epoch", "leader_resigned",
    "rescale_requested", "checkpoint_asap",
    # finished_tasks / undrained_sources / published_epoch are mutated
    # both by the drive task and by RPC report handlers ("main" root) by
    # design: set/dict ops are atomic between yields and published_epoch
    # only moves via monotonic max-merge.
    multi_writer=("stop_requested", "failure", "leader_resigned",
                  "rescale_requested", "checkpoint_asap",
                  "finished_tasks", "undrained_sources",
                  "published_epoch"),
)
class JobHandle:
    def __init__(self, job_id: str, graph: LogicalGraph,
                 storage_url: Optional[str], sql: Optional[str] = None,
                 parallelism: int = 1, tenant: str = "default"):
        self.job_id = job_id
        self.graph = graph
        self.sql = sql  # canonical program: workers re-plan deterministically
        self.parallelism = parallelism
        self.storage_url = storage_url
        self.tenant = tenant
        self.state = JobState.CREATED
        self.backend: Optional[StateBackend] = None
        self.workers: List[WorkerHandle] = []
        self.assignments: Dict[tuple, int] = {}
        self.epoch = 0
        # last epoch whose manifest PUBLISHED (or restored from): the
        # serving tier's read snapshot level — reads never observe a
        # fanned-out-but-unpublished epoch (StateServe, ISSUE 12)
        self.published_epoch = 0
        self.n_subtasks = sum(n.parallelism for n in graph.nodes.values())
        # autoscale/rescale state: per-node parallelism overrides applied
        # on top of the base plan (shipped to workers so their SQL re-plan
        # matches this graph), a pending rescale request ({node: target},
        # actuated by the state-machine driver), the decision audit log,
        # and the pin that freezes automatic actuation
        self.parallelism_overrides: Dict[int, int] = {}
        self.rescale_requested: Optional[Dict[int, int]] = None
        self.rescale_trace: Optional[tuple] = None
        self.rescales = 0
        self.autoscale_pinned = False
        self.autoscale_decisions: List[dict] = []
        # epoch -> {task_id: report}
        self.checkpoints: Dict[int, Dict[str, dict]] = {}
        # pipelined checkpoint accounting (ROADMAP item 4): epochs whose
        # barrier is fanned out but whose manifest isn't published yet —
        # {epoch: {"deadline", "trace"}}. Completions may arrive >1
        # epoch late (workers keep state.max_inflight_flushes uploads in
        # flight); manifests still publish strictly in epoch order.
        self.pending_epochs: Dict[int, dict] = {}
        self.finished_tasks: set = set()
        # bounded sources that reported FINAL completion WITHOUT having
        # drained their assigned range (task_id -> detail): the controller
        # refuses to FINISH over these — a truncated source run must
        # recover, not masquerade as success (carried robustness bug:
        # chaos kill loops turned "prefix of the output" into FINISHED)
        self.undrained_sources: Dict[str, str] = {}
        self.failure: Optional[str] = None
        self.stop_requested: Optional[str] = None
        self.restarts = 0
        self.schedules = 0  # StartExecution rounds (data-plane namespace)
        # hot-standby failover (ISSUE 17): promotions of a warm standby
        # generation in place of a cold recovery reschedule
        self.promotions = 0
        self.events: List[dict] = []
        # worker-leader mode: the leader finished its local work and handed
        # the checkpoint cadence back to the controller
        self.leader_resigned = False
        # shared-plan multi-tenancy (ISSUE 16): the scan fingerprint this
        # job is mounted on (None = owns its data plane), the mount
        # directive shipped to workers ({node_id, fingerprint,
        # connector} — sql/fingerprint.py apply_mount), and the
        # accelerated-cadence flag the sharing manager sets while a host
        # epoch is gated on this tenant's next durable checkpoint
        self.shared_fp: Optional[str] = None
        self.mount: Optional[dict] = None
        self.checkpoint_asap = False
        # event-driven driver: parked waits register a future here and
        # every RPC arrival / state change that can move this job's
        # predicates kicks them. `wakeups` counts predicate-loop wakeups —
        # the fleet harness and the parked-job regression test read it (a
        # parked RUNNING job must sit at ZERO over a poll interval).
        # `kicks` is the generation of those events: a predicate loop
        # reads it BEFORE it evaluates its predicates and hands it to
        # `wait_kick`, so an event that arrived while the loop was
        # awaiting something else (no wait parked) is not lost.
        self._waiters: set = set()
        self.kicks = 0
        self.wakeups = 0

    def kick(self):
        """Wake every parked wait of this job (an event arrived)."""
        self.kicks += 1
        for fut in list(self._waiters):
            if not fut.done():
                fut.set_result(True)

    async def wait_kick(self, wheel: TimerWheel, timeout: Optional[float],
                        seen: int) -> bool:
        """Park until kicked or until the coarse deadline passes. Returns
        True when kicked (state possibly changed), False on deadline.
        `seen` is `kicks` as the caller read it before evaluating the
        predicates it is about to park on: a kick since then returns at
        once."""
        if seen != self.kicks:
            self.wakeups += 1
            return True
        fut = asyncio.get_event_loop().create_future()
        self._waiters.add(fut)
        if timeout is not None:
            wheel.at(time.monotonic() + max(timeout, 0.0), fut)
        try:
            kicked = await fut
        finally:
            self._waiters.discard(fut)
        self.wakeups += 1
        return kicked

    def apply_parallelism_overrides(self, overrides: Dict[int, int]) -> None:
        """Fold per-node targets into the job's graph and bookkeeping.
        The overrides accumulate (a second rescale layers on the first)
        and ride the StartExecution request, so workers re-planning from
        canonical SQL reach the identical physical graph."""
        self.parallelism_overrides.update(overrides)
        self.graph.update_parallelism(overrides)
        self.n_subtasks = sum(
            n.parallelism for n in self.graph.nodes.values()
        )

    def transition(self, nxt: JobState):
        check_transition(self.state, nxt)
        logger.info("job %s: %s -> %s", self.job_id, self.state.value,
                    nxt.value)
        self.events.append(
            {"time": now_nanos(), "from": self.state.value, "to": nxt.value}
        )
        self.state = nxt
        self.kick()  # state watchers (wait_for_state) park on the job


# registration waiters and the benched-worker registry are touched by
# the registration RPC handler, release paths inside per-job drive
# loops, and TimerWheel deadline kicks; individual dict/set ops are
# atomic between yields, so multi_writer is the declared policy
@shared_state("_benched", "_reg_waiters",
              multi_writer=("_benched", "_reg_waiters"))
class ControllerServer:
    def __init__(self, scheduler: Optional[Scheduler] = None,
                 bind: str = "127.0.0.1", max_restarts: int = 3):
        self.scheduler = scheduler or make_scheduler(
            config().controller.scheduler
        )
        self.rpc = RpcServer(bind)
        self.bind = bind
        self.workers: Dict[int, WorkerHandle] = {}
        self.nodes: Dict[str, "NodeHandle"] = {}
        self.jobs: Dict[str, JobHandle] = {}
        self.max_restarts = max_restarts
        self._job_tasks: Dict[str, asyncio.Task] = {}
        self.wheel = TimerWheel()
        self.admission = AdmissionController(self)
        # StateServe gateway (ISSUE 12): the queryable-state read path —
        # key-routed worker fan-out, epoch-invalidated cache, per-tenant
        # read admission. REST state routes and /debug/serve read it.
        from ..serve.gateway import StateGateway

        self.serve = StateGateway(self)
        # shared-plan multi-tenancy (ISSUE 16): mount-vs-spawn admission,
        # refcounted host lifecycle, publication gate
        from .sharing import SharingManager

        self.sharing = SharingManager(self)
        # hot-standby failover (ISSUE 17): warm standby generations per
        # durable job + sub-second promotion on heartbeat loss
        from ..failover import StandbyManager

        self.failover = StandbyManager(self)
        # follower read replicas (ISSUE 20): controller-hosted serving
        # tier tailing each durable job's published delta chains — the
        # gateway routes reads follower-first, worker fan-out becomes
        # the fallback
        from ..replica import ReplicaManager

        self.replicas = ReplicaManager(self)
        self._reg_waiters: set = set()  # scheduling waits on registration
        # handles pruned on suspicion of death, kept so a heartbeat
        # re-registration can resurrect the SAME object — jobs hold
        # handle references, and a fresh object would leave them reading
        # a permanently stale liveness view
        self._benched: Dict[int, WorkerHandle] = {}

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> "ControllerServer":
        chaos.install_from_config()
        obs.set_role("controller")
        self.rpc.add_service(
            "ControllerGrpc",
            {
                "RegisterWorker": self._register_worker,
                "Heartbeat": self._heartbeat,
                "TaskCheckpointEvent": self._task_checkpoint_event,
                "TaskCheckpointCompleted": self._task_checkpoint_completed,
                "TaskFinished": self._task_finished,
                "TaskFailed": self._task_failed,
                "WorkerFinished": self._worker_finished,
                "LeaderCheckpointFinished": self._leader_checkpoint_finished,
                "LeaderResigned": self._leader_resigned,
                "StandbyTaskFailed": self._standby_task_failed,
                "RegisterNode": self._register_node,
            },
        )
        port = await self.rpc.start()
        self.addr = f"{self.bind}:{port}"
        self.wheel.start()
        # schedulers that place onto registered resources need the registry
        self.scheduler.controller = self
        # closed-loop autoscaler (autoscale.enabled gates the loop; the
        # object always exists so REST/debug surfaces can report status)
        from ..autoscale import Autoscaler

        self.autoscaler = Autoscaler(self)
        self.autoscaler.maybe_start()
        # watchtower (ISSUE 13): the retained-history scrape pump + the
        # per-job SLO engine with its alert ledger and diagnostic-bundle
        # spool (watch.enabled gates the loop; the object always exists
        # so REST/debug surfaces can report status)
        from ..obs.watchtower import Watchtower

        self.watchtower = Watchtower(self)
        self.watchtower.maybe_start()
        from ..utils.admin import serve_admin

        self._admin, self.admin_port = await serve_admin(
            "controller",
            lambda: {
                "workers": len(self.workers),
                "pool_workers": len(self._live_pool_workers()),
                "admission": self.admission.status(),
                "jobs": {j.job_id: j.state.value for j in self.jobs.values()},
            },
            extra_routes={
                "/debug/autoscale": self._debug_autoscale,
                "/debug/serve": self._debug_serve,
                "/debug/watch": self._debug_watch,
                "/debug/sharing": self._debug_sharing,
                "/debug/failover": self._debug_failover,
                "/debug/replica": self._debug_replica,
                "/debug/audit": self._debug_audit,
            },
        )
        logger.info("controller up at %s", self.addr)
        return self

    async def _debug_serve(self, request):
        """Admin surface: serve-gateway status (cache occupancy, tenant
        quotas + noisy flags, slowest read over the decaying
        serve.slow_read_window); `?job=<id>` adds the job's table
        registry, published epoch and per-view occupancy (with the rows
        staged and the rows materialised), `?clear=1` empties the
        slow-read window after reporting it."""
        from aiohttp import web

        doc = self.serve.status()
        if request.query.get("clear"):
            self.serve.clear_slow()
            doc["slow_read_cleared"] = True
        jid = request.query.get("job")
        if jid and jid in self.jobs:
            job = self.jobs[jid]
            doc["job"] = {
                "id": jid,
                "state": job.state.value,
                "published_epoch": job.published_epoch,
                "schedules": job.schedules,
                "tables": await self.serve.tables(jid),
                "views": await self.serve.view_stats(jid),
            }
        return web.json_response(
            doc, dumps=lambda d: json.dumps(d, default=str)
        )

    async def _debug_audit(self, request):
        """Admin surface: the conservation ledger — every live job's
        reconciler status (per-edge attestations, flow checks, breach
        records). `?job=<id>` narrows to one job's reconciler."""
        from aiohttp import web

        return web.json_response(
            audit.status(request.query.get("job")),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def _debug_autoscale(self, request):
        """Admin surface: the autoscaler's per-job decision audit log."""
        from aiohttp import web

        return web.json_response(
            self.autoscaler.status(),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def _debug_watch(self, request):
        """Admin surface: watchtower status — history-tier stats, the
        resolved rule table, non-ok alert states, the recent ledger and
        the bundle index. `?job=<id>` narrows alerts/ledger/bundles to
        one job."""
        from aiohttp import web

        return web.json_response(
            self.watchtower.status(request.query.get("job")),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def _debug_sharing(self, request):
        """Admin surface: shared-plan mounts — per-fingerprint host job,
        refcount, tenants, and the bus's retained-log/subscriber view."""
        from aiohttp import web

        return web.json_response(
            self.sharing.status(),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def _debug_failover(self, request):
        """Admin surface: hot-standby state — armed standbys with their
        tailed epochs, promotion count, active grace windows, and the
        task-local chain cache's occupancy."""
        from aiohttp import web

        return web.json_response(
            self.failover.status(),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def _debug_replica(self, request):
        """Admin surface: follower read-replica state — per-follower
        mounts with served epochs and view sizes, job assignments, kill
        count, and in-flight subscribes/tails."""
        from aiohttp import web

        return web.json_response(
            self.replicas.status(),
            dumps=lambda d: json.dumps(d, default=str),
        )

    async def stop(self):
        if getattr(self, "watchtower", None) is not None:
            await self.watchtower.stop()
        if getattr(self, "autoscaler", None) is not None:
            await self.autoscaler.stop()
        for t in self._job_tasks.values():
            t.cancel()
        await asyncio.gather(*self._job_tasks.values(),
                             return_exceptions=True)
        # tear down workers of any job still live: a controller stopping
        # over a running job must not strand worker servers (an
        # un-shut-down grpc server hangs interpreter exit joining its
        # poller thread from the completion queue's finalizer)
        for job in list(self.jobs.values()):
            try:
                await self._release_job(job, force=True)
            except Exception as e:  # noqa: BLE001 - teardown best effort
                logger.debug("release_job(%s) at controller stop: %s",
                             job.job_id, e)
        await self.scheduler.shutdown()
        for w in self.workers.values():
            await w.client.close()
        for job in self.jobs.values():
            for w in job.workers:
                await w.client.close()
        for n in self.nodes.values():
            await n.client.close()
        if getattr(self, "_admin", None) is not None:
            await self._admin.cleanup()
        await self.wheel.stop()
        await self.rpc.stop()

    # -- ControllerGrpc -----------------------------------------------------

    def _kick_registration(self):
        for fut in list(self._reg_waiters):
            if not fut.done():
                fut.set_result(True)

    async def _register_node(self, req: dict) -> dict:
        """A node daemon offers worker slots (reference node scheduler)."""
        n = NodeHandle(req["node_id"], req["addr"], req.get("slots", 1))
        self.nodes[n.node_id] = n
        logger.info("node %s registered (%s, %d slots)", n.node_id, n.addr,
                    n.slots)
        return {}

    async def _register_worker(self, req: dict) -> dict:
        cur = self.workers.get(req["worker_id"])
        benched = self._benched.get(req["worker_id"])
        if cur is not None and cur.rpc_addr == req["rpc_addr"]:
            # re-registration of a live handle (heartbeat self-heal):
            # refresh in place so jobs holding this handle keep a live
            # liveness view instead of reading a stale replacement
            cur.last_heartbeat = time.monotonic()
        elif benched is not None and benched.rpc_addr == req["rpc_addr"]:
            # a pruned-but-alive worker came back: resurrect the SAME
            # handle object — jobs still holding it heal instantly
            benched.last_heartbeat = time.monotonic()
            self.workers[benched.worker_id] = benched
            del self._benched[benched.worker_id]
        else:
            w = WorkerHandle(req["worker_id"], req["rpc_addr"],
                             req["data_addr"], req.get("slots", 1),
                             pooled=bool(req.get("pooled")))
            self.workers[w.worker_id] = w
            logger.info("worker %s registered (%s%s)", w.worker_id,
                        w.rpc_addr, ", pooled" if w.pooled else "")
        self._kick_registration()
        self.admission.pump()  # fresh capacity may admit queued jobs
        return {}

    async def _heartbeat(self, req: dict) -> dict:
        w = self.workers.get(req["worker_id"])
        if w is not None:
            # monotonic merge: _worker_call's liveness refresh races this
            # from the drive roots; a max keeps the newest evidence
            w.last_heartbeat = max(w.last_heartbeat, time.monotonic())
        # `known=False` tells a live worker it was pruned (a loop stall
        # can age heartbeats past the timeout and a recovery then drops
        # the handle); the worker re-registers and the registry
        # self-heals instead of wedging scheduling forever
        return {"known": w is not None}

    def _req_job(self, req: dict) -> Optional[JobHandle]:
        """O(1) job resolution from the event's job_id (workers stamp
        every task event). Falls back to the legacy O(jobs) worker-
        membership scan for payloads without one."""
        jid = req.get("job_id")
        if jid is not None:
            return self.jobs.get(jid)
        for job in self.jobs.values():
            if any(w.worker_id == req.get("worker_id")
                   for w in job.workers):
                return job
        return None

    async def _task_checkpoint_event(self, req: dict) -> dict:
        return {}

    async def _task_checkpoint_completed(self, req: dict) -> dict:
        job = self._req_job(req)
        if job is not None:
            # conservation ledger: recovery checks (rewind behind the
            # published epoch, zombie-generation append) run at intake,
            # and a flagged/stale report is FENCED out of the epoch's
            # bookkeeping instead of folded into a manifest
            if req.get("audit") is not None and audit.reconciler(
                job.job_id
            ).intake(
                req["task_id"], req["epoch"], req["audit"],
                job.published_epoch or None,
            ):
                return {}
            job.checkpoints.setdefault(req["epoch"], {})[req["task_id"]] = req
            job.kick()
        return {}

    async def _task_finished(self, req: dict) -> dict:
        job = self._req_job(req)
        if job is not None:
            job.finished_tasks.add(req["task_id"])
            if req.get("source_drained") is False:
                # a bounded source claims completion without having
                # emitted its full assigned range: record it — the run
                # loop refuses to FINISH the job over truncated output
                job.undrained_sources[req["task_id"]] = str(
                    req.get("source_drain_detail") or "undrained"
                )
            job.kick()
        return {}

    async def _task_failed(self, req: dict) -> dict:
        job = self._req_job(req)
        if job is not None:
            if job.failure is None:
                job.failure = f"{req['task_id']}: {req['error']}"
            job.kick()
        return {}

    async def _standby_task_failed(self, req: dict) -> dict:
        """A PARKED standby runner failed (restore error, local fault):
        strictly a failover-manager concern — the primary incarnation of
        the job is untouched."""
        self.failover.on_standby_task_failed(
            req.get("job_id"), str(req.get("error"))
        )
        return {}

    async def _worker_finished(self, req: dict) -> dict:
        return {}

    async def _leader_checkpoint_finished(self, req: dict) -> dict:
        """Worker-leader mode: the leader published a checkpoint manifest;
        track the epoch for observability and stop/restore bookkeeping."""
        job = self._req_job(req)
        if job is not None:
            job.epoch = max(job.epoch, req["epoch"])
            # worker-leader mode publishes manifests on the leader; this
            # report is the controller's (and the serving tier's) only
            # view of publication progress
            job.published_epoch = max(job.published_epoch, req["epoch"])
            # follower replicas tail off publication regardless of who
            # publishes — worker-leader jobs get the same serving tier
            self.replicas.note_publish(job)
            job.kick()
        return {}

    async def _leader_resigned(self, req: dict) -> dict:
        """The job leader's local work ended before the job did: the
        controller takes the checkpoint cadence back (workers fall back to
        forwarding reports here when the leader stops answering)."""
        job = self._req_job(req)
        if job is not None:
            job.leader_resigned = True
            # skip past every epoch the leader ISSUED (published or
            # not) so controller-driven barriers never reuse one
            job.epoch = max(job.epoch, req.get("epoch", 0))
            job.kick()
        return {}

    # -- job API ------------------------------------------------------------

    async def submit_job(
        self,
        job_id: str,
        sql: Optional[str] = None,
        graph: Optional[LogicalGraph] = None,
        storage_url: Optional[str] = None,
        n_workers: int = 1,
        parallelism: int = 1,
        tenant: str = "default",
    ) -> JobHandle:
        """Submit by SQL (workers re-plan the canonical text — the moral
        equivalent of shipping the reference's ArrowProgram proto) or by a
        pre-built LogicalGraph (single-process/embedded paths)."""
        if graph is None:
            from ..sql import plan_query

            plan = plan_query(sql, parallelism=parallelism)
            graph = plan.graph
            # what each source table sends of what it declares (leaf
            # fields; less where the planner narrowed it, sql/pruning.py)
            for table, (kept, declared) in plan.source_fields.items():
                timeline.note("plan.prune", 0.0, job=job_id, task=table,
                              n=kept, padded=declared)
            logger.info(
                "job %s planned: %d nodes at parallelism %d; source fields "
                "sent of declared: %s", job_id, len(graph.nodes), parallelism,
                ", ".join(f"{t} {k}/{d}"
                          for t, (k, d) in plan.source_fields.items()),
            )
        # shared-plan admission (ISSUE 16): an eligible scan mounts onto
        # the shared host instead of spawning a copy. The mount directive
        # rides StartExecution so workers re-planning the canonical SQL
        # apply the identical source rewrite.
        mount = self.sharing.try_mount(job_id, graph)
        # a fresh submission is a NEW job even when the id is reused (a
        # re-created pipeline, a drill phase, a test): drop any stale
        # conservation reconciler so its incarnation fencing and published
        # horizon don't outlive the job that earned them
        audit.expunge_job(job_id)
        job = JobHandle(job_id, graph, storage_url, sql=sql,
                        parallelism=parallelism, tenant=tenant)
        job.mount = mount
        job.shared_fp = mount["fingerprint"] if mount else None
        self.jobs[job_id] = job
        self._job_tasks[job_id] = asyncio.ensure_future(
            self._drive_job(job, n_workers)
        )
        return job

    async def stop_job(self, job_id: str, mode: str = "checkpoint"):
        job = self.jobs[job_id]
        job.stop_requested = mode
        job.kick()

    async def rescale_job(self, job_id: str, overrides: Dict[int, int]):
        """Request an exactly-once rescale of a running durable job to the
        given per-node parallelism targets (the autoscaler's actuation
        entry; also usable directly). The state-machine driver picks the
        request up: stop-with-checkpoint, apply overrides, reschedule,
        restore with key-range re-read."""
        job = self.jobs[job_id]
        if job.backend is None:
            raise ValueError(
                f"job {job_id} has no durable state; rescaling would drop "
                "its progress"
            )
        overrides = {int(n): int(p) for n, p in overrides.items()}
        for nid, p in overrides.items():
            if nid not in job.graph.nodes:
                raise ValueError(f"unknown node {nid} in rescale request")
            if p < 1:
                raise ValueError(f"parallelism must be >= 1 (node {nid})")
        job.rescale_requested = overrides
        job.kick()

    async def wait_for_state(self, job_id: str, *states: JobState,
                             timeout: float = 120.0):
        deadline = time.monotonic() + timeout
        job = self.jobs[job_id]
        while job.state not in states:
            seen = job.kicks
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"job {job_id} stuck in {job.state} waiting for {states}"
                )
            # parked on the job's kick list: transition() wakes us, the
            # wheel bounds the wait — zero wakeups while nothing changes
            await job.wait_kick(self.wheel, remaining, seen)
        return job.state

    # -- worker pool --------------------------------------------------------

    @staticmethod
    async def _worker_call(w: WorkerHandle, service: str, method: str,
                           payload: dict, timeout: float = 30.0) -> dict:
        """Worker rpc + liveness refresh: a successful rpc is evidence at
        least as strong as a heartbeat. Under event-loop stalls (mass
        recovery on a small host) heartbeats age past the timeout while
        real rpcs keep succeeding — without this, spurious timeouts
        stampede every co-scheduled job into recovery at once."""
        resp = await w.client.call(service, method, payload,
                                   timeout=timeout)
        # monotonic merge (see _heartbeat): never regress fresher evidence
        w.last_heartbeat = max(w.last_heartbeat, time.monotonic())
        return resp

    def _pool_mode(self) -> bool:
        return multiplexing_active(getattr(self.scheduler, "kind", ""))

    def _worker_stale(self, w: WorkerHandle) -> bool:
        timeout = config().controller.heartbeat_timeout
        return time.monotonic() - w.last_heartbeat > timeout

    def _live_pool_workers(self) -> List[WorkerHandle]:
        return [w for w in self.workers.values()
                if w.pooled and not self._worker_stale(w)]

    def _pick_pool_workers(self, n_workers: int) -> List[WorkerHandle]:
        """Least-loaded placement over the live pool: spread jobs by
        currently assigned subtask counts (ties by id for determinism)."""
        live = sorted(
            self._live_pool_workers(),
            key=lambda w: (sum(w.assigned.values()), w.worker_id),
        )
        return live[:n_workers]

    async def _wait_registration(self, predicate, timeout: float = 30.0):
        deadline = time.monotonic() + timeout
        while not predicate():
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("workers did not register in time")
            fut = asyncio.get_event_loop().create_future()
            self._reg_waiters.add(fut)
            # liveness (heartbeat staleness) can change without an event:
            # re-check at least once a second
            self.wheel.at(time.monotonic() + min(remaining, 1.0), fut)
            try:
                await fut
            finally:
                self._reg_waiters.discard(fut)

    async def _release_job(self, job: JobHandle, force: bool = False,
                           expunge: bool = False):
        """Release a job's workers. Pooled workers get a per-job StopJob
        teardown (co-resident jobs keep running, dead workers are pruned
        from the registry for the scheduler to replace); dedicated
        workers are stopped through the scheduler as before. `expunge`
        (terminal states) additionally drops the job's metric series and
        returns its admission slots."""
        if self._pool_mode() and any(w.pooled for w in job.workers):
            for w in job.workers:
                w.assigned.pop(job.job_id, None)
                stale = self._worker_stale(w)
                if stale and w.worker_id in self.workers:
                    # dead pool worker: prune it; the scheduler's next
                    # ensure-pool pass (any job's (re)schedule) replaces
                    # it. Benched, not discarded: a loop stall can make a
                    # LIVE worker look dead, and its next heartbeat
                    # resurrects this same handle.
                    if self.workers.pop(w.worker_id, None) is not None:
                        self._benched[w.worker_id] = w
                try:
                    # StopJob goes to PRESUMED-DEAD workers too: a
                    # pruned-but-alive worker (stalled heartbeats) would
                    # otherwise keep running a ZOMBIE incarnation of this
                    # job — cancelled nowhere, racing the restarted
                    # incarnation's sink files. A truly dead worker's rpc
                    # fails fast (connection refused).
                    await self._worker_call(
                        w, "WorkerGrpc", "StopJob",
                        {"job_id": job.job_id, "force": True,
                         "expunge": expunge},
                        timeout=5.0 if stale else 30.0,
                    )
                except Exception as e:  # noqa: BLE001 - worker may be dying
                    logger.warning("StopJob(%s) on worker %s failed: %s",
                                   job.job_id, w.worker_id, e)
            await self.scheduler.stop_workers(job.job_id, force=force)
        else:
            await self.scheduler.stop_workers(job.job_id, force=force)
        if expunge:
            # failover (ISSUE 17): standby workers are usually NOT in
            # job.workers, so the StopJob loop above misses them — tear
            # the staged incarnation down explicitly and drop the
            # per-job promotion bookkeeping
            await self.failover.discard(job)
            self.failover.on_job_expunged(job.job_id)
            # follower replicas (ISSUE 20): a terminal job unmounts from
            # its follower; the job-labeled arroyo_replica_* series ride
            # the drop_job below
            self.replicas.detach(job.job_id)
            self.replicas.on_job_expunged(job.job_id)
            # shared-plan detach (ISSUE 16): a terminal tenant releases
            # its mount (the LAST one stops the host); a terminal host
            # drops its bus channel
            await self.sharing.on_job_expunged(job)
            self.admission.release(job)
            # serving-tier GC: cached reads and routing state of a
            # terminal job go NOW (reads already refuse non-RUNNING
            # jobs; the job-labeled arroyo_serve_* series ride the
            # drop_job below)
            self.serve.expunge_job(job.job_id)
            # watchtower GC: a released job's alert state machines go
            # with it (ledger events and captured bundles stay — they
            # are diagnostics of the past, bounded by their own caps);
            # its retained history series ride obs.expunge_job below
            if getattr(self, "watchtower", None) is not None:
                self.watchtower.expunge_job(job.job_id)
            from ..metrics import REGISTRY

            # cardinality GC: a churned fleet must not grow /metrics
            # forever — drop the terminal job's series in this process
            # (pooled worker processes dropped theirs via StopJob
            # expunge), after a grace window for UIs reading the
            # just-finished job's metric groups
            from .. import obs

            ttl = float(config().cluster.metrics_ttl or 0)
            if ttl <= 0:
                REGISTRY.drop_job(job.job_id)
                obs.expunge_job(job.job_id)
            else:
                loop = asyncio.get_event_loop()
                loop.call_later(ttl, REGISTRY.drop_job, job.job_id)
                # the observatory sweep (trace-ring spans, timeline
                # phase instants, attribution accumulators) rides the
                # same grace window as the metric series drop
                loop.call_later(ttl, obs.expunge_job, job.job_id)

    # -- state machine driver ----------------------------------------------

    async def _drive_job(self, job: JobHandle, n_workers: int):
        set_task_root(f"drive:{job.job_id}")
        try:
            while not job.state.is_terminal():
                if job.state == JobState.CREATED:
                    job.transition(JobState.SCHEDULING)
                elif job.state == JobState.SCHEDULING:
                    await self._schedule(job, n_workers)
                elif job.state == JobState.RUNNING:
                    await self._run(job)
                elif job.state == JobState.RESCALING:
                    await self._rescale(job)
                elif job.state == JobState.RECOVERING:
                    await self._recover(job, n_workers)
                else:
                    break
        except Exception:
            logger.exception("job %s driver crashed", job.job_id)
            job.failure = job.failure or "driver crashed"
            if not job.state.is_terminal():
                job.transition(JobState.FAILED)
                await self._release_job(job, force=True, expunge=True)

    async def _schedule(self, job: JobHandle, n_workers: int):
        """reference scheduling.rs:65-100. Worker-facing failures (a
        worker dying between registration and StartExecution, a
        registration timeout) are retryable: they route through
        Recovering — bounded by max_restarts — instead of crashing the
        job driver into FAILED."""
        # one lifecycle trace per (re)schedule: StartExecution rpc
        # spans, worker build + state-restore spans nest under it, so
        # a failed restore pinpoints its stage in the flight recording.
        # A rescale-triggered schedule parents into the {job}/rescale-N
        # trace instead, completing its decide -> stop-checkpoint ->
        # reschedule -> restore tree.
        trace = obs.new_trace(job.job_id, f"schedule-{job.restarts}")
        parent = None
        if job.rescale_trace is not None:
            trace, parent = job.rescale_trace
        try:
            with obs.span(
                "job.schedule", trace=trace, parent=parent,
                cat="controller", job=job.job_id, restarts=job.restarts,
            ):
                await self._schedule_inner(job, n_workers)
        except Exception as e:  # noqa: BLE001 - scheduling is retryable
            logger.warning("job %s scheduling failed: %r", job.job_id, e)
            job.failure = f"scheduling failed: {e!r}"
            job.transition(JobState.RECOVERING)
        finally:
            job.rescale_trace = None

    @protocol_effect("ctrl.schedule")
    async def _schedule_inner(self, job: JobHandle, n_workers: int):
        if job.storage_url and job.backend is None:
            job.backend = StateBackend(job.storage_url, job.job_id).initialize()
        pool = self._pool_mode()
        if pool:
            # admission control + fair slot scheduling: the job waits its
            # fair-share turn for pool slots (tenant quotas apply); a
            # recovery reschedule keeps the grant it already holds
            await self.admission.acquire(job)
        await self.scheduler.start_workers(self.addr, n_workers, job.job_id)
        if pool:
            await self._wait_registration(
                lambda: len(self._live_pool_workers()) >= n_workers
            )
            job.workers = self._pick_pool_workers(n_workers)
        else:
            await self._wait_registration(
                lambda: len(self._free_workers()) >= n_workers
            )
            job.workers = self._free_workers()[:n_workers]
            for w in job.workers:
                w.job_id = job.job_id
        # round-robin subtask assignment
        job.assignments, counts = self._assign_subtasks(job, job.workers)
        if pool:
            for w in job.workers:
                w.assigned[job.job_id] = counts.get(w.worker_id, 0)
        job.checkpoints.clear()
        job.pending_epochs.clear()
        job.finished_tasks.clear()
        job.undrained_sources.clear()
        job.failure = None
        job.leader_resigned = False
        job.schedules += 1
        req = self._start_request(job, job.workers, job.assignments)
        if job.backend and job.backend.restore_epoch:
            job.epoch = job.backend.restore_epoch
            # the restore manifest IS the last published state: reads
            # resume at it the moment the job is RUNNING again
            job.published_epoch = job.backend.restore_epoch
        # worker-leader mode: the first worker runs the job-control loop
        # (checkpoint cadence, manifests, 2PC); the controller only
        # supervises scheduling/recovery/stop (reference JobControllerMode)
        leader_mode = (
            config().controller.job_controller_mode == "worker"
            and job.backend is not None
        )
        if leader_mode:
            req["leader_addr"] = job.workers[0].rpc_addr
            req["worker_rpc_addrs"] = {
                str(w.worker_id): w.rpc_addr for w in job.workers
            }
            req["checkpoint_interval"] = (
                config().pipeline.checkpointing.interval
            )
            req["n_subtasks"] = len(job.assignments)
        for w in job.workers:
            try:
                await self._worker_call(
                    w, "WorkerGrpc", "StartExecution",
                    {**req, "is_leader": leader_mode and w is job.workers[0]},
                )
            except Exception:
                # a worker refusing StartExecution is dead or wedged, but
                # its handle can still look heartbeat-fresh (a chaos kill
                # lands between beats): age it out NOW so the recovery
                # retry prunes + replaces it instead of re-picking the
                # same corpse until the restart budget burns out. A live
                # worker's next heartbeat un-ages it.
                w.last_heartbeat = float("-inf")
                raise
        # all partitions built + routes registered: release the sources
        for w in job.workers:
            try:
                await self._worker_call(w, "WorkerGrpc", "StartProcessing",
                                        {"job_id": job.job_id})
            except Exception:
                w.last_heartbeat = float("-inf")
                raise
        job.transition(JobState.RUNNING)

    @staticmethod
    def _assign_subtasks(job: JobHandle, workers) -> tuple:
        """Round-robin subtask assignment over `workers`: returns
        (assignments, per-worker subtask counts). Pure — callers decide
        when the result becomes the job's live assignment (the overlap
        rescale computes the NEW incarnation's map while the old one is
        still running on the current map)."""
        assignments: Dict[tuple, int] = {}
        wi = 0
        for node in job.graph.topo_order():
            for i in range(node.parallelism):
                assignments[(node.node_id, i)] = (
                    workers[wi % len(workers)].worker_id
                )
                wi += 1
        counts: Dict[int, int] = {}
        for (_nid, _sub), wid in assignments.items():
            counts[wid] = counts.get(wid, 0) + 1
        return assignments, counts

    @staticmethod
    def _start_request(job: JobHandle, workers, assignments: Dict[tuple, int]) -> dict:
        """The StartExecution payload for one incarnation of the job
        (shared by the schedule path and the overlap rescale's staged
        start)."""
        return {
            "job_id": job.job_id,
            "sql": job.sql,
            "parallelism": job.parallelism,
            # rescale overrides layered on the base plan: workers re-plan
            # canonical SQL at `parallelism`, then apply these, landing on
            # this controller's exact graph (assignments must agree)
            "parallelism_overrides": {
                str(n): p for n, p in job.parallelism_overrides.items()
            },
            "graph": None if job.sql else job.graph.to_json(),
            # shared-plan mount directive (ISSUE 16): applied after the
            # worker's re-plan (deterministic node ids make it land on
            # the same source node the controller rewrote)
            "mount": job.mount,
            "assignments": [
                {"node_id": n, "subtask": s, "worker_id": w}
                for (n, s), w in assignments.items()
            ],
            "worker_data_addrs": {
                str(w.worker_id): w.data_addr for w in workers
            },
            "storage_url": job.storage_url,
            "generation": job.backend.generation if job.backend else None,
            "restore_epoch": job.backend.restore_epoch if job.backend else None,
            # route namespace: quads collide across multiplexed jobs, and
            # the schedule counter fences straggler connections of a
            # torn-down incarnation of this same job
            "data_ns": f"{job.job_id}@{job.schedules}",
        }

    def _heartbeat_horizon(self, job: JobHandle) -> float:
        """Earliest monotonic instant a worker of this job COULD be
        declared dead — the deadline the timer wheel arms for liveness
        re-checks (heartbeat arrivals push it forward without kicking)."""
        timeout = config().controller.heartbeat_timeout
        beats = [
            w.last_heartbeat for w in job.workers
            if not (job.leader_resigned and w is job.workers[0])
        ]
        if not beats:
            return time.monotonic() + timeout
        return min(beats) + timeout

    @protocol_effect("ctrl.run_cadence")
    async def _run(self, job: JobHandle):
        """Checkpoint cadence + completion/failure watching
        (reference job_controller/controller.rs:292-551). Event-driven:
        each pass runs the same predicate checks the 50 Hz poll loop ran,
        then parks until a task event kicks the job or the earliest
        deadline (cadence due, heartbeat horizon, epoch deadline) fires
        on the shared timer wheel."""
        cfg = config()
        interval = cfg.pipeline.checkpointing.interval
        leader_mode = cfg.controller.job_controller_mode == "worker"
        last_checkpoint = time.monotonic()
        while True:
            # events from here on (a TaskFinished that lands while an
            # epoch is being published below) end the park at once
            seen = job.kicks
            if job.failure is not None:
                # hot-standby failover (ISSUE 17): a task failure while
                # RUNNING (worker death surfaces as peer connection
                # failures long before the heartbeat horizon) promotes
                # the warm standby instead of cold-recovering
                if await self._failover_promote(job):
                    last_checkpoint = time.monotonic()
                    continue
                job.transition(JobState.RECOVERING)
                return
            # finished-check MUST precede heartbeat expiry: a cleanly
            # finished worker stops heartbeating, and treating that as a
            # timeout would recover (and re-finish, and re-recover) forever
            if (len(job.finished_tasks) >= job.n_subtasks
                    and job.undrained_sources and not job.stop_requested):
                # FINISH guard: every task "finished", but a bounded
                # source completed without draining its assigned range.
                # FINISHED here would bless a prefix of the output as the
                # whole result — recover and replay from the last durable
                # checkpoint instead.
                job.failure = (
                    "source finished without draining: "
                    f"{dict(job.undrained_sources)}"
                )
                job.transition(JobState.RECOVERING)
                return
            if len(job.finished_tasks) >= job.n_subtasks:
                # release BEFORE the terminal transition: a caller woken
                # by wait_for_state(FINISHED) may immediately tear the
                # controller down, and the expunge (slot return + metric
                # GC) must not race that cancellation
                job.transition(JobState.FINISHING)
                await self._release_job(job, expunge=True)
                job.transition(JobState.FINISHED)
                return
            if self._heartbeat_expired(job):
                if await self._failover_promote(job):
                    last_checkpoint = time.monotonic()
                    continue
                # the promote attempt awaited: a real task failure
                # arriving meanwhile is the better diagnosis — keep it
                job.failure = job.failure or "worker heartbeat timeout"
                job.transition(JobState.RECOVERING)
                return
            if job.rescale_requested and not job.stop_requested:
                job.transition(JobState.RESCALING)
                return
            # reap pipelined epochs: publish (in epoch order) any whose
            # report set completed since the last wakeup — completions can
            # arrive >1 epoch late with multi-inflight worker flushes
            if job.backend is not None and job.pending_epochs:
                await self._checkpoint_reap(job)
                if job.failure is not None:
                    continue
            if job.stop_requested:
                mode = job.stop_requested
                job.stop_requested = None
                if mode == "checkpoint" and job.backend:
                    job.transition(JobState.CHECKPOINT_STOPPING)
                    await self._drain_pending_epochs(job)
                    if job.failure is not None:
                        # re-arm the stop, but never clobber a stop mode
                        # that arrived while the drain was awaiting: the
                        # newer request wins (RACE002: `mode` is stale)
                        job.stop_requested = job.stop_requested or mode
                        job.transition(JobState.RECOVERING)
                        return
                    if leader_mode and not job.leader_resigned:
                        # the leader runs the stopping checkpoint itself
                        try:
                            resp = await job.workers[0].client.call(
                                "WorkerGrpc", "CheckpointStop",
                                {"job_id": job.job_id},
                                timeout=90.0,
                            )
                            job.epoch = max(job.epoch, resp.get("epoch", 0))
                            job.published_epoch = max(
                                job.published_epoch, resp.get("epoch", 0)
                            )
                        except Exception as e:  # noqa: BLE001
                            if len(job.finished_tasks) >= job.n_subtasks:
                                logger.warning(
                                    "leader CheckpointStop raced job "
                                    "finish: %s", e,
                                )
                            else:
                                # wedged leader: fall back to a plain
                                # graceful stop so the job doesn't zombie
                                logger.warning(
                                    "leader CheckpointStop failed; falling "
                                    "back to graceful stop: %s", e,
                                )
                                for w in job.workers:
                                    try:
                                        await w.client.call(
                                            "WorkerGrpc", "StopExecution",
                                            {"job_id": job.job_id,
                                             "mode": "graceful"},
                                            timeout=5.0,
                                        )
                                    except Exception:  # noqa: BLE001
                                        pass
                    else:
                        await self._checkpoint(job, then_stop=True)
                    if job.failure is not None:
                        # the stopping checkpoint could not publish
                        # (storage fault / fencing): don't pretend the
                        # state is durable — recover and retry the stop
                        # (a stop requested during the await wins)
                        job.stop_requested = job.stop_requested or mode
                        job.transition(JobState.RECOVERING)
                        return
                    await self._await_all_finished(job)
                    if (len(job.finished_tasks) < job.n_subtasks
                            and (self._heartbeat_expired(job)
                                 or job.failure is not None)):
                        # model checker (ISSUE 9, V_STRANDED): a worker
                        # died between the durable stop checkpoint and its
                        # finish — its sink may hold a sealed transaction
                        # whose phase-2 commit never applied. Recover (the
                        # restore replays the claimed commit) and retry
                        # the stop instead of stopping over stranded state.
                        job.failure = (job.failure
                                       or "worker died finishing the stop")
                        job.stop_requested = job.stop_requested or mode
                        job.transition(JobState.RECOVERING)
                        return
                    await self._release_job(job, expunge=True)
                    job.transition(JobState.STOPPED)
                else:
                    job.transition(JobState.STOPPING)
                    for w in job.workers:
                        try:
                            await w.client.call(
                                "WorkerGrpc", "StopExecution",
                                {"job_id": job.job_id,
                                 "mode": "graceful" if mode == "graceful"
                                 else "immediate"},
                            )
                        except Exception as e:  # noqa: BLE001 - dead worker
                            logger.warning(
                                "StopExecution to worker %s failed: %s",
                                w.worker_id, e,
                            )
                    await self._await_all_finished(job)
                    await self._release_job(job, expunge=True)
                    job.transition(JobState.STOPPED)
                return
            cadence_armed = (
                job.backend is not None
                and (not leader_mode or job.leader_resigned)
                and not job.finished_tasks
                and len(job.pending_epochs)
                < max(1, config().state.max_inflight_flushes)
            )
            if (cadence_armed
                    and (job.checkpoint_asap
                         or time.monotonic() - last_checkpoint >= interval)):
                # checkpoint_asap (ISSUE 16): the sharing manager pulls a
                # mounted tenant's next checkpoint forward while a host
                # epoch is gated on its durable position — reconciliation
                # bounded by a round-trip, not a cadence interval
                job.checkpoint_asap = False
                last_checkpoint = time.monotonic()
                await self._checkpoint_start(job)
                continue
            # hot-standby failover (ISSUE 17): keep a warm standby armed
            # for every eligible job (no-op guard off the failover path)
            self.failover.note_running(job)
            # follower replicas (ISSUE 20): keep each eligible job
            # mounted on a follower (reattaches after follower death)
            self.replicas.note_running(job)
            # park: RPC arrivals kick the job; the wheel wakes us at the
            # earliest deadline that could change a predicate above
            deadlines = [self._heartbeat_horizon(job)]
            if cadence_armed:
                deadlines.append(last_checkpoint + interval)
            if job.pending_epochs:
                deadlines.append(
                    min(i["deadline"] for i in job.pending_epochs.values())
                )
            rearm_at = self.failover.wake_deadline(job)
            if rearm_at is not None:
                # an eligible job without a standby (arm backing off):
                # wake at the backoff horizon so re-arming isn't starved
                deadlines.append(rearm_at)
            await job.wait_kick(
                self.wheel, max(min(deadlines) - time.monotonic(), 0.0),
                seen,
            )

    @protocol_effect("ctrl.failover_promote")
    async def _failover_promote(self, job: JobHandle) -> bool:
        """Hot-standby promotion (ISSUE 17): on heartbeat loss or a task
        failure while RUNNING, swap the warm standby generation in for
        the (possibly merely slow) primary WITHOUT a SCHEDULING pass.
        RUNNING stays RUNNING on success; False falls back to the normal
        RECOVERING path. The promotion protocol is exhaustively model-
        checked (analysis/model: standby.arm / standby.tail /
        failover.promote) — in particular, the fresh generation re-
        resolves the LATEST published manifest rather than trusting the
        standby's tailed epoch (see the promote_while_primary_alive
        mutant)."""
        return await self.failover.try_promote(job)

    @protocol_effect("ctrl.rescale")
    async def _rescale(self, job: JobHandle):
        """Exactly-once automatic rescale (reference states/rescaling.rs;
        the autoscaler's actuation path). Two modes:

        * generation-overlap (`rescale.mode = overlap`, pooled
          multiplexed workers — the default shape): while the stop
          barrier drains, the NEW incarnation's workers are acquired
          (`_overlap_prepare`); once the rescale checkpoint publishes,
          the new incarnation is STAGED — built and restored from that
          durable checkpoint with its sources parked — concurrently with
          the old generation draining its final epoch, then promoted in
          place (`_overlap_activate`, RESCALING -> RUNNING). Output gap
          per rescale is the `rescale.overlap` span, ~one checkpoint
          interval instead of a full teardown+restore.
        * stop-the-world (fallback / `rescale.mode = stop_the_world`):
          stop with a checkpoint, fold the overrides into the graph, tear
          the workers down, reschedule.

        Failures anywhere route through Recovering: before the stop
        checkpoint published nothing durable changed (recover at the old
        parallelism); after it, overrides are applied (recovery
        reschedules at the new one) — the model checker's overlap window
        (`analysis/model/spec.py` overlap.prepare/overlap.activate, the
        epoch-emitted-by-both-generations invariant) pins both windows.
        Fully flight-recorded as the `{job}/rescale-N` trace."""
        overrides = job.rescale_requested or {}
        job.rescale_requested = None
        job.rescales += 1
        # hot-standby failover (ISSUE 17): the overlap rescale stages its
        # OWN incarnation under the same job id — discard the standby
        # (worker `_staged` would collide) and re-arm after the rescale
        await self.failover.discard(job)
        trace, parent = job.rescale_trace or (
            obs.new_trace(job.job_id, f"rescale-{job.rescales}"), None
        )
        overlap_done = False
        with obs.span(
            "job.rescale", trace=trace, parent=parent, cat="controller",
            job=job.job_id, rescale=job.rescales, overrides=str(overrides),
        ) as sp:
            job.rescale_trace = (
                (sp.trace_id, sp.span_id) if sp.recording else None
            )
            spec = chaos.fire("rescale.stop_delay", job=job.job_id)
            if spec is not None:
                logger.warning(
                    "chaos[rescale.stop_delay]: job %s holding %.1fs "
                    "before the rescale stop", job.job_id,
                    spec.param("delay", 0.5),
                )
                await asyncio.sleep(float(spec.param("delay", 0.5)))
            if self._heartbeat_expired(job):
                # a worker died in the decide->stop window: recover first,
                # rescale once the job is stable again
                job.failure = "worker heartbeat timeout"
                job.rescale_trace = None
                job.transition(JobState.RECOVERING)
                return
            await self._drain_pending_epochs(job)
            if job.failure is not None:
                job.rescale_trace = None
                job.transition(JobState.RECOVERING)
                return
            overlap = (
                config().rescale.mode == "overlap"
                and self._pool_mode()
                and bool(job.workers)
                and all(w.pooled for w in job.workers)
            )
            prep: Optional[asyncio.Task] = None
            if overlap:
                # overlap leg 1, concurrent with the stop barrier + report
                # wait: make sure the new incarnation's workers exist
                prep = asyncio.ensure_future(self._overlap_prepare(job))
            barrier_at = time.monotonic()
            with obs.span("rescale.stop_checkpoint", cat="controller"):
                await self._checkpoint(job, then_stop=True, nested=True)
            if job.failure is not None:
                # the stopping checkpoint did not publish (worker killed
                # mid-rescale, storage fault): nothing changed durably, so
                # recover at the CURRENT parallelism — the autoscaler
                # re-decides once rates stabilize
                if prep is not None:
                    prep.cancel()
                job.rescale_trace = None
                job.transition(JobState.RECOVERING)
                return
            if overlap:
                with obs.span(
                    "rescale.overlap", cat="controller", job=job.job_id,
                    rescale=job.rescales,
                ) as osp:
                    overlap_done = await self._overlap_activate(
                        job, overrides, prep, barrier_at, osp
                    )
                job.rescale_trace = None
                if not overlap_done:
                    job.transition(JobState.RECOVERING)
                    return
            else:
                await self._await_all_finished(job)
                job.apply_parallelism_overrides(overrides)
                if chaos.fire("rescale.reschedule_fail", job=job.job_id):
                    # crash window between the durable stop checkpoint and
                    # the reschedule: recovery must come back AT the new
                    # parallelism from that checkpoint, exactly once
                    logger.warning(
                        "chaos[rescale.reschedule_fail]: job %s failing "
                        "before the post-rescale schedule", job.job_id,
                    )
                    # drain awaited above: don't clobber a real
                    # failure that landed during it
                    job.failure = (job.failure
                                   or "chaos: rescale reschedule failure")
                    job.transition(JobState.RECOVERING)
                    return
                if self._pool_mode() and any(w.pooled for w in job.workers):
                    await self._release_job(job, force=True)
                else:
                    for w in job.workers:
                        self.workers.pop(w.worker_id, None)
                    await self.scheduler.stop_workers(job.job_id)
                # fresh generation fences any straggler; the restore epoch
                # is the stop checkpoint just published
                job.backend = StateBackend(
                    job.storage_url, job.job_id
                ).initialize()
        job.transition(
            JobState.RUNNING if overlap_done else JobState.SCHEDULING
        )

    @protocol_effect("ctrl.overlap_prepare")
    async def _overlap_prepare(self, job: JobHandle) -> int:
        """Overlap leg 1 (modeled as `overlap.prepare`): runs concurrently
        with the rescale's stop barrier — grow/heal the shared pool to the
        job's worker count and wait for registration. Claims nothing
        durable; a failure anywhere simply discards the attempt."""
        n_workers = max(1, len(job.workers))
        await self.scheduler.start_workers(self.addr, n_workers, job.job_id)
        await self._wait_registration(
            lambda: len(self._live_pool_workers()) >= n_workers
        )
        return n_workers

    @protocol_effect("ctrl.overlap_activate")
    async def _overlap_activate(self, job: JobHandle,
                                overrides: Dict[int, int],
                                prep: asyncio.Task, barrier_at: float,
                                span) -> bool:
        """Overlap leg 2 (modeled as `overlap.activate`): the durable
        rescale checkpoint is published, so claim the fresh generation,
        STAGE the new incarnation — StartExecution(staged): program built,
        state restored from that checkpoint, sources parked on the release
        gate — while the old generation drains its final epoch (sink
        commits applying, tasks finishing), then promote it in place.
        Returns False (with job.failure set) to route to Recovering —
        safe in every window: the checkpoint is durable and overrides are
        applied, so recovery comes back at the NEW parallelism, and the
        incarnation-fenced route namespaces + generation-stamped blob
        paths keep any old-generation straggler harmless."""
        old_workers = list(job.workers)
        old_subtasks = job.n_subtasks
        job.apply_parallelism_overrides(overrides)
        # fresh generation NOW: the old generation publishes nothing after
        # its stop manifest, and gen-stamped data paths keep its straggler
        # uploads beside — never over — the new generation's blobs
        job.backend = StateBackend(job.storage_url, job.job_id).initialize()
        drain = asyncio.ensure_future(
            self._await_all_finished(job, expected=old_subtasks)
        )
        new_workers: List[WorkerHandle] = []
        assignments: Dict[tuple, int] = {}
        counts: Dict[int, int] = {}
        try:
            n_workers = await asyncio.wait_for(
                asyncio.shield(prep), config().rescale.prepare_timeout
            )
            # refresh the admission grant for the new size (idempotent —
            # the job keeps the slots it holds)
            await self.admission.acquire(job)
            new_workers = self._pick_pool_workers(n_workers)
            if len(new_workers) < n_workers:
                raise RuntimeError(
                    f"{len(new_workers)} live pool workers, need {n_workers}"
                )
            job.schedules += 1  # fresh data_ns fences old-gen stragglers
            assignments, counts = self._assign_subtasks(job, new_workers)
            req = self._start_request(job, new_workers, assignments)
            req["staged"] = True
            for w in new_workers:
                await self._worker_call(
                    w, "WorkerGrpc", "StartExecution",
                    {**req, "is_leader": False},
                )
            # chaos seams land at the heart of the overlap window: the
            # old generation is draining its final epoch AND the new
            # generation is staged and restoring
            if chaos.fire("rescale.overlap_kill", job=job.job_id) is not None:
                self._chaos_kill_pool_worker(job)
            if chaos.fire("rescale.reschedule_fail", job=job.job_id):
                raise RuntimeError("chaos: rescale reschedule failure")
        except Exception as e:  # noqa: BLE001 - every window recovers
            prep.cancel()
            drain.cancel()
            await asyncio.gather(drain, return_exceptions=True)
            logger.warning("job %s overlap prepare failed: %r",
                           job.job_id, e)
            job.failure = f"overlap prepare failed: {e!r}"
            return False
        # the overlap window proper: staged restore completes while the
        # old generation drains (a post-publish worker death is safe —
        # the restore idempotently replays the claimed commit)
        await drain
        if job.failure is not None:
            # a staged-restore failure (or old-generation teardown noise)
            # surfaced as a task failure: recover at the new parallelism
            logger.warning("job %s overlap window failed: %s",
                           job.job_id, job.failure)
            return False
        try:
            for w in new_workers:
                await self._worker_call(
                    w, "WorkerGrpc", "StartProcessing",
                    {"job_id": job.job_id, "promote": True},
                )
            # old-generation release: promotion already tore down the old
            # runtime on every shared worker; workers that dropped out of
            # the placement get an explicit per-job teardown
            for w in old_workers:
                if w in new_workers:
                    continue
                w.assigned.pop(job.job_id, None)
                try:
                    await self._worker_call(
                        w, "WorkerGrpc", "StopJob",
                        {"job_id": job.job_id, "force": True},
                        timeout=5.0,
                    )
                except Exception as e:  # noqa: BLE001 - may be dying
                    logger.warning("StopJob(%s) on worker %s failed: %s",
                                   job.job_id, w.worker_id, e)
        except Exception as e:  # noqa: BLE001
            logger.warning("job %s overlap promote failed: %r",
                           job.job_id, e)
            job.failure = job.failure or f"overlap promote failed: {e!r}"
            return False
        if job.failure is not None:
            # a task failure landed WHILE the promote RPCs were awaiting
            # (e.g. a new-generation worker died mid-promote). The
            # pre-drain check above read job.failure before those awaits;
            # clearing it blindly below would mask the failure and serve
            # a half-promoted generation — re-read and route to recovery
            # (RACE002: revalidate after the last await)
            logger.warning("job %s failed during overlap promote: %s",
                           job.job_id, job.failure)
            return False
        job.workers = new_workers
        job.assignments = assignments
        for w in new_workers:
            w.assigned[job.job_id] = counts.get(w.worker_id, 0)
        job.checkpoints.clear()
        job.pending_epochs.clear()
        job.finished_tasks.clear()
        job.undrained_sources.clear()
        job.failure = None
        job.leader_resigned = False
        restore = job.backend.restore_epoch or 0
        job.epoch = max(job.epoch, restore)
        # the rescale checkpoint IS the published state: serving resumes
        # at it the moment the new generation runs
        job.published_epoch = max(job.published_epoch, restore)
        gap_ms = round((time.monotonic() - barrier_at) * 1e3, 3)
        span.set(gap_ms=gap_ms, workers=len(new_workers),
                 restore_epoch=restore)
        logger.info(
            "job %s generation-overlap rescale complete: output gap "
            "%.1f ms (barrier -> sources released), restore epoch %d",
            job.job_id, gap_ms, restore,
        )
        return True

    def _chaos_kill_pool_worker(self, job: JobHandle) -> None:
        """chaos[rescale.overlap_kill]: SIGKILL-equivalent teardown of a
        pool worker hosting this job INSIDE the overlap window (old
        generation draining its final epoch, new generation restoring).
        Embedded pools only — the drill's shape."""
        pool = getattr(self.scheduler, "pool", None) or []
        targets = {w.worker_id for w in job.workers}
        for w, _t in pool:
            if w.worker_id in targets:
                logger.warning(
                    "chaos[rescale.overlap_kill]: killing worker %s inside "
                    "the overlap window", w.worker_id,
                )
                # retained: a GC'd teardown task would half-kill the worker
                self._chaos_kill_task = asyncio.ensure_future(w.shutdown())
                return
        logger.warning(
            "chaos[rescale.overlap_kill]: no embedded pool worker to kill"
        )

    @protocol_effect("ctrl.checkpoint_start")
    async def _checkpoint_start(self, job: JobHandle):
        """Pipelined cadence: fan the barrier out and return — the epoch
        joins `pending_epochs` and publishes from _checkpoint_reap once
        its report set completes (possibly several epochs later)."""
        job.epoch += 1
        epoch = job.epoch
        trace = obs.new_trace(job.job_id, f"ck-{epoch}")
        with obs.span(
            "checkpoint", trace=trace, cat="controller", job=job.job_id,
            epoch=epoch, then_stop=False,
        ) as sp:
            ck_trace = (sp.trace_id, sp.span_id) if sp.recording else (None, None)
            with obs.span("barrier_fanout", cat="controller"):
                await self._fanout_barrier(job, epoch, then_stop=False)
        job.pending_epochs[epoch] = {
            "deadline": time.monotonic() + 60,
            "trace": ck_trace,
        }

    @protocol_effect("ctrl.checkpoint_reap")
    async def _checkpoint_reap(self, job: JobHandle):
        """Publish every pending epoch whose reports completed, strictly
        in epoch order (manifest N+1 references chain blobs first
        recorded in N). An epoch that misses its deadline is abandoned —
        a LATER epoch may still publish: per-subtask flushes are epoch-
        ordered, so a subtask reporting N+1 has durably flushed N."""
        for epoch in sorted(job.pending_epochs):
            info = job.pending_epochs[epoch]
            reports = job.checkpoints.get(epoch, {})
            if len(reports) < job.n_subtasks:
                if len(job.finished_tasks) >= job.n_subtasks:
                    job.pending_epochs.clear()
                    return
                if time.monotonic() > info["deadline"]:
                    logger.warning("checkpoint %d incomplete (abandoned)",
                                   epoch)
                    del job.pending_epochs[epoch]
                    continue
                return  # strict order: later epochs wait for this one
            if self.sharing.gate_blocks(job, epoch):
                # publication gate (ISSUE 16): a shared host's epoch
                # must not publish while a mounted durable tenant's own
                # durable position trails the host's captured offset — a
                # host restore would resume the scan beyond rows that
                # tenant still needs. Tenant publishes/detaches kick
                # this job, so the wait is event-driven; reports are
                # complete, so the abandon deadline doesn't apply.
                return
            del job.pending_epochs[epoch]
            tid, sid = info["trace"]
            with obs.span("checkpoint.finish", trace=tid, parent=sid,
                          cat="controller", epoch=epoch):
                await self._publish_epoch(job, epoch, reports)
            if job.failure is not None:
                return

    @protocol_effect("ctrl.drain_pending")
    async def _drain_pending_epochs(self, job: JobHandle):
        """Settle every pending epoch (publish or abandon) — stop,
        rescale and recovery paths stay strictly drained, exactly as the
        single-inflight design behaved."""
        while job.pending_epochs and job.failure is None:
            seen = job.kicks
            if self._heartbeat_expired(job):
                job.failure = "worker heartbeat timeout"
                return
            if len(job.finished_tasks) >= job.n_subtasks:
                job.pending_epochs.clear()
                return
            await self._checkpoint_reap(job)
            if job.pending_epochs and job.failure is None:
                deadline = min(
                    [i["deadline"] for i in job.pending_epochs.values()]
                    + [self._heartbeat_horizon(job)]
                )
                await job.wait_kick(
                    self.wheel, max(deadline - time.monotonic(), 0.0), seen
                )

    async def _fanout_barrier(self, job: JobHandle, epoch: int,
                              then_stop: bool):
        for w in job.workers:
            try:
                await self._worker_call(
                    w, "WorkerGrpc", "Checkpoint",
                    {"job_id": job.job_id, "epoch": epoch,
                     "then_stop": then_stop},
                )
            except Exception as e:  # noqa: BLE001 - resigned/dead worker
                logger.warning(
                    "checkpoint fan-out to worker %s failed: %s",
                    w.worker_id, e,
                )

    async def _checkpoint(self, job: JobHandle, then_stop: bool = False,
                          nested: bool = False):
        job.epoch += 1
        epoch = job.epoch
        # flight recorder: one trace per checkpoint epoch, minted here.
        # The barrier fan-out rpcs carry the context to workers; barriers
        # carry it in-band through the dataflow; completion reports and
        # storage writes stitch back into this tree. `nested` checkpoints
        # (the rescale stop) join the AMBIENT trace instead, so the whole
        # rescale reads as one connected tree.
        if nested:
            with obs.span(
                "checkpoint", cat="controller", job=job.job_id,
                epoch=epoch, then_stop=then_stop,
            ):
                await self._checkpoint_inner(job, epoch, then_stop)
            return
        with obs.span(
            "checkpoint", trace=obs.new_trace(job.job_id, f"ck-{epoch}"),
            cat="controller", job=job.job_id, epoch=epoch,
            then_stop=then_stop,
        ):
            await self._checkpoint_inner(job, epoch, then_stop)

    @protocol_effect("ctrl.stop_checkpoint")
    async def _checkpoint_inner(self, job: JobHandle, epoch: int,
                                then_stop: bool):
        with obs.span("barrier_fanout", cat="controller"):
            await self._fanout_barrier(job, epoch, then_stop)
        deadline = time.monotonic() + 60
        with obs.span("await_reports", cat="controller") as wait_span:
            while len(job.checkpoints.get(epoch, {})) < job.n_subtasks:
                seen = job.kicks
                if job.failure is not None or time.monotonic() > deadline:
                    logger.warning("checkpoint %d incomplete", epoch)
                    wait_span.set(outcome="incomplete")
                    if then_stop and job.failure is None:
                        # model checker (ISSUE 9, V_STRANDED): a stopping
                        # checkpoint that never completed must not let the
                        # stop proceed as if state were durable — fail it
                        # so the stop routes through Recovering and retries
                        job.failure = f"stop checkpoint {epoch} incomplete"
                    return
                if self._heartbeat_expired(job):
                    # a worker died mid-barrier: its subtasks can never
                    # report, so don't sit out the full checkpoint deadline
                    # — surface the liveness failure now and let _run
                    # recover
                    logger.warning(
                        "checkpoint %d abandoned: worker heartbeat timeout",
                        epoch,
                    )
                    job.failure = "worker heartbeat timeout"
                    wait_span.set(outcome="heartbeat_timeout")
                    return
                if len(job.finished_tasks) >= job.n_subtasks:
                    # the job completed while the barrier was in flight; a
                    # finished task can never report, so stop waiting and
                    # let _run see the finish
                    logger.info("checkpoint %d abandoned: job finished",
                                epoch)
                    wait_span.set(outcome="job_finished")
                    return
                park = min(deadline, self._heartbeat_horizon(job))
                await job.wait_kick(
                    self.wheel, max(park - time.monotonic(), 0.0), seen
                )
        await self._publish_epoch(job, epoch, job.checkpoints[epoch])

    @protocol_effect("ctrl.publish_epoch")
    async def _publish_epoch(self, job: JobHandle, epoch: int,
                             reports: Dict[str, dict]):
        """Manifest publish + 2PC commit + compaction/GC for one epoch
        whose full report set arrived (shared by the synchronous stop
        path and the pipelined reap)."""
        try:
            with obs.span("publish_manifest", cat="controller"):
                manifest = job.backend.publish_checkpoint(
                    epoch,
                    {tid: CheckpointReport(r) for tid, r in reports.items()},
                )
        except Exception as e:  # noqa: BLE001 - storage/protocol boundary
            # transient write failures, lost CAS races, and zombie fencing
            # must not crash the job driver into FAILED: the epoch is
            # abandoned and the failure routes through Recovering, which
            # claims a fresh generation and restores the latest durable
            # manifest — exactly-once is preserved by the restore, not by
            # this epoch
            logger.warning("checkpoint %d publish failed: %r", epoch, e)
            job.failure = f"checkpoint {epoch} publish failed: {e!r}"
            return
        # the manifest is durable: advance the serving tier's read
        # snapshot (cache entries of earlier epochs self-invalidate)
        job.published_epoch = max(job.published_epoch, epoch)
        # conservation ledger: join this epoch's sealed per-edge
        # attestations (sender == receiver) + flow checks, now that the
        # full report set is durable
        audits = {tid: r.get("audit") for tid, r in reports.items()}
        if any(a is not None for a in audits.values()):
            audit.reconciler(job.job_id).reconcile(epoch, audits)
        # shared-plan (ISSUE 16): a mounted tenant's publish raises its
        # durable restore floor on the bus and may clear the host's
        # gated epoch
        self.sharing.note_publish(job)
        # failover (ISSUE 17): wake the standby's tailer so it applies
        # this epoch's delta chains and stays within one epoch of us
        self.failover.note_publish(job)
        # follower replicas (ISSUE 20): same wake for the serving tier's
        # tailer — follower staleness stays <= one checkpoint interval
        self.replicas.note_publish(job)
        try:
            committing = manifest.get("committing")
            if committing and job.backend.claim_commit(epoch):
                # target only workers hosting committing subtasks: a
                # source-only worker legitimately finishes and closes its
                # rpc server right after a then_stop barrier, and a
                # refused no-op commit must not fail the epoch (sink
                # workers stay up in committing state until this lands)
                commit_workers = {
                    wid for (node_id, _sub), wid in job.assignments.items()
                    if str(node_id) in committing
                }
                with obs.span("commit_phase", cat="controller"):
                    for w in job.workers:
                        if w.worker_id not in commit_workers:
                            continue
                        await self._worker_call(
                            w, "WorkerGrpc", "Commit",
                            {"job_id": job.job_id, "epoch": epoch,
                             "committing": committing},
                        )
        except Exception as e:  # noqa: BLE001
            logger.warning("checkpoint %d commit phase failed: %r", epoch, e)
            job.failure = f"checkpoint {epoch} commit phase failed: {e!r}"
            return
        # compaction cadence: merge small carried-forward files (off the
        # event loop — merges are data-proportional), tell the owning
        # subtasks to swap references, GC unreferenced epochs. Advisory:
        # a failed swap delivery, merge, or GC pass must not fail the job
        # (old files stay referenced until a later cadence retries).
        try:
            with obs.span("compaction", cat="controller"):
                swaps = await asyncio.to_thread(
                    job.backend.compact_epoch, epoch, manifest
                )
                for swap in swaps:
                    for w in job.workers:
                        try:
                            await self._worker_call(
                                w, "WorkerGrpc", "LoadCompacted",
                                {**swap, "job_id": job.job_id},
                            )
                        except Exception as e:  # noqa: BLE001
                            logger.warning(
                                "LoadCompacted to worker %s failed: %s",
                                w.worker_id, e,
                            )
                await asyncio.to_thread(job.backend.retire_unreferenced)
        except Exception:  # noqa: BLE001
            logger.exception("checkpoint %d compaction/GC failed", epoch)

    async def _await_all_finished(self, job: JobHandle, timeout: float = 60.0,
                                  expected: Optional[int] = None):
        """Wait for the job's tasks to finish. `expected` pins the count
        when the caller already changed job.n_subtasks (the overlap
        rescale drains the OLD incarnation after applying the new
        parallelism overrides)."""
        want = job.n_subtasks if expected is None else expected
        deadline = time.monotonic() + timeout
        while len(job.finished_tasks) < want:
            seen = job.kicks
            if time.monotonic() > deadline:
                logger.warning("job %s: tasks did not finish in time",
                               job.job_id)
                return
            if self._heartbeat_expired(job):
                # a dead worker's tasks can never finish; don't sit out
                # the deadline — callers decide whether that's fatal
                logger.warning("job %s: worker died awaiting task finish",
                               job.job_id)
                return
            # parked on the job's kick list: TaskFinished/TaskFailed
            # arrivals wake us; the wheel covers the deadline + liveness
            park = min(deadline, self._heartbeat_horizon(job))
            await job.wait_kick(self.wheel,
                                max(park - time.monotonic(), 0.0), seen)

    @protocol_effect("ctrl.recover")
    async def _recover(self, job: JobHandle, n_workers: int):
        """reference states/recovering.rs:24-60 (escalating teardown) then
        reschedule from the latest durable checkpoint. Pool mode: the
        job's state is torn down PER JOB on live shared workers (StopJob)
        — co-scheduled jobs keep running — while actually-dead workers
        are pruned from the registry for the scheduler to replace. Each
        job sharing a dead worker runs this recovery independently
        (shared-fate failure, per-job recovery independence — the model
        checker's 2-job configuration pins that property)."""
        job.restarts += 1
        if job.restarts > self.max_restarts:
            await self._release_job(job, force=True, expunge=True)
            job.transition(JobState.FAILED)
            return
        # a cold recovery replaces the generation and reschedules — any
        # parked standby is stale the moment that happens (ISSUE 17)
        await self.failover.discard(job)
        logger.warning("job %s recovering (%s)", job.job_id, job.failure)
        job.pending_epochs.clear()  # unpublished epochs die with the gen
        # flight recorder: each recovery is its own lifecycle trace; the
        # fault that triggered it rides as an attribute so drill timelines
        # read fault -> detection -> recovery causally
        with obs.span(
            "job.recover",
            trace=obs.new_trace(job.job_id, f"recover-{job.restarts}"),
            cat="controller", job=job.job_id, restarts=job.restarts,
            failure=str(job.failure)[:300],
        ):
            if self._pool_mode() and any(w.pooled for w in job.workers):
                await self._release_job(job, force=True)
            else:
                for w in job.workers:
                    try:
                        await w.client.call(
                            "WorkerGrpc", "StopExecution",
                            {"job_id": job.job_id, "mode": "immediate"},
                            timeout=2.0,
                        )
                    except Exception:  # noqa: BLE001 - worker may be dead
                        pass
                    self.workers.pop(w.worker_id, None)
                await self.scheduler.stop_workers(job.job_id, force=True)
            # new generation fences the old; restore from latest manifest
            if job.backend is not None:
                job.backend = StateBackend(
                    job.storage_url, job.job_id
                ).initialize()
        job.transition(JobState.SCHEDULING)

    # -- helpers ------------------------------------------------------------

    def _free_workers(self) -> List[WorkerHandle]:
        return [w for w in self.workers.values()
                if w.job_id is None and not w.pooled]

    def _heartbeat_expired(self, job: JobHandle) -> bool:
        timeout = config().controller.heartbeat_timeout
        return any(
            time.monotonic() - w.last_heartbeat > timeout
            for w in job.workers
            # a resigned leader shut down after finishing its local work
            if not (job.leader_resigned and w is job.workers[0])
        )
