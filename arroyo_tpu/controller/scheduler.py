"""Worker schedulers.

Capability parity with the reference's scheduler implementations
(/root/reference/crates/arroyo-controller/src/schedulers/mod.rs:49-71
trait + Process/Embedded/Manual/Kubernetes impls): given a job's slot
requirement, start workers and wait for them to register. The embedded
scheduler runs workers as asyncio tasks in the controller process
(`arroyo run` mode); the process scheduler forks `python -m arroyo_tpu
worker` subprocesses; the manual scheduler waits for externally-launched
workers to join; a kubernetes scheduler renders worker pod specs (applied
via kubectl when available).
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
from typing import Dict, List, Optional

from ..utils.logging import get_logger

logger = get_logger("scheduler")


def multiplexing_active(kind: str) -> bool:
    """Whether jobs of this scheduler kind share a pooled, multiplexed
    worker set (ROADMAP item 3). Only the embedded and process schedulers
    own their worker lifecycle; multiplexing additionally requires the
    controller-resident job control loop (worker-leader mode elects one
    leader per job and assumes a dedicated worker set) and no
    multi-process device mesh (mesh ranks are per-job env assignments a
    shared process cannot take twice)."""
    from ..config import config

    cfg = config()
    mode = cfg.cluster.multiplexing
    if mode == "off" or kind not in ("embedded", "process"):
        return False
    if int(cfg.tpu.mesh_processes or 0) >= 2:
        return False
    if cfg.controller.job_controller_mode != "controller":
        return False
    return True  # "auto" and "on"


class Scheduler:
    kind = "?"  # scheduler kind (multiplexing_active gates on it)
    controller = None  # ControllerServer, attached by start()

    async def start_workers(self, controller_addr: str, n_workers: int,
                            job_id: str) -> None:
        raise NotImplementedError

    async def stop_workers(self, job_id: str, force: bool = False) -> None:
        pass

    async def shutdown(self) -> None:
        """Tear down pooled workers (controller stop); per-job teardown
        goes through stop_workers/StopJob instead."""


_next_embedded_id = 1000


class EmbeddedScheduler(Scheduler):
    """Workers as asyncio tasks inside the controller process. With
    multiplexing active (the default), a shared pool of
    `cluster.worker_pool_size` long-lived workers hosts every job;
    otherwise each job gets dedicated workers (legacy)."""

    kind = "embedded"

    def __init__(self):
        self.jobs: Dict[str, List] = {}  # job_id -> [(worker, task)] legacy
        self.pool: List = []  # [(worker, serve_task)] shared across jobs
        self._pool_lock: Optional[asyncio.Lock] = None

    async def start_workers(self, controller_addr, n_workers, job_id):
        global _next_embedded_id

        from ..config import config
        from ..engine.worker import WorkerServer

        if multiplexing_active("embedded"):
            # serialized: concurrent job schedules must not each find the
            # pool short and over-spawn it (the spawn loop awaits)
            if self._pool_lock is None:
                self._pool_lock = asyncio.Lock()
            async with self._pool_lock:
                # the pool grows on demand to the largest worker request —
                # dead workers (chaos kill, crash) are pruned and replaced
                # here, which is the path recovery rescheduling drives
                want = max(int(config().cluster.worker_pool_size or 1),
                           n_workers)
                live = []
                for w, t in self.pool:
                    if getattr(w, "_shutdown_started", False) or t.done():
                        t.cancel()
                    else:
                        live.append((w, t))
                self.pool = live
                while len(self.pool) < want:
                    wid = _next_embedded_id
                    _next_embedded_id += 1
                    w = WorkerServer(controller_addr, worker_id=wid,
                                     pooled=True)
                    await w.start()
                    self.pool.append(
                        (w, asyncio.ensure_future(w.serve_forever()))
                    )
            return
        entries = self.jobs.setdefault(job_id, [])
        for _ in range(n_workers):
            wid = _next_embedded_id
            _next_embedded_id += 1  # unique across concurrent jobs
            w = WorkerServer(controller_addr, worker_id=wid)
            await w.start()
            entries.append(
                (w, asyncio.ensure_future(w.run_until_finished()))
            )

    async def stop_workers(self, job_id, force=False):
        # pooled workers are shared: the controller already tore the job
        # down on them via StopJob; only dedicated (legacy) entries die
        entries = self.jobs.pop(job_id, [])
        if force:
            # full teardown: cancel runners, heartbeats and servers so no
            # zombie keeps refreshing the controller's liveness view
            for w, t in entries:
                await w.shutdown()
                t.cancel()
            await asyncio.gather(
                *[t for _, t in entries], return_exceptions=True
            )

    async def shutdown(self):
        pool, self.pool = self.pool, []
        for w, t in pool:
            await w.shutdown()
            t.cancel()
        await asyncio.gather(*[t for _, t in pool], return_exceptions=True)


_next_process_id = 2000


def spawn_worker(controller_addr: str, worker_id: int,
                 extra_env: Optional[dict] = None,
                 spawn_generation: int = 0) -> subprocess.Popen:
    """Fork one `arroyo-tpu worker` subprocess (shared by the process
    scheduler and node daemons). `spawn_generation` counts RESPAWNS of
    this scheduling slot: a config-installed fault plan
    (ARROYO__CHAOS__PLAN) arms only in generation 0 by default, so a
    heartbeat-hit worker.kill cannot become a kill LOOP — each respawned
    process used to re-read the env and re-install the plan with fresh
    hit counters (the carried truncation-as-FINISHED bug).

    The environment is inherited, so every worker process whose
    operators take a device tier initialises jax's accelerator backend.
    A chip serves ONE process: a second worker on the same chip fails
    its StartExecution with the error ops/_jax.accelerator_present
    raises (libtpu refuses the claim within a second; measured on a
    v5e, PR 21) and the job goes FAILED after the restart budget — run
    one device-tier worker per chip and pin the others to
    JAX_PLATFORMS=cpu through `extra_env`."""
    env = dict(os.environ)
    env.update(extra_env or {})
    env["ARROYO_WORKER_ID"] = str(worker_id)
    env["ARROYO_CHAOS_SPAWN_GEN"] = str(int(spawn_generation))
    return subprocess.Popen(
        [sys.executable, "-m", "arroyo_tpu", "worker",
         "--controller", controller_addr],
        env=env,
    )


async def terminate_procs(procs, force: bool = False):
    """Stop worker subprocesses without blocking the event loop."""
    import asyncio

    for p in procs:
        if p.poll() is None:
            p.kill() if force else p.terminate()
    for p in procs:
        try:
            await asyncio.to_thread(p.wait, 5)
        except subprocess.TimeoutExpired:
            p.kill()


def mesh_env_for_worker(index: int, n_workers: int,
                        coordinator: Optional[str]) -> dict:
    """Multi-host mesh assignment for one spawned worker: when the job
    is configured for a multi-process mesh (tpu.mesh_processes >= 2),
    the scheduler hands each worker its rank and the shared coordinator
    so the worker's `multihost.ensure_initialized()` joins the global
    mesh before any jax init. Empty dict in single-host deployments."""
    from ..config import config
    from ..parallel.multihost import env_overrides

    n_proc = int(config().tpu.mesh_processes or 0)
    if n_proc < 2:
        return {}
    if n_proc != n_workers:
        raise ValueError(
            f"tpu.mesh_processes={n_proc} but the job schedules "
            f"{n_workers} workers; the mesh spans every worker"
        )
    return env_overrides(coordinator, n_proc, index)


def pick_coordinator() -> str:
    """Coordinator address for a new job's mesh: a free port on this
    (controller) host — process 0's jax coordinator service binds it.

    Bind-then-close is inherently racy: the port stays unbound until
    worker rank 0 reaches jax.distributed.initialize (process fork +
    jax import later). The window is accepted for the process scheduler
    (single host, ephemeral-range port, job startup is seconds); an
    operator can pin tpu.mesh_coordinator explicitly to avoid it. When
    the race IS lost, workers don't surface jax's bare connect error:
    parallel/multihost.ensure_initialized raises a RuntimeError naming
    this coordinator address and pointing at tpu.mesh_coordinator."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return f"127.0.0.1:{s.getsockname()[1]}"


class ProcessScheduler(Scheduler):
    """Forks worker subprocesses (reference ProcessScheduler mod.rs:118).
    With multiplexing active, a shared pool of `cluster.worker_pool_size`
    long-lived processes hosts every job (ARROYO_WORKER_POOLED=1 keeps
    them serving past their first job); mesh jobs and worker-leader mode
    fall back to fork-per-job."""

    kind = "process"

    def __init__(self):
        self.procs: Dict[str, List[subprocess.Popen]] = {}
        self.pool_procs: List[subprocess.Popen] = []
        # chaos-plan dedupe across incarnations: replacements of dead
        # pool processes (and per-job respawn rounds) carry a spawn
        # generation > 0, which suppresses ARROYO__CHAOS__PLAN re-arming
        self._pool_spawn_gen = 0
        self._job_spawn_rounds: Dict[str, int] = {}

    async def start_workers(self, controller_addr, n_workers, job_id):
        global _next_process_id

        from ..config import config

        if multiplexing_active("process"):
            want = max(int(config().cluster.worker_pool_size or 1),
                       n_workers)
            live = [p for p in self.pool_procs if p.poll() is None]
            if len(live) < len(self.pool_procs):
                # dead workers pruned: the spawns below are REPLACEMENTS
                # (respawned incarnations), not pool growth
                self._pool_spawn_gen += 1
            self.pool_procs = live
            while len(self.pool_procs) < want:
                p = spawn_worker(
                    controller_addr, _next_process_id,
                    extra_env={"ARROYO_WORKER_POOLED": "1"},
                    spawn_generation=self._pool_spawn_gen,
                )
                _next_process_id += 1
                self.pool_procs.append(p)
            return
        coord = None
        if int(config().tpu.mesh_processes or 0) >= 2:
            coord = config().tpu.mesh_coordinator or pick_coordinator()
        spawn_round = self._job_spawn_rounds.get(job_id, 0)
        self._job_spawn_rounds[job_id] = spawn_round + 1
        for i in range(n_workers):
            p = spawn_worker(
                controller_addr, _next_process_id,
                extra_env=mesh_env_for_worker(i, n_workers, coord),
                spawn_generation=spawn_round,
            )
            _next_process_id += 1
            self.procs.setdefault(job_id, []).append(p)

    async def stop_workers(self, job_id, force=False):
        await terminate_procs(self.procs.pop(job_id, []), force)

    async def shutdown(self):
        procs, self.pool_procs = self.pool_procs, []
        await terminate_procs(procs, force=True)


class NodeScheduler(Scheduler):
    """Places workers on registered node daemons (reference node scheduler,
    schedulers/mod.rs): most-free-slots first; the node forks the worker
    processes. `controller` is attached by ControllerServer.start()."""

    kind = "node"

    def __init__(self):
        self.controller = None  # ControllerServer, set on attach
        # job_id -> [node_handle] (one entry per worker placed on it)
        self.placements: Dict[str, list] = {}

    async def start_workers(self, controller_addr, n_workers, job_id):
        from ..config import config

        # multi-host mesh across node daemons: rank assignment works the
        # same as the process scheduler, but the coordinator must be an
        # operator-provided address reachable from EVERY node (rank 0
        # binds it; a controller-local free port would be meaningless on
        # another machine)
        n_proc = int(config().tpu.mesh_processes or 0)
        coord = config().tpu.mesh_coordinator or None
        if n_proc >= 2 and not coord:
            raise RuntimeError(
                "node scheduler: tpu.mesh_processes >= 2 requires an "
                "operator-provided tpu.mesh_coordinator (host:port "
                "reachable from every node; rank 0's worker binds it)"
            )
        try:
            for i in range(n_workers):
                await self._place_one(
                    controller_addr, job_id,
                    mesh_env_for_worker(i, n_workers, coord),
                )
        except Exception:
            # partial scheduling failure: release what was started so the
            # slots and orphan workers don't leak
            await self.stop_workers(job_id, force=True)
            raise

    async def _place_one(self, controller_addr, job_id, extra_env=None):
        while True:
            nodes = list(getattr(self.controller, "nodes", {}).values())
            if not nodes:
                raise RuntimeError(
                    "node scheduler: no node daemons registered "
                    "(start them with `arroyo-tpu node --controller ...`)"
                )
            node = max(nodes, key=lambda n: n.slots - n.used)
            if node.slots - node.used <= 0:
                raise RuntimeError("node scheduler: no free slots")
            # reserve BEFORE awaiting: a concurrent job must not grab the
            # same last slot while the rpc is in flight
            node.used += 1
            self.placements.setdefault(job_id, []).append(node)
            try:
                await node.client.call(
                    "NodeGrpc", "StartWorkers",
                    {"job_id": job_id, "n": 1,
                     "controller_addr": controller_addr,
                     "extra_env": extra_env or {}},
                )
                return
            except Exception as e:  # noqa: BLE001 - dead node: drop + retry
                logger.warning("node %s unreachable, dropping: %s",
                               node.node_id, e)
                node.used -= 1
                self.placements[job_id].remove(node)
                self.controller.nodes.pop(node.node_id, None)

    async def stop_workers(self, job_id, force=False):
        placed = self.placements.pop(job_id, [])
        for node in {id(n): n for n in placed}.values():
            try:
                await node.client.call(
                    "NodeGrpc", "StopWorkers",
                    {"job_id": job_id, "force": force},
                )
            except Exception as e:  # noqa: BLE001 - node may be gone
                logger.warning("StopWorkers on %s failed: %s",
                               node.node_id, e)
        for node in placed:
            node.used = max(0, node.used - 1)


class ManualScheduler(Scheduler):
    """Workers join on their own (reference mod.rs:334)."""

    kind = "manual"

    async def start_workers(self, controller_addr, n_workers, job_id):
        logger.info(
            "manual scheduler: waiting for %d workers to join %s",
            n_workers, controller_addr,
        )


class KubernetesScheduler(Scheduler):
    """Renders worker pod specs (reference schedulers/kubernetes/mod.rs:240);
    applies them with kubectl when present, else raises with the manifest
    path so operators can apply it themselves."""

    kind = "kubernetes"

    def __init__(self, namespace: str = "default",
                 image: str = "arroyo-tpu:latest", task_slots: int = 4):
        self.namespace = namespace
        self.image = image
        self.task_slots = task_slots

    def render_pod(self, controller_addr: str, job_id: str, index: int) -> dict:
        return {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": f"arroyo-worker-{job_id}-{index}".lower(),
                "namespace": self.namespace,
                "labels": {
                    "app": "arroyo-tpu-worker",
                    "arroyo/job_id": job_id,
                },
            },
            "spec": {
                "restartPolicy": "Never",
                "containers": [
                    {
                        "name": "worker",
                        "image": self.image,
                        "command": [
                            "python", "-m", "arroyo_tpu", "worker",
                            "--controller", controller_addr,
                        ],
                        "env": [
                            {"name": "ARROYO__WORKER__TASK_SLOTS",
                             "value": str(self.task_slots)},
                        ],
                        "resources": {
                            "requests": {"google.com/tpu": "1"},
                            "limits": {"google.com/tpu": "1"},
                        },
                    }
                ],
            },
        }

    async def start_workers(self, controller_addr, n_workers, job_id):
        import json
        import shutil
        import tempfile

        pods = [
            self.render_pod(controller_addr, job_id, i)
            for i in range(n_workers)
        ]
        manifest = tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        )
        json.dump({"apiVersion": "v1", "kind": "List", "items": pods},
                  manifest)
        manifest.close()
        if shutil.which("kubectl"):
            # kubectl blocks on the API server; keep the control loop live
            await asyncio.to_thread(
                subprocess.run, ["kubectl", "apply", "-f", manifest.name],
                check=True,
            )
        else:
            raise RuntimeError(
                f"kubectl not available; worker pod manifest written to "
                f"{manifest.name}"
            )

    async def stop_workers(self, job_id, force=False):
        import shutil

        if shutil.which("kubectl"):
            await asyncio.to_thread(
                subprocess.run,
                ["kubectl", "delete", "pod", "-n", self.namespace,
                 "-l", f"arroyo/job_id={job_id}",
                 "--wait=false" if not force else "--force"],
                check=False,
            )


def make_scheduler(kind: str) -> Scheduler:
    return {
        "embedded": EmbeddedScheduler,
        "process": ProcessScheduler,
        "manual": ManualScheduler,
        "node": NodeScheduler,
        "kubernetes": KubernetesScheduler,
    }[kind]()
