"""CLI: all roles as subcommands of one entrypoint.

Capability parity with the reference binary
(/root/reference/crates/arroyo/src/main.rs:43-120): `run` (single-process
cluster for one query), `worker`, `controller`, `api`, `cluster`
(api+controller), `visualize` (DAG dump), plus `bench` for the nexmark
benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(prog="arroyo_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run_p = sub.add_parser("run", help="run a query in an embedded cluster")
    run_p.add_argument("query", help="SQL text or path to a .sql file")
    run_p.add_argument("--parallelism", type=int, default=1)
    run_p.add_argument("--state-dir", default=None,
                       help="checkpoint storage URL (enables durability)")
    run_p.add_argument("--workers", type=int, default=1)
    run_p.add_argument("--scheduler", default="embedded",
                       choices=["embedded", "process"])
    run_p.add_argument("--autoscale", action="store_true",
                       help="enable the closed-loop autoscaler (requires "
                       "--state-dir: rescales restore from checkpoints)")
    run_p.add_argument("--max-parallelism", type=int, default=None,
                       help="autoscaler parallelism ceiling "
                       "(autoscale.max_parallelism)")

    w_p = sub.add_parser("worker", help="start a worker")
    w_p.add_argument("--controller", required=True)

    n_p = sub.add_parser("node", help="start a node daemon (offers "
                         "worker slots to the controller)")
    n_p.add_argument("--controller", required=True)
    n_p.add_argument("--slots", type=int, default=None)

    c_p = sub.add_parser("controller", help="start a controller")
    c_p.add_argument("--scheduler", default=None,
                     choices=["embedded", "process", "manual", "node",
                              "kubernetes"])
    c_p.add_argument("--port", type=int, default=None)

    api_p = sub.add_parser("api", help="start the REST API server")
    api_p.add_argument("--port", type=int, default=None)

    cl_p = sub.add_parser("cluster", help="start api + controller")
    cl_p.add_argument("--port", type=int, default=None)
    cl_p.add_argument("--scheduler", default="process")
    cl_p.add_argument("--autoscale", action="store_true",
                      help="enable the closed-loop autoscaler for jobs "
                      "with durable state")

    v_p = sub.add_parser("visualize", help="print a query's dataflow DAG")
    v_p.add_argument("query")

    sub.add_parser("bench", help="run the nexmark benchmark")

    args = ap.parse_args(argv)
    if args.cmd == "run":
        return _serve(_run(args))
    if args.cmd == "worker":
        return _serve(_worker(args))
    if args.cmd == "node":
        return _serve(_node(args))
    if args.cmd == "controller":
        return _serve(_controller(args))
    if args.cmd == "api":
        return _serve(_api(args))
    if args.cmd == "cluster":
        return _serve(_cluster(args))
    if args.cmd == "visualize":
        return _visualize(args)
    if args.cmd == "bench":
        import subprocess

        return subprocess.call([sys.executable, "bench.py"])


def _serve(role):
    """Every role's loop is made in one place: its selector books the
    loop's idle time into the phase ledger (`obs/timeline.py`)."""
    from .obs.timeline import event_loop

    return asyncio.run(role, loop_factory=event_loop)


def _load_sql(q: str) -> str:
    import os

    if os.path.exists(q) and q.endswith(".sql"):
        return open(q).read()
    return q


async def _run(args):
    """reference crates/arroyo/src/run.rs: embedded cluster, one query."""
    from .controller.controller import ControllerServer
    from .controller.scheduler import make_scheduler
    from .controller.state_machine import JobState
    from .sql import plan_query
    from .utils import init_logging

    init_logging()
    sql = _load_sql(args.query)
    plan_query(sql, parallelism=args.parallelism)  # validate before boot
    # idempotent reuse (reference crates/arroyo run.rs: pipelines are keyed
    # by query): with a state dir, the job id derives from the query text,
    # so re-running the same query resumes its own checkpoints and a
    # different query never collides with stale state
    if args.state_dir:
        import hashlib

        job_id = "q" + hashlib.sha256(sql.encode()).hexdigest()[:12]
        from .state import protocol
        from .state.storage import StorageProvider

        latest = protocol.resolve_latest(
            StorageProvider(args.state_dir), protocol.ProtocolPaths(job_id)
        )
        if latest:
            print(f"resuming pipeline {job_id} from epoch "
                  f"{latest['epoch']}")
        # pipeline metadata rides the state dir (reference MaybeLocalDb)
        from .api.db import ApiDb

        meta = ApiDb(remote_url=args.state_dir)
        if not any(p["query"] == sql for p in meta.list_pipelines()):
            meta.create_pipeline(job_id, sql, args.parallelism)
    else:
        job_id = "job_cli"
    import contextlib

    from .config import update

    cfg_ctx = contextlib.nullcontext()
    if args.autoscale:
        if not args.state_dir:
            print("--autoscale requires --state-dir: automatic rescales "
                  "stop with a checkpoint and restore from it",
                  file=sys.stderr)
            return 2
        autoscale = {"enabled": True}
        if args.max_parallelism:
            autoscale["max_parallelism"] = args.max_parallelism
        cfg_ctx = update(autoscale=autoscale)
    with cfg_ctx:
        controller = await ControllerServer(
            make_scheduler(args.scheduler)
        ).start()
        await controller.submit_job(
            job_id, sql=sql, storage_url=args.state_dir,
            n_workers=args.workers, parallelism=args.parallelism,
        )
        try:
            state = await controller.wait_for_state(
                job_id, JobState.FINISHED, JobState.FAILED,
                JobState.STOPPED, timeout=86400,
            )
            print(f"job {state.value.lower()}")
            if state == JobState.FAILED:
                print(f"cause: {controller.jobs[job_id].failure}",
                      file=sys.stderr)
                return 1
            return 0
        except KeyboardInterrupt:
            await controller.stop_job(job_id, "checkpoint"
                                      if args.state_dir else "graceful")
            await controller.wait_for_state(
                job_id, JobState.STOPPED, JobState.FAILED, timeout=60
            )
            return 0
        finally:
            await controller.stop()


async def _node(args):
    from .controller.node import NodeServer
    from .utils import init_logging

    init_logging()
    node = await NodeServer(args.controller, slots=args.slots).start()
    try:
        await node.run_forever()
    except KeyboardInterrupt:
        await node.stop()
    return 0


async def _worker(args):
    from .engine.worker import worker_main
    from .parallel.multihost import ensure_initialized
    from .utils import init_logging

    init_logging()
    # join the job's multi-process device mesh BEFORE any jax backend
    # init: the controller assigned (coordinator, n, rank) via
    # ARROYO__TPU__MESH_* env overrides at scheduling time
    # (parallel/multihost.py; no-op in single-process deployments)
    ensure_initialized()
    await worker_main(args.controller)


async def _controller(args):
    from .config import config
    from .controller.controller import ControllerServer
    from .controller.scheduler import make_scheduler
    from .utils import init_logging

    init_logging()
    sched = make_scheduler(args.scheduler or config().controller.scheduler)
    c = ControllerServer(sched)
    if args.port:
        c.rpc.port = args.port
    await c.start()
    print(f"controller listening at {c.addr}")
    await asyncio.Event().wait()


async def _api(args):
    from .api.rest import serve_api
    from .utils import init_logging

    init_logging()
    await serve_api(port=args.port)


async def _cluster(args):
    import contextlib

    from .api.rest import serve_api
    from .config import config, update
    from .controller.controller import ControllerServer
    from .controller.scheduler import make_scheduler
    from .utils import init_logging

    init_logging()
    cfg_ctx = (update(autoscale={"enabled": True}) if args.autoscale
               else contextlib.nullcontext())
    with cfg_ctx:
        c = ControllerServer(make_scheduler(args.scheduler))
        await c.start()
        print(f"controller at {c.addr}")
        await serve_api(port=args.port, controller=c)


def _visualize(args):
    from .sql import plan_query

    plan = plan_query(_load_sql(args.query))
    g = plan.graph
    print("digraph pipeline {")
    for n in g.nodes.values():
        ops = " | ".join(op.operator.value for op in n.chain)
        print(f'  n{n.node_id} [label="{n.description}\\n{ops}\\n'
              f'p={n.parallelism}"];')
    for e in g.edges:
        print(f'  n{e.src} -> n{e.dst} [label="{e.edge_type.value}"];')
    print("}")
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)
