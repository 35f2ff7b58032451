"""REST API server (aiohttp).

Capability parity with the reference's API routes
(/root/reference/crates/arroyo-api/src/rest.rs:65-243): pipelines
CRUD/validate/preview/stop/restart, jobs, checkpoint listings, operator
metric groups, connectors metadata, connection profiles/tables (+test),
UDFs CRUD/validate, websocket tail of preview output. Served under
/api/v1; job output and state come straight from the in-process controller
(the reference couples these through Postgres + gRPC; this build embeds
the controller in the API process or is pointed at one).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Optional

from aiohttp import web

from ..config import config
from ..controller.controller import ControllerServer
from ..controller.state_machine import JobState
from ..sql import plan_query
from ..sql.lexer import SqlError
from ..utils.logging import get_logger
from .db import ApiDb

logger = get_logger("api")


def json_response(data, status=200):
    return web.json_response(data, status=status, dumps=lambda d: json.dumps(
        d, default=str))


def error(status: int, message: str):
    return web.json_response({"error": message}, status=status)


class ApiServer:
    def __init__(self, controller: Optional[ControllerServer] = None,
                 db_path: Optional[str] = None):
        self.controller = controller
        self.db = ApiDb(
            db_path or config().database.path,
            remote_url=config().database.remote_url or None,
            backend=(
                "sqlite" if db_path else config().database.backend
            ),
            dsn=config().database.dsn,
        )
        self.previews: dict = {}  # pipeline id -> preview rows list
        # background tasks (job trackers, preview runs): the loop only
        # weak-refs tasks, so fire-and-forget work must be retained here
        # or it can be garbage-collected mid-flight
        self._bg_tasks: set = set()

    def _spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    # -- pipelines ----------------------------------------------------------

    async def validate_query(self, request: web.Request):
        body = await request.json()
        try:
            plan = plan_query(body["query"],
                              parallelism=body.get("parallelism", 1))
        except SqlError as e:
            return json_response({"errors": [str(e)]}, status=400)
        g = plan.graph
        return json_response(
            {
                "graph": {
                    "nodes": [
                        {
                            "node_id": n.node_id,
                            "description": n.description,
                            "operator": " -> ".join(
                                op.operator.value for op in n.chain
                            ),
                            "parallelism": n.parallelism,
                        }
                        for n in g.nodes.values()
                    ],
                    "edges": [
                        {"src": e.src, "dst": e.dst,
                         "edge_type": e.edge_type.value}
                        for e in g.edges
                    ],
                },
                "errors": [],
            }
        )

    async def create_pipeline(self, request: web.Request):
        body = await request.json()
        name = body.get("name") or "pipeline"
        query = body.get("query")
        parallelism = int(body.get("parallelism", 1))
        if not query:
            return error(400, "query is required")
        try:
            plan = plan_query(query, parallelism=parallelism)
        except SqlError as e:
            return error(400, str(e))
        tenant = str(body.get("tenant") or "default")
        pipeline = self.db.create_pipeline(name, query, parallelism,
                                           tenant=tenant)
        if self.controller is not None:
            await self._submit_pipeline_job(
                pipeline["id"], query, parallelism, tenant=tenant
            )
        return json_response(pipeline)

    async def _submit_pipeline_job(self, pid: str, query: str,
                                   parallelism: int,
                                   tenant: str = "default") -> dict:
        """Create + submit + track one job of a pipeline. Checkpoint
        storage is keyed by PIPELINE id, so a restart or rescale restores
        the pipeline's latest durable checkpoint (state, source
        positions) instead of starting blank — the generation protocol
        fences any zombie writer from the previous job. The tenant rides
        into admission control (quota + fair share)."""
        job = self.db.create_job(pid)
        storage = config().pipeline.checkpointing.storage_url
        await self.controller.submit_job(
            job["id"], sql=query,
            storage_url=f"{storage}/{pid}" if storage else None,
            parallelism=parallelism,
            tenant=tenant,
        )
        self._spawn(self._track_job(pid, job["id"]))
        return job

    def _live_jobs(self, pid: str) -> list:
        if self.controller is None:
            return []
        return [
            j for j in self.db.jobs_for_pipeline(pid)
            if j["id"] in self.controller.jobs
            and not self.controller.jobs[j["id"]].state.is_terminal()
        ]

    async def _track_job(self, pid: str, jid: str):
        """Mirror a job's state into the DB. Event-driven: parked on the
        job's kick list (state transitions wake it) with a coarse
        fallback deadline, writing only on CHANGE — the old 0.2s poll
        loop burned 5 wakeups + 2 DB writes per second PER JOB even when
        nothing moved, which is O(jobs) idle cost a 100-job fleet
        notices."""
        job = self.controller.jobs.get(jid)
        last = None
        while job is not None and not job.state.is_terminal():
            seen = job.kicks
            if job.state.value != last:
                last = job.state.value
                self.db.update_job(jid, last, job.restarts)
                self.db.set_pipeline_state(pid, last)
            await job.wait_kick(self.controller.wheel, 30.0, seen)
        if job is not None:
            self.db.update_job(jid, job.state.value, job.restarts)
            self.db.set_pipeline_state(pid, job.state.value)

    async def list_pipelines(self, request: web.Request):
        return json_response({"data": self.db.list_pipelines()})

    async def get_pipeline(self, request: web.Request):
        p = self.db.get_pipeline(request.match_info["id"])
        if p is None:
            return error(404, "pipeline not found")
        return json_response(p)

    async def delete_pipeline(self, request: web.Request):
        pid = request.match_info["id"]
        p = self.db.get_pipeline(pid)
        if p is None:
            return error(404, "pipeline not found")
        await self._stop_pipeline_jobs(pid, "immediate")
        self.db.delete_pipeline(pid)
        return json_response({"deleted": pid})

    async def patch_pipeline(self, request: web.Request):
        """stop modes and rescale (reference: PATCH /pipelines/{id} with
        stop / parallelism fields; parallelism change on a running
        pipeline stops with a checkpoint and resubmits at the new
        parallelism, like the reference's Rescaling transition)."""
        pid = request.match_info["id"]
        if self.db.get_pipeline(pid) is None:
            return error(404, "pipeline not found")
        body = await request.json()
        stop = body.get("stop")
        if stop not in (None, "none", "checkpoint", "graceful", "immediate"):
            return error(400, f"invalid stop mode {stop}")
        if stop and stop != "none":
            await self._stop_pipeline_jobs(pid, stop)
        if "parallelism" in body:
            try:
                par = int(body["parallelism"])
            except (TypeError, ValueError):
                return error(400, "parallelism must be an integer")
            if par < 1 or par > 128:
                return error(400, "parallelism must be in [1, 128]")
            p = self.db.get_pipeline(pid)
            if (stop in (None, "none") and self._live_jobs(pid)
                    and par != p["parallelism"]):
                # rescale: checkpoint-stop the running job, then resubmit
                # at the new parallelism (restores the pipeline's latest
                # checkpoint — key-range state sharding re-reads). The DB
                # records the new parallelism only AFTER the stop
                # succeeds: on the 409 path the job keeps running at the
                # old parallelism and the record must keep saying so
                # (ADVICE r4).
                await self._stop_pipeline_jobs(pid, "checkpoint")
                if self._live_jobs(pid):
                    # the stop timed out: running a second job against
                    # the same sources would double-process
                    return error(
                        409, "running job did not stop; rescale aborted"
                    )
                self.db.set_pipeline_parallelism(pid, par)
                await self._submit_pipeline_job(
                    pid, p["query"], par,
                    tenant=p.get("tenant", "default"),
                )
            else:
                self.db.set_pipeline_parallelism(pid, par)
        return json_response(self.db.get_pipeline(pid))

    async def restart_pipeline(self, request: web.Request):
        pid = request.match_info["id"]
        p = self.db.get_pipeline(pid)
        if p is None:
            return error(404, "pipeline not found")
        if self.controller is None:
            return error(400, "no controller attached")
        await self._stop_pipeline_jobs(pid, "checkpoint")
        if self._live_jobs(pid):
            return error(409, "running job did not stop; restart aborted")
        job = await self._submit_pipeline_job(
            pid, p["query"], p["parallelism"],
            tenant=p.get("tenant", "default"),
        )
        return json_response(job)

    async def _stop_pipeline_jobs(self, pid: str, mode: str):
        if self.controller is None:
            return
        for j in self.db.jobs_for_pipeline(pid):
            cjob = self.controller.jobs.get(j["id"])
            if cjob is not None and not cjob.state.is_terminal():
                await self.controller.stop_job(j["id"], mode)
                try:
                    await self.controller.wait_for_state(
                        j["id"], JobState.STOPPED, JobState.FAILED,
                        JobState.FINISHED, timeout=60,
                    )
                except TimeoutError:
                    pass
                cj = self.controller.jobs[j["id"]]
                self.db.update_job(j["id"], cj.state.value, cj.restarts)

    # -- jobs / checkpoints -------------------------------------------------

    async def pipeline_jobs(self, request: web.Request):
        return json_response(
            {"data": self.db.jobs_for_pipeline(request.match_info["id"])}
        )

    async def all_jobs(self, request: web.Request):
        return json_response({"data": self.db.all_jobs()})

    async def job_checkpoints(self, request: web.Request):
        jid = request.match_info["job_id"]
        if self.controller is None or jid not in self.controller.jobs:
            return json_response({"data": []})
        job = self.controller.jobs[jid]
        out = []
        if job.backend is not None:
            for epoch in sorted(job.checkpoints):
                out.append(
                    {
                        "epoch": epoch,
                        "tasks": len(job.checkpoints[epoch]),
                        "backend": job.backend.paths.checkpoint_dir(epoch),
                    }
                )
        return json_response({"data": out})

    async def operator_checkpoint_groups(self, request: web.Request):
        """Per-operator drill-down of one checkpoint (reference
        webui CheckpointDetails + api checkpoint details route): groups
        the tasks' completion reports by operator node with per-subtask
        state sizes, file/row counts and watermarks."""
        jid = request.match_info["job_id"]
        try:
            epoch = int(request.match_info["epoch"])
        except ValueError:
            return json_response({"data": []})
        job = self.controller.jobs.get(jid) if self.controller else None
        if job is None or epoch not in job.checkpoints:
            return json_response({"data": []})
        by_node: dict = {}
        for task_id, rep in sorted(job.checkpoints[epoch].items()):
            tables = []
            total_bytes = 0
            total_rows = 0
            # metadata nests per chained operator: {op{idx}: {table: meta}}
            for op_key, op_tables in (rep.get("metadata") or {}).items():
                for tname, meta in (op_tables or {}).items():
                    label = f"{op_key}/{tname}"
                    if meta.get("kind") == "global":
                        b = int(meta.get("bytes", 0))
                        tables.append({"table": label, "kind": "global",
                                       "bytes": b, "files": 1,
                                       "rows": None})
                        total_bytes += b
                    else:
                        files = meta.get("files") or []
                        b = sum(int(f.get("bytes", 0)) for f in files
                                if isinstance(f, dict))
                        r = sum(int(f.get("rows", 0)) for f in files
                                if isinstance(f, dict))
                        tables.append({"table": label, "kind": "time_key",
                                       "bytes": b, "files": len(files),
                                       "rows": r})
                        total_bytes += b
                        total_rows += r
            by_node.setdefault(rep.get("node_id"), []).append({
                "subtask": rep.get("subtask"),
                "task_id": task_id,
                "watermark": rep.get("watermark"),
                "bytes": total_bytes,
                "rows": total_rows,
                "tables": tables,
            })
        data = [
            {
                "node_id": nid,
                "bytes": sum(t["bytes"] for t in tasks),
                "tasks": sorted(tasks, key=lambda t: t["subtask"] or 0),
            }
            for nid, tasks in sorted(by_node.items(),
                                     key=lambda kv: kv[0] or 0)
        ]
        return json_response({"data": data, "epoch": epoch})

    async def job_traces(self, request: web.Request):
        """Flight-recorder export: this process's recorded spans for the
        job (trace ids are prefixed `{job_id}/`) as Chrome trace-event
        JSON — Perfetto-loadable directly, or merged across worker
        processes with tools/trace_report.py. `?trace=<id>` narrows to a
        single checkpoint epoch / lifecycle event."""
        from .. import obs

        jid = request.match_info["job_id"]
        spans = obs.recorder().snapshot(
            trace_prefix=f"{jid}/",
            trace_id=request.query.get("trace"),
        )
        if request.query.get("fmt") == "perfetto":
            # Perfetto export: spans plus the batch-phase timeline
            # ledger as named per-(job, phase) swimlanes
            body = obs.perfetto_trace(spans, job=jid)
        else:
            body = obs.chrome_trace(spans)
        body["spanCount"] = len(spans)
        return json_response(body)

    async def job_latency(self, request: web.Request):
        """Device-tier observatory surface: the job's latency-marker
        histograms (per-operator transit + end-to-end at the sinks, p50/
        p95/p99 in ms) and the XLA compile/dispatch telemetry summary
        (compiles, cache hit/miss, dispatch quantiles, padding waste,
        recompile-cause log). Reads this process's registry — merge
        worker dumps with tools/trace_report.py --latency for
        multi-process deployments."""
        from .. import obs

        return json_response(
            obs.latency_report(request.match_info["job_id"])
        )

    async def job_doctor(self, request: web.Request):
        """Bottleneck doctor (ISSUE 11): per-job busy ratio,
        backpressure, queue depth, watermark lag, dispatch floor,
        padding waste, loop lag and per-tenant attributed-cost shares
        combined into a ranked verdict naming the limiting operator and
        the suspected cause (host-bound / device-bound / exchange-bound
        / starved / noisy-neighbor — the latter names the co-resident
        tenant holding the shared worker). Reads this process's
        registry; for multi-process deployments run the doctor on each
        worker's admin server (/debug/doctor) or offline from a trace
        dump via tools/trace_report.py --doctor."""
        from ..obs import doctor

        jid = request.match_info["job_id"]
        if self.controller is not None and jid not in self.controller.jobs:
            return error(404, "job not found")
        rep = doctor.report(jid)
        if self.controller is not None:
            # StateServe wiring: a noisy-neighbor verdict squeezes the
            # suspect tenant's read quota at the serve gateway
            self.controller.serve.note_doctor_report(rep)
        return json_response(rep)

    # -- watchtower (ISSUE 13): alerts, metric history, bundles ------------

    def _watchtower(self):
        return getattr(self.controller, "watchtower", None)

    async def job_alerts(self, request: web.Request):
        """Watchtower SLO state for one job: per-rule alert states
        (ok/pending/firing/clearing — hysteresis per obs/watchtower.py)
        plus the job's slice of the firing/cleared ledger, each event
        carrying the cause series' recent history."""
        jid = request.match_info["job_id"]
        wt = self._watchtower()
        if wt is None:
            return json_response({"job": jid, "alerts": {},
                                  "firing": [], "ledger": []})
        return json_response(wt.alerts_for(jid))

    async def job_metrics_history(self, request: web.Request):
        """Retained metric history for one job: windowed samples plus
        derived rate/delta/quantiles per series (obs/history.py).
        `?series=<family>` narrows to one metric family, `?window=<s>`
        sets the lookback (default watch.window)."""
        from ..obs.history import HISTORY

        jid = request.match_info["job_id"]
        wt = self._watchtower()
        hist = wt.history if wt is not None else HISTORY
        try:
            window = float(request.query.get(
                "window", config().watch.window))
        except ValueError:
            return error(400, "bad window")
        series = request.query.get("series")
        return json_response({
            "job": jid,
            "window": window,
            "series": hist.export_job(jid, window=window, series=series),
        })

    async def job_audit(self, request: web.Request):
        """Conservation ledger for one job: per-edge epoch attestations
        (sender/receiver counts + digests), flow-check results and every
        recorded exactly-once breach (obs/audit.py)."""
        from ..obs import audit

        jid = request.match_info["job_id"]
        if (self.controller is not None and jid not in self.controller.jobs
                and audit.peek(jid) is None):
            return error(404, "job not found")
        return json_response(audit.status(jid))

    async def job_bundles(self, request: web.Request):
        """Diagnostic bundles captured for the job's SLO breaches:
        the bounded-spool index (download one via .../bundles/{n})."""
        jid = request.match_info["job_id"]
        wt = self._watchtower()
        metas = wt.bundles_for(jid) if wt is not None else []
        return json_response({"data": metas})

    async def job_bundle(self, request: web.Request):
        """Download one diagnostic bundle (doctor verdict + flight
        recording + Perfetto timeline + metric-history window around
        the breach) by sequence number."""
        jid = request.match_info["job_id"]
        wt = self._watchtower()
        try:
            n = int(request.match_info["n"])
        except ValueError:
            return error(400, "bad bundle number")
        bundle = wt.bundle(n) if wt is not None else None
        if bundle is None or bundle.get("job") not in (None, jid):
            return error(404, "no such bundle")
        return json_response(bundle)

    # -- queryable state (StateServe, ISSUE 12) ----------------------------

    async def job_state_tables(self, request: web.Request):
        """List the job's queryable tables: every keyed operator view
        (windowed aggregates, updating aggregates) with its key/value
        fields, parallelism and routability, plus the published epoch
        reads are currently served at."""
        jid = request.match_info["job_id"]
        if self.controller is None or jid not in self.controller.jobs:
            return error(404, "job not found")
        job = self.controller.jobs[jid]
        tables = await self.controller.serve.tables(jid)
        # follower replicas (ISSUE 20): surface whether reads route to
        # the follower tier and how far it trails publication
        replicas = getattr(self.controller, "replicas", None)
        lag = replicas.lag_epochs(job) if replicas is not None else None
        return json_response({
            "data": sorted(tables.values(), key=lambda d: d["table"]),
            "publishedEpoch": job.published_epoch,
            "replicaLagEpochs": lag,
            "state": job.state.value,
        })

    @staticmethod
    def _parse_state_key(raw: str):
        """`?key=` values parse as JSON where possible (numbers, quoted
        strings, composite `[a, b]` keys) and fall back to the raw
        string — `?key=42` is an int lookup, `?key=abc` a string one."""
        try:
            return json.loads(raw)
        except (json.JSONDecodeError, ValueError):
            return raw

    def _state_read_response(self, out: dict):
        status = out.pop("status", 200)
        out.pop("outcome", None)
        if "error" in out and "results" not in out:
            return json_response(
                {"error": out["error"],
                 "retriable": bool(out.get("retriable"))},
                status=status,
            )
        return json_response(out, status=status)

    async def job_state_get(self, request: web.Request):
        """Point lookup: GET .../state/{table}?key=K (epoch-consistent:
        the value is the key's aggregate at the last published
        checkpoint epoch; retriable errors mean back off and retry)."""
        if self.controller is None:
            return error(400, "no controller attached")
        raw = request.query.get("key")
        if raw is None:
            return error(400, "key query parameter is required")
        out = await self.controller.serve.read(
            request.match_info["job_id"], request.match_info["table"],
            [self._parse_state_key(raw)],
        )
        return self._state_read_response(out)

    async def job_state_bulk(self, request: web.Request):
        """Bulk multi-key lookup: POST {"keys": [k1, [k2a, k2b], ...]} —
        keys fan out to their owning workers concurrently and merge
        into one response (per-key found/value/error entries)."""
        if self.controller is None:
            return error(400, "no controller attached")
        body = await request.json()
        keys = body.get("keys")
        if not isinstance(keys, list) or not keys:
            return error(400, "body must carry a non-empty 'keys' list")
        out = await self.controller.serve.read(
            request.match_info["job_id"], request.match_info["table"],
            keys,
        )
        return self._state_read_response(out)

    def _autoscale_status(self, job) -> dict:
        return {
            "enabled": bool(config().autoscale.enabled),
            "policy": config().autoscale.policy,
            "pinned": job.autoscale_pinned,
            "rescales": job.rescales,
            "parallelism": {
                str(n.node_id): n.parallelism
                for n in job.graph.nodes.values()
            },
            "decisions": list(job.autoscale_decisions),
        }

    async def job_autoscale(self, request: web.Request):
        """Autoscaler surface: the job's decision audit log (one entry per
        control period: action, per-node targets, the signals they were
        decided from) plus pin state and current parallelism."""
        jid = request.match_info["job_id"]
        job = self.controller.jobs.get(jid) if self.controller else None
        if job is None:
            return error(404, "job not found")
        return json_response(self._autoscale_status(job))

    async def patch_job_autoscale(self, request: web.Request):
        """Pin (freeze automatic rescaling — decisions keep recording) or
        unpin a job: {"pinned": true|false}."""
        jid = request.match_info["job_id"]
        job = self.controller.jobs.get(jid) if self.controller else None
        if job is None:
            return error(404, "job not found")
        body = await request.json()
        if not isinstance(body.get("pinned"), bool):
            return error(400, "body must carry a boolean 'pinned'")
        job.autoscale_pinned = body["pinned"]
        return json_response(self._autoscale_status(job))

    async def job_errors(self, request: web.Request):
        jid = request.match_info["job_id"]
        job = self.controller.jobs.get(jid) if self.controller else None
        return json_response(
            {"data": [{"message": job.failure}] if job and job.failure else []}
        )

    async def operator_metric_groups(self, request: web.Request):
        """Per-operator metric groups (reference api/src/metrics.rs
        OperatorMetricGroup): task-labeled counters grouped by logical
        node, one single-point series per subtask (the UI polls and
        accumulates). The raw Prometheus text rides along for debugging."""
        import time as _time

        from ..metrics import REGISTRY, hist_quantiles

        now = int(_time.time() * 1000)
        job_id = request.match_info["job_id"]
        # operator id -> metric name -> subtask index -> value
        ops: dict = {}
        for name, entries in REGISTRY.snapshot().items():
            short = name.removeprefix("arroyo_worker_")
            for labels, value in entries:
                # split per-phase families (checkpoint_phase_seconds) into
                # one scalar series per phase; state families split per
                # table the same way (arroyo_state_bytes:sess, ...)
                metric = (f"{short}:{labels['phase']}"
                          if "phase" in labels else short)
                if "table" in labels:
                    metric = f"{metric}:{labels['table']}"
                task = labels.get("task")
                if task is None or "-" not in task:
                    continue
                if labels.get("job") != job_id:
                    continue  # counters from other jobs in this process
                node_id, _, sub = task.rpartition("-")
                try:
                    sub_i = int(sub)
                except ValueError:
                    continue
                if isinstance(value, dict):
                    # histogram snapshot ({sum, count, buckets}): one
                    # scalar series for the running mean plus tail
                    # quantiles estimated from the cumulative buckets —
                    # the autoscaler's audit log and the UI sparklines
                    # both need p95/p99, not just the mean
                    series = [(
                        metric,
                        value["sum"] / value["count"]
                        if value.get("count") else 0.0,
                    )]
                    series += [
                        (f"{metric}:{q}", v)
                        for q, v in sorted(hist_quantiles(value).items())
                    ]
                else:
                    series = [(metric, value)]
                for mname, v in series:
                    ops.setdefault(node_id, {}).setdefault(mname, {})[
                        sub_i
                    ] = v
        # device-tier families carry a `program` label instead of a task:
        # surface them under a synthetic "__device__" operator (one
        # series per program — the exchange/dispatch cost of the mesh
        # tier belongs beside the per-operator groups, not orphaned in
        # the raw prometheus text)
        for name, entries in REGISTRY.snapshot().items():
            if not (name.startswith("arroyo_device_")
                    or name.startswith("arroyo_xla_")):
                continue
            short = name.removeprefix("arroyo_")
            for labels, value in entries:
                program = labels.get("program")
                if program is None:
                    continue
                suffix = "".join(
                    f":{labels[k]}" for k in sorted(labels)
                    if k != "program"
                )
                metric = f"{short}:{program}{suffix}"
                if isinstance(value, dict):
                    series = [(
                        metric,
                        value["sum"] / value["count"]
                        if value.get("count") else 0.0,
                    )]
                    series += [
                        (f"{metric}:{q}", v)
                        for q, v in sorted(hist_quantiles(value).items())
                    ]
                else:
                    series = [(metric, value)]
                for mname, v in series:
                    ops.setdefault("__device__", {}).setdefault(
                        mname, {}
                    )[0] = v
        data = [
            {
                "operatorId": op,
                "metricGroups": [
                    {
                        "name": metric,
                        "subtasks": [
                            {"index": i,
                             "metrics": [{"time": now, "value": v}]}
                            for i, v in sorted(subs.items())
                        ],
                    }
                    for metric, subs in sorted(groups.items())
                ],
            }
            for op, groups in sorted(ops.items())
        ]
        return json_response(
            {"data": data, "prometheus": REGISTRY.expose()}
        )

    # -- preview ------------------------------------------------------------

    async def preview_pipeline(self, request: web.Request):
        """Bounded preview run executed in-process (reference: preview
        pipelines with the preview sink + websocket output tail)."""
        body = await request.json()
        query = body.get("query")
        if not query:
            return error(400, "query is required")
        results: list = []
        try:
            plan = plan_query(query, preview_results=results)
        except SqlError as e:
            return error(400, str(e))
        from ..engine import Engine

        pid = self.db.create_pipeline(body.get("name", "preview"), query, 1)
        # mark in the DB so the TTL sweep can find preview rows whose
        # registry entry is gone (cap eviction, process restart)
        self.db.set_pipeline_state(pid["id"], "Preview")
        self.previews[pid["id"]] = {"rows": results, "done": False,
                                    "created": time.time()}

        async def run():
            eng = None
            try:
                eng = Engine(plan.graph).start()
                await eng.join(body.get("timeout", 60))
            except Exception as e:  # noqa: BLE001
                self.previews[pid["id"]]["error"] = str(e)
                if eng is not None:
                    # a timed-out preview must not keep burning CPU
                    from ..types import StopMode

                    await eng.stop(StopMode.IMMEDIATE)
                    for t in eng.tasks:
                        t.cancel()
            finally:
                self.previews[pid["id"]]["done"] = True
                done_ids = [
                    k for k, v in self.previews.items()
                    if v.get("done") and k != pid["id"]
                ]
                while len(self.previews) > 20 and done_ids:
                    # evict finished previews only: a running preview's
                    # cleanup still needs its entry
                    self.previews.pop(done_ids.pop(0), None)

        self._spawn(run())
        return json_response(pid)

    def cleanup_previews(self, now: Optional[float] = None) -> int:
        """TTL sweep over stale previews (reference: the controller
        update loop cleans stale preview pipelines, arroyo-controller
        lib.rs:600-706). Two sources: FINISHED registry entries past the
        TTL, and DB rows in state 'Preview' past the TTL with no live
        registry entry — those cover cap-evicted previews and previews
        from a previous process (the registry is in-memory). Returns the
        number removed."""
        from ..config import config as config_fn

        ttl = float(config_fn().api.preview_ttl or 0)
        if ttl <= 0:
            return 0
        now = time.time() if now is None else now
        stale = [
            pid for pid, pv in self.previews.items()
            if pv.get("done") and now - pv.get("created", now) > ttl
        ]
        try:
            stale += [
                p["id"] for p in self.db.list_pipelines()
                if p.get("state") == "Preview"
                and now - p.get("created_at", now) > ttl
                # a LIVE registry entry means the preview may still be
                # running; only its own done+TTL path may remove it
                and p["id"] not in self.previews
            ]
        except Exception as e:  # noqa: BLE001 - sweep must not die
            logger.warning("preview ttl: db scan failed: %s", e)
        n = 0
        for pid in dict.fromkeys(stale):
            self.previews.pop(pid, None)
            try:
                self.db.delete_pipeline(pid)
                n += 1
            except Exception as e:  # noqa: BLE001
                logger.warning("preview ttl: delete %s failed: %s", pid, e)
        return n

    async def preview_ttl_loop(self):
        while True:
            await asyncio.sleep(30.0)
            try:
                n = self.cleanup_previews()
                if n:
                    logger.info("preview ttl: removed %d stale previews", n)
            except Exception as e:  # noqa: BLE001
                logger.warning("preview ttl sweep failed: %s", e)

    async def preview_output(self, request: web.Request):
        pv = self.previews.get(request.match_info["id"])
        if pv is None:
            return error(404, "no preview for pipeline")
        return json_response(
            {"rows": pv["rows"], "done": pv["done"],
             "error": pv.get("error")}
        )

    async def preview_output_ws(self, request: web.Request):
        """Websocket tail of preview rows (reference: job output ws)."""
        pv = self.previews.get(request.match_info["id"])
        if pv is None:
            return error(404, "no preview for pipeline")
        ws = web.WebSocketResponse()
        await ws.prepare(request)
        sent = 0
        while not ws.closed:
            rows = pv["rows"]
            while sent < len(rows):
                await ws.send_json(rows[sent], dumps=lambda d: json.dumps(
                    d, default=str))
                sent += 1
            if pv["done"]:
                break
            await asyncio.sleep(0.1)
        await ws.close()
        return ws

    # -- connectors / connections ------------------------------------------

    async def list_connectors(self, request: web.Request):
        from ..connectors import connectors

        return json_response({"data": [c.metadata() for c in connectors()]})

    async def list_connection_profiles(self, request: web.Request):
        return json_response({"data": self.db.list_connection_profiles()})

    async def create_connection_profile(self, request: web.Request):
        body = await request.json()
        return json_response(
            self.db.create_connection_profile(
                body["name"], body["connector"], body.get("config", {})
            )
        )

    async def list_connection_tables(self, request: web.Request):
        return json_response({"data": self.db.list_connection_tables()})

    async def create_connection_table(self, request: web.Request):
        from ..connectors import get_connector

        body = await request.json()
        try:
            conn = get_connector(body["connector"])
            conn.validate_options(body.get("config", {}), None)
        except (ValueError, KeyError) as e:
            return error(400, str(e))
        return json_response(
            self.db.create_connection_table(
                body["name"], body["connector"], body.get("config", {}),
                body.get("schema"), body.get("table_type", "source"),
                body.get("profile_id"),
            )
        )

    async def delete_connection_table(self, request: web.Request):
        self.db.delete_connection_table(request.match_info["id"])
        return json_response({"deleted": request.match_info["id"]})

    async def test_connection_table(self, request: web.Request):
        from ..connectors import get_connector

        body = await request.json()
        try:
            conn = get_connector(body["connector"])
            cfg = conn.validate_options(body.get("config", {}), None)
            ok, message = conn.test(cfg)
        except (ValueError, KeyError) as e:
            ok, message = False, str(e)
        return json_response({"ok": ok, "message": message})

    # -- udfs ---------------------------------------------------------------

    async def validate_udf(self, request: web.Request):
        from ..udf import registry

        body = await request.json()
        snap = registry.snapshot()
        try:
            names = registry.register_from_source(body["definition"])
        except Exception as e:  # noqa: BLE001 - user code boundary
            return json_response({"errors": [str(e)]}, status=400)
        finally:
            registry.restore(snap)  # validation must not mutate the registry
        return json_response({"udfs": names, "errors": []})

    async def create_udf(self, request: web.Request):
        from ..udf import registry

        body = await request.json()
        try:
            names = registry.register_from_source(body["definition"])
        except Exception as e:  # noqa: BLE001
            return error(400, str(e))
        if not names:
            return error(400, "definition registers no UDFs")
        return json_response(
            self.db.create_udf(names[0], body["definition"])
        )

    async def list_udfs(self, request: web.Request):
        return json_response({"data": self.db.list_udfs()})

    async def delete_udf(self, request: web.Request):
        self.db.delete_udf(request.match_info["id"])
        return json_response({"deleted": request.match_info["id"]})

    async def ping(self, request: web.Request):
        return json_response({"pong": True})


def build_app(controller: Optional[ControllerServer] = None,
              db_path: Optional[str] = None) -> web.Application:
    api = ApiServer(controller, db_path)
    # re-register saved UDFs so pipelines can use them after restarts
    from ..udf import registry as udf_registry

    for u in api.db.list_udfs():
        try:
            udf_registry.register_from_source(u["definition"])
        except Exception:  # noqa: BLE001
            logger.warning("failed to re-register udf %s", u["name"])

    app = web.Application()
    r = app.router
    v1 = "/api/v1"
    # routes register from the same table that generates the OpenAPI spec
    # (openapi.py ROUTES), so /api/v1/openapi.json cannot drift
    from .openapi import ROUTES, build_spec

    for method, path, handler, *_ in ROUTES:
        if method == "get":  # add_get also registers HEAD
            r.add_get(v1 + path, getattr(api, handler))
        else:
            r.add_route(method.upper(), v1 + path, getattr(api, handler))

    spec = build_spec(v1)

    async def openapi_json(request: web.Request):
        return json_response(spec)

    r.add_get(f"{v1}/openapi.json", openapi_json)
    from .console import add_console_routes

    add_console_routes(app)
    app["api"] = api

    async def _preview_ttl_ctx(app_):
        task = asyncio.ensure_future(api.preview_ttl_loop())
        yield
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)

    app.cleanup_ctx.append(_preview_ttl_ctx)
    return app


async def serve_api(port: Optional[int] = None,
                    controller: Optional[ControllerServer] = None):
    cfg = config()
    app = build_app(controller)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(
        runner, cfg.api.bind_address, port or cfg.api.http_port
    )
    await site.start()
    logger.info("api listening on %s:%s", cfg.api.bind_address,
                port or cfg.api.http_port)
    await asyncio.Event().wait()
