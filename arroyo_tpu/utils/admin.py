"""Per-process admin HTTP server: /status, /metrics, /debug/*.

Capability parity with the reference's admin server
(/root/reference/crates/arroyo-server-common/src/lib.rs start_admin_server:
/status, /name, /metrics, /debug/pprof): every role (controller, worker,
api) can expose liveness, Prometheus metrics, a stack/task dump, and a
windowed CPU profile capture (/debug/profile — the Python analog of the
reference's /debug/pprof/profile flamegraph endpoint,
arroyo-server-common/src/profile.rs:12-51) on a local port.
"""

from __future__ import annotations

import asyncio
import io
import time
from typing import Optional

from aiohttp import web

from ..config import config
from ..utils.logging import get_logger

logger = get_logger("admin")

_STARTED = time.time()


def build_admin_app(role: str, details_fn=None,
                    extra_routes: Optional[dict] = None) -> web.Application:
    """`details_fn() -> dict` supplies role-specific status fields;
    `extra_routes` maps paths to aiohttp GET handlers for role-specific
    debug surfaces (the controller mounts /debug/autoscale this way)."""

    async def status(request: web.Request):
        body = {
            "service": f"arroyo-tpu-{role}",
            "status": "ok",
            "uptime_seconds": round(time.time() - _STARTED, 1),
        }
        if details_fn is not None:
            try:
                body.update(details_fn() or {})
            except Exception as e:  # noqa: BLE001
                body["details_error"] = repr(e)
        return web.json_response(body)

    async def name(request: web.Request):
        return web.Response(text=f"arroyo-tpu-{role}\n")

    async def metrics(request: web.Request):
        from ..metrics import REGISTRY

        return web.Response(
            text=REGISTRY.expose(),
            content_type="text/plain",
        )

    async def debug_tasks(request: web.Request):
        lines = []
        for t in asyncio.all_tasks():
            coro = t.get_coro()
            lines.append(
                f"{'CANCELLED' if t.cancelled() else 'DONE' if t.done() else 'RUNNING'} "
                f"{getattr(coro, '__qualname__', coro)}"
            )
        return web.Response(text="\n".join(sorted(lines)) + "\n",
                            content_type="text/plain")

    async def debug_stacks(request: web.Request):
        import sys
        import threading
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        buf = io.StringIO()
        for tid, frame in sys._current_frames().items():
            buf.write(f"Thread {names.get(tid, tid)}:\n")
            buf.write("".join(traceback.format_stack(frame)))
            buf.write("\n")
        return web.Response(text=buf.getvalue(), content_type="text/plain")

    profile_lock = asyncio.Lock()

    async def debug_profile(request: web.Request):
        """CPU profile capture over a sampling window (reference:
        /debug/pprof/profile flamegraphs, arroyo-server-common
        profile.rs:12-51). cProfile wraps the event-loop thread for
        ?seconds=N (default 5, max 60) and returns the pstats table
        sorted by ?sort= (tottime default) — round-4's perf work leaned
        on ad-hoc cProfile runs; this standardizes the capture."""
        import cProfile
        import pstats

        try:
            seconds = min(float(request.query.get("seconds", 5)), 60.0)
            # row budget for the pstats table: stage-budget consumers
            # (tools/mesh_profile.py) need the long tail, humans don't
            limit = min(int(request.query.get("limit", 60)), 1000)
        except ValueError:
            return web.Response(status=400, text="bad seconds/limit\n")
        sort = request.query.get("sort", "tottime")
        if sort not in ("tottime", "cumulative", "ncalls"):
            return web.Response(status=400, text="bad sort\n")
        if profile_lock.locked():
            return web.Response(status=409,
                                text="profile already in progress\n")
        async with profile_lock:
            pr = cProfile.Profile()
            pr.enable()
            try:
                await asyncio.sleep(seconds)
            finally:
                pr.disable()
        buf = io.StringIO()
        pstats.Stats(pr, stream=buf).sort_stats(sort).print_stats(limit)
        return web.Response(text=buf.getvalue(), content_type="text/plain")

    async def debug_trace(request: web.Request):
        """Flight-recorder dump: the process's span ring buffer as Chrome
        trace-event JSON (load in Perfetto / chrome://tracing; merge
        multi-process dumps with tools/trace_report.py). Query params:
        ?trace=<id> filters one trace, ?prefix=<job_id>/ one job,
        ?clear=1 empties the buffer after the dump."""
        from .. import obs

        rec = obs.recorder()
        spans = rec.snapshot(
            trace_prefix=request.query.get("prefix"),
            trace_id=request.query.get("trace"),
        )
        if request.query.get("fmt") == "perfetto":
            # fleet-observatory export: spans + the batch-phase timeline
            # ledger as named per-(job, phase) swimlanes (?prefix= still
            # narrows spans; phase entries filter by the prefix's job)
            prefix = request.query.get("prefix") or ""
            body = obs.perfetto_trace(
                spans, job=prefix.rstrip("/") or None
            )
        else:
            body = obs.chrome_trace(spans)
        body["spanCount"] = len(spans)
        body["dropped"] = rec.dropped
        if request.query.get("clear"):
            rec.clear()
        return web.json_response(body)

    async def debug_latency(request: web.Request):
        """Device-tier observatory dump: this process's latency-marker
        quantiles (per-operator + end-to-end) and XLA compile/dispatch
        telemetry, including the recompile-cause log. ?job=<id> narrows
        to one job's subtasks."""
        from .. import obs

        return web.json_response(
            obs.latency_report(request.query.get("job"))
        )

    async def debug_attribution(request: web.Request):
        """Fleet-observatory dump: per-job attributed wall/CPU/device
        seconds, dispatch counts and bytes, the coverage ratio vs the
        unattributed bucket, and event-loop lag percentiles — the
        numbers that let an operator audit the admission ledger's
        fair-share grants against actual consumption on a multiplexed
        worker."""
        from ..obs import attribution

        return web.json_response(attribution.ACCOUNTING.summary())

    async def debug_history(request: web.Request):
        """Metric-history tier dump for THIS process (ISSUE 13): ring
        stats plus, with ?job=<id>, the job's retained series with
        windowed rate/delta/quantiles (?window=<s>, ?series=<family>).
        The controller's /debug/watch adds SLO/alert state on top; this
        route exists on every role so a worker's local history is
        inspectable in multi-process deployments."""
        from ..obs.history import HISTORY

        doc = {"history": HISTORY.stats(),
               "families": HISTORY.families()}
        job = request.query.get("job")
        if job:
            try:
                window = float(request.query.get(
                    "window", config().watch.window))
            except ValueError:
                return web.Response(status=400, text="bad window\n")
            doc["job"] = job
            doc["window"] = window
            doc["series"] = HISTORY.export_job(
                job, window=window, series=request.query.get("series"))
        return web.json_response(doc)

    async def debug_timeline(request: web.Request):
        """The phase ledger's totals for THIS process (`obs/timeline.py`):
        per phase count, wall and self seconds and, beside them, the CPU
        seconds of the thread that ran it (`cpu_s`, `self_cpu_s`: wall
        less CPU is time off a core), with the recorder's lists of what
        encloses, what waits and what waits for the device. ?job=<id>
        narrows to one job (`loop.idle` and `loop.run` are the process's:
        job ""); ?last=<s> to the newest seconds."""
        from ..obs import timeline

        try:
            last = float(request.query.get("last", 0))
        except ValueError:
            return web.Response(status=400, text="bad last\n")
        t0_us = (time.time() - last) * 1e6 if last > 0 else None
        return web.json_response({
            "totals": timeline.totals(t0_us, job=request.query.get("job")),
            "enclosing": timeline.ENCLOSING, "waits": timeline.WAITS,
            "device_waits": timeline.DEVICE_WAITS,
        })

    async def debug_doctor(request: web.Request):
        """Bottleneck doctor for one job hosted in this process:
        ?job=<id> (required) returns the ranked limiting-factor verdict
        (see obs/doctor.py). The REST equivalent is
        GET /api/v1/jobs/{id}/doctor."""
        from ..obs import doctor

        job = request.query.get("job")
        if not job:
            return web.Response(status=400, text="job param required\n")
        return web.json_response(doctor.report(job))

    async def debug_state(request: web.Request):
        """State-at-scale dump: per-(task, table, kind) state sizes, rows,
        spill bytes and global-table delta-chain lengths from the
        scrape-time-refreshed gauges — the live numbers the rebase/spill
        knobs (state.rebase_epochs, state.memory_budget_bytes) are tuned
        from. ?job=<id> narrows to one job's subtasks."""
        from ..metrics import REGISTRY

        job = request.query.get("job")
        snap = REGISTRY.snapshot()
        tables: dict = {}
        fields = {
            "arroyo_state_bytes": "bytes",
            "arroyo_state_rows": "rows",
            "arroyo_state_spilled_bytes": "spilled_bytes",
            "arroyo_state_delta_chain_len": "chain_len",
        }
        for family, field in fields.items():
            for labels, value in snap.get(family, []):
                if job and labels.get("job") != job:
                    continue
                key = (labels.get("task", ""), labels.get("table", ""))
                ent = tables.setdefault(key, {
                    "task": labels.get("task"),
                    "table": labels.get("table"),
                    "kind": labels.get("kind"),
                })
                if labels.get("kind") and not ent.get("kind"):
                    ent["kind"] = labels["kind"]
                ent[field] = value
        return web.json_response({
            "tables": sorted(
                tables.values(),
                key=lambda e: (e["task"] or "", e["table"] or ""),
            ),
        })

    app = web.Application()
    app.router.add_get("/status", status)
    app.router.add_get("/name", name)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/state", debug_state)
    app.router.add_get("/debug/tasks", debug_tasks)
    app.router.add_get("/debug/stacks", debug_stacks)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_get("/debug/latency", debug_latency)
    app.router.add_get("/debug/history", debug_history)
    app.router.add_get("/debug/attribution", debug_attribution)
    app.router.add_get("/debug/timeline", debug_timeline)
    app.router.add_get("/debug/doctor", debug_doctor)
    for path, handler in (extra_routes or {}).items():
        app.router.add_get(path, handler)
    return app


async def serve_admin(role: str, details_fn=None,
                      port: Optional[int] = None,
                      extra_routes: Optional[dict] = None):
    """Start the admin server; returns (runner, bound port). Port 0 binds
    an ephemeral port; admin.http_port < 0 disables (returns (None, 0))."""
    cfg = config().admin
    if port is None:
        port = cfg.http_port
    if port < 0:
        return None, 0
    app = build_admin_app(role, details_fn, extra_routes=extra_routes)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, cfg.bind_address, port)
    try:
        await site.start()
    except OSError as e:
        # a fixed port is already held by another role on this host; the
        # admin surface is advisory, so log and continue without it
        logger.warning("admin server bind failed on port %s: %s", port, e)
        await runner.cleanup()
        return None, 0
    bound = site._server.sockets[0].getsockname()[1]
    logger.info("admin server for %s on %s:%s", role, cfg.bind_address, bound)
    return runner, bound
