"""Watchtower (ISSUE 13): the per-job SLO engine + breach actions.

Evaluates declarative SLO rules over the retained metric history
(`obs/history.py`) for every non-terminal job on the controller, with
hysteresis (breach/clear thresholds + sustain windows — the
`ActuationGate` warmup/cooldown pattern applied to alerting) so a
signal wobbling on a threshold cannot flap an alert. Built-in rules:

  freshness        max subtask watermark lag (the "is data flowing"
                   SLO — a stalled tenant's lag grows unboundedly);
  e2e_p99          end-to-end latency-marker p99 over the window
                   (the PR 6 Flink-style markers, windowed);
  throughput       processed/emitted rate ratio (sustained backlog);
  checkpoint       seconds since the published epoch last advanced
                   (epoch stall on a durable job);
  serve_p99        serve-gateway read latency p99 over the window;
  loop_lag         event-loop lag p99 (shared-worker contention);
  trace_drops      flight-recorder span-drop rate (the recording of
                   the NEXT incident is silently incomplete);
  conservation     exactly-once conservation breaches recorded by the
                   audit ledger (obs/audit.py) — any count above zero
                   means rows were duplicated, lost, or re-emitted.

Per-tenant / per-job threshold overrides ride `watch.overrides`.

Breach actions: every firing/cleared transition lands in a bounded
alert ledger (with the cause series' recent history attached) and in
`arroyo_watch_alerts_total`; the FIRING transition additionally
captures a diagnostic bundle — doctor verdict + flight-recorder dump +
Perfetto timeline + the metric-history window around the breach —
into a bounded on-disk spool, downloadable via
`GET /api/v1/jobs/{id}/bundles[/{n}]`. The 3am question "what was
happening when it broke" is answered by an artifact captured at the
moment the SLO engine noticed, not by whatever survived until morning.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import re
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from ..config import config
from ..utils.logging import get_logger
from . import timeline
from .history import HISTORY, MetricHistory

logger = get_logger("watchtower")

_WM_LAG = "arroyo_worker_watermark_lag_seconds"
_E2E = "arroyo_worker_e2e_latency_seconds"
_RECV = "arroyo_worker_messages_recv"
_SENT = "arroyo_worker_messages_sent"
_EPOCH = "arroyo_job_published_epoch"
_SERVE = "arroyo_serve_request_seconds"
_LOOP_LAG = "arroyo_worker_loop_lag_seconds"
_TRACE_DROPS = "arroyo_trace_dropped_spans_total"
_AUDIT_BREACHES = "arroyo_audit_breaches_total"
_REPLICA_LAG = "arroyo_replica_lag_epochs"


@dataclasses.dataclass
class SLOContext:
    """What a rule signal may read: one job's identity + the history."""

    job_id: str
    tenant: str
    history: MetricHistory
    window: float
    now: float
    job: object = None  # JobHandle when evaluated on a controller


def _merge_hist_windows(series: List, window: float,
                        now: float) -> Optional[dict]:
    """Union several series' windowed histograms (e.g. every terminal
    subtask's e2e marker histogram) into one snapshot for a job-level
    quantile."""
    merged: Optional[dict] = None
    for s in series:
        h = s.hist_window(window, now)
        if not h:
            continue
        if merged is None:
            merged = {"sum": 0.0, "count": 0, "buckets": {}}
        merged["sum"] += h["sum"]
        merged["count"] += h["count"]
        for le, c in h["buckets"].items():
            merged["buckets"][le] = merged["buckets"].get(le, 0) + c
    return merged


def _windowed_p99(ctx: SLOContext, family: str, **labels) -> Optional[float]:
    from ..metrics import hist_quantiles

    series = ctx.history.get(family, **labels)
    h = _merge_hist_windows(series, ctx.window, ctx.now)
    q = hist_quantiles(h, (0.99,)) if h else {}
    return q.get("p99")


# -- built-in rule signals ----------------------------------------------------


def sig_freshness(ctx: SLOContext) -> Optional[float]:
    vals = [
        s.latest() for s in ctx.history.get(_WM_LAG, job=ctx.job_id)
    ]
    vals = [float(v) for v in vals if v is not None]
    return max(vals) if vals else None


def sig_e2e_p99(ctx: SLOContext) -> Optional[float]:
    return _windowed_p99(ctx, _E2E, job=ctx.job_id)


def sig_throughput(ctx: SLOContext) -> Optional[float]:
    """Processed-vs-produced ratio: windowed recv rate of the job's
    non-source tasks over the windowed sent rate of its source tasks.
    ~1 in steady state; sustained <1 means the pipeline consumes slower
    than the sources emit (backlog). Abstains below the source-rate
    floor or without a graph to split sources from."""
    job = ctx.job
    if job is None or getattr(job, "graph", None) is None:
        return None
    graph = job.graph
    dsts = {e.dst for e in graph.edges}
    sources = {str(nid) for nid in graph.nodes if nid not in dsts}
    if not sources or len(sources) == len(graph.nodes):
        return None

    def node_of(series) -> str:
        task = series.label("task")
        node, _, _sub = task.rpartition("-")
        return node

    sent = [
        r for r in (
            s.rate(ctx.window, ctx.now)
            for s in ctx.history.get(_SENT, job=ctx.job_id)
            if node_of(s) in sources
        ) if r is not None
    ]
    recv = [
        r for r in (
            s.rate(ctx.window, ctx.now)
            for s in ctx.history.get(_RECV, job=ctx.job_id)
            if node_of(s) not in sources
        ) if r is not None
    ]
    src_rate = sum(sent)
    if not sent or src_rate < float(config().watch.throughput_min_eps):
        return None
    # normalize by the source fan-out: each source row is received once
    # per outgoing edge of the source tier
    fan = max(1, len({e.dst for e in graph.edges
                      if str(e.src) in sources}))
    return (sum(recv) / fan) / src_rate


def sig_checkpoint_age(ctx: SLOContext) -> Optional[float]:
    job = ctx.job
    if job is not None and getattr(job, "backend", None) is None:
        return None  # non-durable jobs have no epochs to stall
    series = ctx.history.get(_EPOCH, job=ctx.job_id)
    ages = [a for a in (s.last_change_age(ctx.now) for s in series)
            if a is not None]
    return max(ages) if ages else None


def sig_serve_p99(ctx: SLOContext) -> Optional[float]:
    return _windowed_p99(ctx, _SERVE, job=ctx.job_id)


def sig_loop_lag(ctx: SLOContext) -> Optional[float]:
    return _windowed_p99(ctx, _LOOP_LAG)


def sig_conservation(ctx: SLOContext) -> Optional[float]:
    """Conservation-ledger breach count for the job (obs/audit.py):
    any recorded breach — digest/count mismatch, flow violation, rewind
    behind commit, zombie append — fires the rule. Abstains until the
    job's reconciler exists (no attested epoch yet)."""
    from . import audit

    return audit.breach_count(ctx.job_id)


def sig_replica_staleness(ctx: SLOContext) -> Optional[float]:
    """Follower read-replica lag (ISSUE 20): epochs the job's follower
    trails publication (arroyo_replica_lag_epochs). Transiently 1 while
    a tail is in flight — the threshold defaults above that so only a
    STUCK follower (storage trouble, death/reattach loop) pages, with
    the rule's sustain window supplying the time dimension. Abstains
    for jobs with no mounted follower (no series)."""
    vals = [
        s.latest() for s in ctx.history.get(_REPLICA_LAG, job=ctx.job_id)
    ]
    vals = [float(v) for v in vals if v is not None]
    return max(vals) if vals else None


def sig_trace_drops(ctx: SLOContext) -> Optional[float]:
    rates = [
        r for r in (
            s.rate(ctx.window, ctx.now)
            for s in ctx.history.get(_TRACE_DROPS)
        ) if r is not None
    ]
    return max(rates) if rates else None


@dataclasses.dataclass
class RuleSpec:
    """One resolved SLO rule: signal + hysteresis parameters. `kind`
    is 'above' (breach when value > threshold) or 'below'."""

    name: str
    description: str
    signal: Callable[[SLOContext], Optional[float]]
    kind: str
    threshold: float
    clear: float
    sustain: float
    clear_sustain: float
    cause_family: str
    unit: str = "s"

    def breached(self, value: float) -> bool:
        return value > self.threshold if self.kind == "above" \
            else value < self.threshold

    def cleared(self, value: float) -> bool:
        return value <= self.clear if self.kind == "above" \
            else value >= self.clear

    def describe(self) -> dict:
        return {
            "name": self.name, "description": self.description,
            "kind": self.kind, "threshold": self.threshold,
            "clear": self.clear, "sustain": self.sustain,
            "clear_sustain": self.clear_sustain, "unit": self.unit,
        }


# (name, description, signal, kind, config threshold attr, cause family,
# unit) — thresholds resolve from watch.* at evaluation time so config
# overrides and tests see live values
BUILTIN_RULES: Tuple[tuple, ...] = (
    ("freshness", "max subtask watermark lag", sig_freshness, "above",
     "freshness_lag_s", _WM_LAG, "s"),
    ("e2e_p99", "end-to-end latency-marker p99 over the window",
     sig_e2e_p99, "above", "e2e_p99_s", _E2E, "s"),
    ("throughput", "processed/emitted rate ratio vs the sources",
     sig_throughput, "below", "throughput_ratio", _RECV, "ratio"),
    ("checkpoint", "seconds since the published epoch advanced",
     sig_checkpoint_age, "above", "checkpoint_age_s", _EPOCH, "s"),
    ("serve_p99", "serve-gateway read latency p99 over the window",
     sig_serve_p99, "above", "serve_p99_s", _SERVE, "s"),
    ("loop_lag", "event-loop lag p99 over the window", sig_loop_lag,
     "above", "loop_lag_s", _LOOP_LAG, "s"),
    ("trace_drops", "flight-recorder span-drop rate", sig_trace_drops,
     "above", "trace_drop_rate", _TRACE_DROPS, "/s"),
    ("conservation", "exactly-once conservation breaches (audit ledger)",
     sig_conservation, "above", "conservation_breaches", _AUDIT_BREACHES,
     "count"),
    ("replica_staleness", "follower epochs behind publication",
     sig_replica_staleness, "above", "replica_lag_epochs", _REPLICA_LAG,
     "epochs"),
)


def _load_overrides(raw: str) -> dict:
    """watch.overrides: inline JSON or a JSON file path; {} on empty.
    Raises on malformed input at evaluation setup (config error, not a
    silent no-op)."""
    raw = (raw or "").strip()
    if not raw:
        return {}
    if not raw.startswith("{"):
        with open(raw) as f:
            raw = f.read()
    obj = json.loads(raw)
    if not isinstance(obj, dict):
        raise ValueError("watch.overrides must be a JSON object")
    return obj


def build_rules(tenant: str = "", job_id: str = "") -> List[RuleSpec]:
    """Resolve the built-in rules against watch.* plus any per-tenant /
    per-job overrides (`job:<id>` wins over `tenant:<t>` wins over the
    section defaults). A rule overridden with {"disabled": true} is
    omitted."""
    cfg = config().watch
    overrides = _load_overrides(cfg.overrides)
    layered: Dict[str, dict] = {}
    for scope in (f"tenant:{tenant}", f"job:{job_id}"):
        for rule, ov in (overrides.get(scope) or {}).items():
            layered.setdefault(rule, {}).update(ov or {})
    out: List[RuleSpec] = []
    for name, desc, signal, kind, attr, cause, unit in BUILTIN_RULES:
        ov = layered.get(name, {})
        if ov.get("disabled"):
            continue
        threshold = float(ov.get("threshold", getattr(cfg, attr)))
        ratio = float(cfg.clear_ratio)
        default_clear = (threshold * ratio if kind == "above"
                         else threshold / max(ratio, 1e-9))
        out.append(RuleSpec(
            name=name, description=desc, signal=signal, kind=kind,
            threshold=threshold,
            clear=float(ov.get("clear", default_clear)),
            sustain=float(ov.get("sustain", cfg.sustain)),
            clear_sustain=float(ov.get("clear_sustain",
                                       cfg.clear_sustain)),
            cause_family=cause, unit=unit,
        ))
    return out


class AlertState:
    """Hysteresis state for one (job, rule): ok -> pending (breached,
    sustaining) -> firing -> clearing (below clear threshold,
    sustaining) -> ok."""

    __slots__ = ("state", "since", "value", "fired_at", "generation")

    def __init__(self):
        self.state = "ok"
        self.since = 0.0
        self.value: Optional[float] = None
        self.fired_at: Optional[float] = None
        self.generation = 0  # firing episodes seen

    def summary(self) -> dict:
        return {
            "state": self.state,
            "value": self.value,
            "since": round(self.since, 3),
            "fired_at": self.fired_at,
            "episodes": self.generation,
        }


def _safe_name(s: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", str(s))[:80]


class Watchtower:
    """Controller-resident SLO evaluator + alert ledger + bundle spool.

    Also usable standalone (controller=None) over synthetic history in
    tests — evaluation then takes explicit (job_id, tenant) pairs."""

    def __init__(self, controller=None,
                 history: Optional[MetricHistory] = None):
        self.controller = controller
        self.history = history or HISTORY
        self.ledger: deque = deque(maxlen=int(config().watch.ledger_events))
        self.alerts: Dict[Tuple[str, str], AlertState] = {}
        self.bundle_index: List[dict] = []
        self._bundle_seq = 0
        self._spool_dir: Optional[str] = None
        self._task: Optional[asyncio.Task] = None
        self._last_remote: Tuple[float, Optional[dict]] = (0.0, None)
        self.false_positive_jobs: set = set()  # set by harness asserts

    # -- lifecycle -----------------------------------------------------------

    def maybe_start(self) -> bool:
        if not config().watch.enabled or self._task is not None:
            return False
        self._task = asyncio.ensure_future(self._loop())
        logger.info(
            "watchtower on: eval=%.1fs window=%.0fs rules=%s",
            config().watch.eval_interval, config().watch.window,
            [r[0] for r in BUILTIN_RULES],
        )
        return True

    async def stop(self):
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None

    async def _loop(self):
        while True:
            await asyncio.sleep(float(config().watch.eval_interval))
            try:
                await self.tick()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - the watch must survive
                logger.exception("watchtower tick failed")

    # -- the scrape pump (controller side) -----------------------------------

    def _set_job_gauges(self) -> None:
        """Controller-state gauges the SLO engine watches (published
        epoch per durable job) — set before the sample so the history
        sees them at this tick's timestamp."""
        from ..metrics import JOB_PUBLISHED_EPOCH

        if self.controller is None:
            return
        for job in self.controller.jobs.values():
            if job.backend is None or job.state.is_terminal():
                continue
            JOB_PUBLISHED_EPOCH.labels(job=job.job_id).set(
                float(job.published_epoch)
            )

    async def _scrape_remote(self, now: float) -> None:
        """Multi-process deployments: merge the pool workers' GetMetrics
        snapshots into the controller's history (embedded workers share
        this process's registry — the local sample already covers them,
        so the rpc round is skipped)."""
        ctrl = self.controller
        if ctrl is None:
            return
        try:
            from ..controller.scheduler import EmbeddedScheduler

            if isinstance(ctrl.scheduler, EmbeddedScheduler):
                return
        except Exception:  # noqa: BLE001 - scheduler import is advisory
            pass
        interval = float(config().watch.sample_interval)
        if now - self._last_remote[0] < interval:
            return
        from ..autoscale.signals import merge_snapshots

        seen: Dict[int, object] = {}
        for job in ctrl.jobs.values():
            if job.state.is_terminal():
                continue
            for w in job.workers:
                seen.setdefault(w.worker_id, w)
        snaps = []
        for w in seen.values():
            try:
                resp = await asyncio.wait_for(
                    w.client.call("WorkerGrpc", "GetMetrics", {}), 2.0
                )
                snaps.append(resp.get("snapshot") or {})
            except Exception as e:  # noqa: BLE001 - dead/slow worker
                logger.debug("watch scrape from worker %s failed: %s",
                             getattr(w, "worker_id", "?"), e)
        merged = merge_snapshots(snaps) if snaps else None
        self._last_remote = (now, merged)
        if merged:
            self.history.ingest(merged, now=now)

    def fresh_remote_snapshot(self, max_age: float) -> Optional[dict]:
        """The last remote merged snapshot if younger than `max_age` —
        lets the autoscaler reuse the watchtower's scrape instead of a
        second GetMetrics round per control period."""
        t, snap = self._last_remote
        if snap is not None and time.monotonic() - t <= max_age:
            return snap
        return None

    async def tick(self, now: Optional[float] = None) -> None:
        if not config().watch.enabled:
            return
        now = time.monotonic() if now is None else now
        self._set_job_gauges()
        self.history.sample_registry(now=now)
        await self._scrape_remote(now)
        self.evaluate(now)

    # -- evaluation ----------------------------------------------------------

    def _jobs(self) -> List[tuple]:
        """(job_id, tenant, JobHandle) for every non-terminal job."""
        if self.controller is None:
            return []
        return [
            (j.job_id, j.tenant, j)
            for j in list(self.controller.jobs.values())
            if not j.state.is_terminal()
        ]

    def evaluate(self, now: Optional[float] = None,
                 jobs: Optional[List[tuple]] = None) -> None:
        now = time.monotonic() if now is None else now
        window = float(config().watch.window)
        for job_id, tenant, job in (jobs if jobs is not None
                                    else self._jobs()):
            ctx = SLOContext(job_id=job_id, tenant=tenant,
                             history=self.history, window=window,
                             now=now, job=job)
            try:
                specs = build_rules(tenant=tenant, job_id=job_id)
            except (ValueError, OSError, json.JSONDecodeError) as e:
                logger.warning("watch.overrides invalid: %s", e)
                specs = []
            for spec in specs:
                try:
                    value = spec.signal(ctx)
                except Exception:  # noqa: BLE001 - one signal must not
                    logger.exception("watch signal %s failed", spec.name)
                    continue
                self._step(job_id, tenant, job, spec, value, now)

    # rules a hot-standby promotion legitimately blips (ISSUE 17): the
    # promoted incarnation's watermarks and latency markers start from
    # its tailed state and catch up within the failover.grace window —
    # paging on that would page on every successful sub-second failover.
    # replica_staleness joins them (ISSUE 20): the promoted generation
    # publishes under a fresh manifest lineage the follower re-tails,
    # so its lag legitimately spikes for the same bounded window.
    _FAILOVER_GRACE_RULES = ("freshness", "e2e_p99", "replica_staleness")

    def _in_failover_grace(self, job_id: str) -> bool:
        fo = getattr(self.controller, "failover", None)
        return fo is not None and fo.in_grace(job_id)

    def _step(self, job_id: str, tenant: str, job, spec: RuleSpec,
              value: Optional[float], now: float) -> None:
        st = self.alerts.setdefault((job_id, spec.name), AlertState())
        st.value = value
        if (spec.name in self._FAILOVER_GRACE_RULES
                and self._in_failover_grace(job_id)):
            # suppress NEW pages only: a pre-existing firing alert keeps
            # firing (the promotion did not fix it), but breach time
            # must not accrue against the catch-up blip
            if st.state == "pending":
                st.state = "ok"
            if st.state == "ok":
                return
        breached = value is not None and spec.breached(value)
        cleared = value is not None and spec.cleared(value)
        if st.state == "ok":
            if breached:
                st.state, st.since = "pending", now
        elif st.state == "pending":
            if not breached:
                st.state = "ok"
            elif now - st.since >= spec.sustain:
                self._fire(job_id, tenant, job, spec, st, value, now)
        elif st.state == "firing":
            if cleared:
                st.state, st.since = "clearing", now
        elif st.state == "clearing":
            if value is None:
                # no evidence either way: hold, but do not accrue clear
                # time on silence — clearing needs positive data
                st.since = now
            elif breached:
                st.state = "firing"
            elif cleared and now - st.since >= spec.clear_sustain:
                self._clear(job_id, tenant, spec, st, value, now)

    def _cause_series(self, job_id: str, spec: RuleSpec) -> List[dict]:
        window = float(config().watch.window)
        return self.history.export_job(job_id, window=window,
                                       series=spec.cause_family)

    def _ledger_event(self, event: str, job_id: str, tenant: str,
                      spec: RuleSpec, value, now: float,
                      **extra) -> dict:
        from ..metrics import WATCH_ALERTS

        ev = {
            "ts": time.time(),
            "event": event,
            "job": job_id,
            "tenant": tenant,
            "rule": spec.name,
            "value": value,
            "threshold": spec.threshold,
            "unit": spec.unit,
            "cause": self._cause_series(job_id, spec),
            **extra,
        }
        self.ledger.append(ev)
        WATCH_ALERTS.labels(job=job_id, rule=spec.name, event=event).inc()
        return ev

    def _fire(self, job_id: str, tenant: str, job, spec: RuleSpec,
              st: AlertState, value: float, now: float) -> None:
        st.state = "firing"
        st.fired_at = time.time()
        st.generation += 1
        ev = self._ledger_event(
            "firing", job_id, tenant, spec, value, now,
            sustained_s=round(now - st.since, 3), episode=st.generation,
        )
        logger.warning(
            "SLO breach: job=%s rule=%s value=%s threshold=%s (%s)",
            job_id, spec.name, value, spec.threshold, spec.unit,
        )
        try:
            # the flight recorder and the phase ring serialised on the loop
            # the controller shares with an embedded worker: seconds with
            # full rings, so a leaf of the ledger (`n` = spans written)
            with timeline.phase("watch.bundle", job=job_id,
                                key=spec.name) as ph:
                ph.n = self._capture_bundle(job_id, tenant, spec, ev)["spans"]
        except Exception:  # noqa: BLE001 - a failed bundle must not
            logger.exception("bundle capture for %s/%s failed",
                             job_id, spec.name)

    def _clear(self, job_id: str, tenant: str, spec: RuleSpec,
               st: AlertState, value: float, now: float) -> None:
        st.state = "ok"
        fired_for = (time.time() - st.fired_at) if st.fired_at else None
        self._ledger_event(
            "cleared", job_id, tenant, spec, value, now,
            fired_for_s=round(fired_for, 3) if fired_for else None,
        )
        logger.info("SLO cleared: job=%s rule=%s value=%s", job_id,
                    spec.name, value)

    # -- diagnostic bundles --------------------------------------------------

    def spool_dir(self) -> str:
        if self._spool_dir is None:
            cfg_dir = str(config().watch.spool_dir or "").strip()
            if cfg_dir:
                self._spool_dir = cfg_dir
            else:
                import tempfile

                self._spool_dir = tempfile.mkdtemp(
                    prefix="arroyo-watch-bundles-")
            os.makedirs(self._spool_dir, exist_ok=True)
        return self._spool_dir

    def _capture_bundle(self, job_id: str, tenant: str, spec: RuleSpec,
                        alert_event: dict) -> dict:
        """The breach-triggered diagnostic bundle: everything a 3am
        responder needs, captured while the evidence is still in the
        rings."""
        from . import doctor
        from . import perfetto_trace, recorder

        n = self._bundle_seq
        self._bundle_seq += 1
        spans = recorder().snapshot(trace_prefix=f"{job_id}/")
        try:
            verdict = doctor.report(job_id)
        except Exception as e:  # noqa: BLE001 - diagnosis is best effort
            verdict = {"error": repr(e)}
        bundle = {
            "n": n,
            "job": job_id,
            "tenant": tenant,
            "rule": spec.name,
            "captured_at": time.time(),
            "alert": {k: v for k, v in alert_event.items() if k != "cause"},
            "cause": alert_event.get("cause"),
            "doctor": verdict,
            "flight_recorder": spans,
            "perfetto": perfetto_trace(spans, job=job_id),
            "history": self.history.export_job(
                job_id, window=float(config().watch.bundle_window_s),
            ),
            "ledger": [e for e in self.ledger if e.get("job") == job_id
                       and e.get("event") != "firing"]
            + [{k: v for k, v in alert_event.items() if k != "cause"}],
        }
        path = os.path.join(
            self.spool_dir(),
            f"bundle-{n:05d}-{_safe_name(job_id)}-{spec.name}.json",
        )
        with open(path, "w") as f:
            json.dump(bundle, f, default=str)
        meta = {
            "n": n, "job": job_id, "tenant": tenant, "rule": spec.name,
            "captured_at": bundle["captured_at"], "path": path,
            "bytes": os.path.getsize(path),
            "spans": len(spans),
        }
        self.bundle_index.append(meta)
        cap = int(config().watch.spool_bundles)
        while len(self.bundle_index) > cap:
            old = self.bundle_index.pop(0)
            try:
                os.unlink(old["path"])
            except OSError:
                pass
        return meta

    def bundles_for(self, job_id: Optional[str] = None) -> List[dict]:
        return [m for m in self.bundle_index
                if job_id is None or m["job"] == job_id]

    def bundle(self, n: int) -> Optional[dict]:
        for m in self.bundle_index:
            if m["n"] == n:
                try:
                    with open(m["path"]) as f:
                        return json.load(f)
                except (OSError, json.JSONDecodeError) as e:
                    return {"error": f"bundle unreadable: {e}", "meta": m}
        return None

    # -- surfaces ------------------------------------------------------------

    def alerts_for(self, job_id: str) -> dict:
        """The REST alerts payload: current rule states + the job's
        slice of the ledger."""
        return {
            "job": job_id,
            "alerts": {
                rule: st.summary()
                for (jid, rule), st in sorted(self.alerts.items())
                if jid == job_id
            },
            "firing": sorted(
                rule for (jid, rule), st in self.alerts.items()
                if jid == job_id and st.state == "firing"
            ),
            "ledger": [e for e in self.ledger if e["job"] == job_id],
        }

    def status(self, job_id: Optional[str] = None) -> dict:
        cfg = config().watch
        doc = {
            "enabled": bool(cfg.enabled and self._task is not None),
            "eval_interval": float(cfg.eval_interval),
            "window": float(cfg.window),
            "history": self.history.stats(),
            "rules": [
                {"name": r[0], "description": r[1], "kind": r[3],
                 "threshold": getattr(cfg, r[4]), "unit": r[6]}
                for r in BUILTIN_RULES
            ],
            "alerts": [
                {"job": jid, "rule": rule, **st.summary()}
                for (jid, rule), st in sorted(self.alerts.items())
                if st.state != "ok" and (job_id is None or jid == job_id)
            ],
            "firing": sum(1 for st in self.alerts.values()
                          if st.state == "firing"),
            "ledger": [
                {k: v for k, v in e.items() if k != "cause"}
                for e in self.ledger
                if job_id is None or e["job"] == job_id
            ][-64:],
            "bundles": self.bundles_for(job_id),
        }
        return doc

    def expunge_job(self, job_id: str) -> None:
        """Job-scoped GC beside Registry.drop_job: alert state machines
        of a released job are dropped (ledger events and captured
        bundles are diagnostics of the past and stay until their own
        bounds evict them)."""
        for key in [k for k in self.alerts if k[0] == job_id]:
            del self.alerts[key]

    def reset(self) -> None:
        self.alerts.clear()
        self.ledger.clear()
        self.bundle_index.clear()
        self._bundle_seq = 0
