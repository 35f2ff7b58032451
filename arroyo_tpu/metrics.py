"""Metrics registry with Prometheus text exposition.

Capability parity with the reference's `arroyo-metrics` crate +
TaskCounters (/root/reference/crates/arroyo-operator/src/context.rs):
per-task messages/batches/bytes rx-tx counters, per-queue occupancy gauges,
and UI-facing 5-minute rate windows (computed in engine.job_metrics).

The flight-recorder layer (arroyo_tpu/obs) adds a histogram kind
(`Registry.histogram` → `.labels(...).observe(v)`) with standard
`_bucket`/`_sum`/`_count` exposition, feeding per-subtask batch-processing
latency, data-plane exchange latency, storage op latency and checkpoint
phase durations, plus watermark-lag and barrier-alignment gauges.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict, deque
from typing import Dict, Optional, Tuple

LabelSet = Tuple[Tuple[str, str], ...]

# latency buckets (seconds): 1ms .. 10s, roughly log-spaced — covers the
# data plane (sub-ms frames) through checkpoint flushes (seconds)
DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0,
)


def _escape_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _Hist:
    """Per-labelset histogram state: bucket counts + running sum/count."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float, buckets: Tuple[float, ...]):
        i = bisect.bisect_left(buckets, value)
        if i < len(self.counts):
            self.counts[i] += 1
        self.sum += value
        self.count += 1

    def cumulative(self) -> list:
        out = []
        cum = 0
        for c in self.counts:
            cum += c
            out.append(cum)
        return out


class _Metric:
    def __init__(self, name: str, help_: str, kind: str,
                 buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.kind = kind
        self.buckets = tuple(buckets)
        self.values: Dict[LabelSet, float] = defaultdict(float)
        self.hists: Dict[LabelSet, _Hist] = {}
        # scrape-time refreshers: key -> zero-arg callable returning the
        # current value (or None to keep the stored sample). Gauges whose
        # producer only updates on its own hot path (e.g. backpressure,
        # sampled every N collect() calls) register one so a quiesced
        # stream can't pin a stale value into every future scrape.
        self.refreshers: Dict[LabelSet, object] = {}
        self.lock = threading.Lock()

    def labels(self, **labels: str) -> "_Handle":
        key = tuple(sorted(labels.items()))
        return _Handle(self, key)

    def observe(self, key: LabelSet, value: float):
        with self.lock:
            h = self.hists.get(key)
            if h is None:
                h = self.hists[key] = _Hist(len(self.buckets))
            h.observe(value, self.buckets)

    def _refresh(self):
        """Run registered refreshers (lock held), dropping dead ones."""
        if not self.refreshers:
            return
        dead = []
        for key, fn in self.refreshers.items():
            try:
                v = fn()
            except Exception:  # noqa: BLE001 - producer gone mid-scrape
                v = None
            if v is None:
                dead.append(key)
            else:
                self.values[key] = v
        for key in dead:
            del self.refreshers[key]

    @staticmethod
    def _label_str(key: LabelSet, extra: str = "") -> str:
        parts = [f'{k}="{_escape_label(v)}"' for k, v in key]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def expose(self) -> str:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        with self.lock:
            if self.kind == "histogram":
                for key, h in self.hists.items():
                    cum = h.cumulative()
                    for le, c in zip(self.buckets, cum):
                        le_label = f'le="{le}"'
                        lines.append(
                            f"{self.name}_bucket"
                            f"{self._label_str(key, le_label)} {c}"
                        )
                    inf_label = 'le="+Inf"'
                    lines.append(
                        f"{self.name}_bucket"
                        f"{self._label_str(key, inf_label)} {h.count}"
                    )
                    lines.append(f"{self.name}_sum{self._label_str(key)} {h.sum}")
                    lines.append(f"{self.name}_count{self._label_str(key)} {h.count}")
                return "\n".join(lines)
            self._refresh()
            for key, val in self.values.items():
                if key:
                    label_s = ",".join(
                        f'{k}="{_escape_label(v)}"' for k, v in key)
                    lines.append(f"{self.name}{{{label_s}}} {val}")
                else:
                    lines.append(f"{self.name} {val}")
        return "\n".join(lines)


class _Handle:
    __slots__ = ("metric", "key")

    def __init__(self, metric: _Metric, key: LabelSet):
        self.metric = metric
        self.key = key

    def inc(self, amount: float = 1.0):
        with self.metric.lock:
            self.metric.values[self.key] += amount

    def set(self, value: float):
        with self.metric.lock:
            self.metric.values[self.key] = value

    def observe(self, value: float):
        """Histogram observation (seconds for the latency families)."""
        self.metric.observe(self.key, value)

    def set_refresher(self, fn):
        """Register a scrape-time refresher: `fn()` is called under the
        metric lock at expose/snapshot and must return the current value,
        or None to unregister itself (producer gone)."""
        with self.metric.lock:
            self.metric.refreshers[self.key] = fn

    def get(self) -> float:
        with self.metric.lock:
            return self.metric.values[self.key]

    def get_hist(self) -> Optional[dict]:
        """Structured view of this labelset's histogram state."""
        with self.metric.lock:
            h = self.metric.hists.get(self.key)
            if h is None:
                return None
            return _hist_dict(h, self.metric.buckets)


def _hist_dict(h: _Hist, buckets: Tuple[float, ...]) -> dict:
    out = {str(le): c for le, c in zip(buckets, h.cumulative())}
    out["+Inf"] = h.count
    return {"sum": h.sum, "count": h.count, "buckets": out}


def hist_quantiles(snapshot: Optional[dict],
                   qs: Tuple[float, ...] = (0.5, 0.95, 0.99)) -> dict:
    """Estimate quantiles from a histogram snapshot's cumulative buckets
    (the {"sum", "count", "buckets": {le: cumulative}} shape _hist_dict /
    Registry.snapshot produce) by linear interpolation within the bucket
    that crosses the target rank — the same estimator Prometheus's
    histogram_quantile() applies server-side. The +Inf bucket has no upper
    edge, so ranks landing there report the highest finite edge (a floor,
    like Prometheus). Returns {"p50": v, ...}; empty dict for a missing or
    empty snapshot."""
    if not snapshot or not snapshot.get("count"):
        return {}
    edges = sorted(
        (float(le), c) for le, c in snapshot["buckets"].items()
        if le != "+Inf"
    )
    total = snapshot["count"]
    out = {}
    for q in qs:
        rank = q * total
        val = edges[-1][0] if edges else 0.0
        prev_edge, prev_cum = 0.0, 0
        for edge, cum in edges:
            if cum >= rank:
                if cum > prev_cum:
                    frac = (rank - prev_cum) / (cum - prev_cum)
                    val = prev_edge + (edge - prev_edge) * frac
                else:
                    val = edge
                break
            prev_edge, prev_cum = edge, cum
        out[f"p{int(q * 100)}"] = val
    return out


class Registry:
    def __init__(self):
        self.metrics: Dict[str, _Metric] = {}
        self.lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> _Metric:
        return self._get(name, help_, "counter")

    def gauge(self, name: str, help_: str = "") -> _Metric:
        return self._get(name, help_, "gauge")

    def histogram(self, name: str, help_: str = "",
                  buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> _Metric:
        return self._get(name, help_, "histogram", buckets)

    def _get(self, name: str, help_: str, kind: str,
             buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> _Metric:
        with self.lock:
            if name not in self.metrics:
                self.metrics[name] = _Metric(name, help_, kind, buckets)
            return self.metrics[name]

    def expose(self) -> str:
        with self.lock:
            metrics = list(self.metrics.values())
        return "\n".join(m.expose() for m in metrics) + "\n"

    def snapshot(self) -> Dict[str, list]:
        """{metric name: [(labels dict, value)]} for structured consumers
        (the API's operator metric groups). Histogram entries carry a
        {"sum", "count", "buckets": {le: cumulative}} dict as the value."""
        with self.lock:
            metrics = list(self.metrics.items())
        out: Dict[str, list] = {}
        for name, m in metrics:
            with m.lock:
                if m.kind == "histogram":
                    out[name] = [
                        (dict(k), _hist_dict(h, m.buckets))
                        for k, h in m.hists.items()
                    ]
                    continue
                m._refresh()
                out[name] = [(dict(k), v) for k, v in m.values.items()]
        return out

    def reset(self):
        """Clear every metric's samples IN PLACE. The _Metric objects stay
        registered: module-level families (MESSAGES_RECV etc.) hand out
        handles bound to those objects, and dropping them from the registry
        would orphan the handles — increments would land in objects no
        longer visible to expose()/snapshot() and silently vanish."""
        with self.lock:
            for m in self.metrics.values():
                with m.lock:
                    m.values.clear()
                    m.hists.clear()
                    m.refreshers.clear()

    def drop_job(self, job_id: str) -> int:
        """Cardinality GC: remove every label set carrying job=job_id
        (values, histograms, refreshers) across all families. Without
        this, a 1000-job churn run grows /metrics exposition unboundedly
        — per-subtask counters, queue gauges and latency histograms of
        stopped jobs would be scraped forever. Handles held by a live
        producer of the dropped job recreate a zeroed entry on their next
        write, which is the counter-restart shape every consumer already
        tolerates. Returns the number of label sets removed."""
        match = ("job", job_id)
        dropped = 0
        with self.lock:
            metrics = list(self.metrics.values())
        for m in metrics:
            with m.lock:
                for store in (m.values, m.hists, m.refreshers):
                    stale = [k for k in store if match in k]
                    for k in stale:
                        del store[k]
                    dropped += len(stale)
        return dropped


REGISTRY = Registry()

# Task-level counters, one label-set per subtask (reference TaskCounters).
MESSAGES_RECV = REGISTRY.counter(
    "arroyo_worker_messages_recv", "messages received by a subtask")
MESSAGES_SENT = REGISTRY.counter(
    "arroyo_worker_messages_sent", "messages sent by a subtask")
BATCHES_RECV = REGISTRY.counter(
    "arroyo_worker_batches_recv", "batches received by a subtask")
BATCHES_SENT = REGISTRY.counter(
    "arroyo_worker_batches_sent", "batches sent by a subtask")
BYTES_RECV = REGISTRY.counter(
    "arroyo_worker_bytes_recv", "bytes received by a subtask")
BYTES_SENT = REGISTRY.counter(
    "arroyo_worker_bytes_sent", "bytes sent by a subtask")
ERRORS = REGISTRY.counter(
    "arroyo_worker_errors", "deserialization/user errors in a subtask")
BACKPRESSURE = REGISTRY.gauge(
    "arroyo_worker_backpressure",
    "fullness (0..1) of a subtask's most-loaded output queue — the "
    "reference derives its backpressure gauge from tx queue occupancy "
    "the same way (job_metrics.rs)")
QUEUE_SIZE = REGISTRY.gauge(
    "arroyo_worker_queue_size", "occupancy of an edge queue (batches)")
QUEUE_BYTES = REGISTRY.gauge(
    "arroyo_worker_queue_bytes", "occupancy of an edge queue (bytes)")
BUSY_SECONDS = REGISTRY.counter(
    "arroyo_worker_busy_seconds",
    "wall seconds a subtask spent doing useful work (processing input "
    "batches, watermark-driven emission, ticks) — excludes time idle on "
    "queue reads or blocked on backpressure. The autoscaler's DS2-style "
    "true-rate estimate is rows / busy-seconds (Kalavri et al., OSDI '18)")

# Flight-recorder latency families (ISSUE 4): histograms in seconds.
BATCH_PROCESSING_SECONDS = REGISTRY.histogram(
    "arroyo_worker_batch_processing_seconds",
    "per-subtask wall time processing one input batch through the "
    "operator chain")
EXCHANGE_FRAME_SECONDS = REGISTRY.histogram(
    "arroyo_exchange_frame_seconds",
    "data-plane frame latency: send-timestamp (frame header) to receive "
    "on the destination worker, per destination subtask")
STORAGE_OP_SECONDS = REGISTRY.histogram(
    "arroyo_storage_op_seconds",
    "object-storage operation latency by op (put/get/cas)")
CHECKPOINT_PHASE_SECONDS = REGISTRY.histogram(
    "arroyo_checkpoint_phase_seconds",
    "checkpoint phase durations per subtask (phase=align|capture|flush)")
# Device-tier observatory (ISSUE 6): end-to-end latency markers +
# XLA compile/dispatch telemetry.
LATENCY_MARKER_SECONDS = REGISTRY.histogram(
    "arroyo_worker_latency_marker_seconds",
    "latency-marker transit time source->this subtask (Flink-style "
    "markers stamped at the sources; per-operator record latency)")
E2E_LATENCY_SECONDS = REGISTRY.histogram(
    "arroyo_worker_e2e_latency_seconds",
    "latency-marker transit time source->sink: the pipeline's "
    "end-to-end record latency, recorded at terminal subtasks")
# XLA compiles run tens of ms (CPU) to seconds (TPU):
# latency-shaped DEFAULT_BUCKETS top out at 10s, so compile histograms
# get their own ladder
COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0, 60.0, 120.0)
XLA_COMPILES = REGISTRY.counter(
    "arroyo_xla_compiles_total",
    "XLA compilations per jitted program (a new shape signature "
    "specializes a fresh executable)")
XLA_COMPILE_CACHE = REGISTRY.counter(
    "arroyo_xla_compile_cache_total",
    "per-program compile-cache outcomes by result=hit|miss (hit = this "
    "process already traced the call's shape signature)")
XLA_COMPILE_SECONDS = REGISTRY.histogram(
    "arroyo_xla_compile_seconds",
    "wall time of calls that triggered an XLA compilation, per program "
    "(includes the compiled executable's first dispatch)",
    buckets=COMPILE_BUCKETS)
DEVICE_DISPATCH_SECONDS = REGISTRY.histogram(
    "arroyo_device_dispatch_seconds",
    "HOST wall time of one call into an already-compiled jitted program "
    "(the enqueue: argument transfer and launch; jax returns before the "
    "device runs it), per program. Device time comes from a profiler "
    "trace only")
DEVICE_EXCHANGE_SECONDS = REGISTRY.histogram(
    "arroyo_device_exchange_seconds",
    "per-dispatch wall time of the mesh EXCHANGE programs only (the "
    "keyed shuffle: device-routed all_to_all route+scatter steps and "
    "the host-fed packed-transfer steps), excluding emission/reset — "
    "the collective cost the mesh tier pays per micro-batch flush")
DEVICE_PADDING_WASTE = REGISTRY.gauge(
    "arroyo_device_padding_waste",
    "fraction (0..1) of rows shipped to the device that were neutral "
    "padding filler, per program and packing rung (shape bucket)")
# fused segment runtime (engine/segments.py): one dispatch per segment
# per batch instead of one per operator — these families are what the
# bench's dispatches_per_batch ratio and the per-segment ledger read
SEGMENT_DISPATCH_SECONDS = REGISTRY.histogram(
    "arroyo_segment_dispatch_seconds",
    "per-batch execution wall time of fused stateless segments, per "
    "segment program")
SEGMENT_FUSED_OPS = REGISTRY.gauge(
    "arroyo_segment_fused_ops",
    "operators fused into each segment program (the dispatches a batch "
    "no longer pays individually)")
SEGMENT_DISPATCHES = REGISTRY.counter(
    "arroyo_segment_dispatches_total",
    "stateless-chain dispatches by job/task and fused=1|0 — fused "
    "segments count one per batch, unfused members of a planned run "
    "count one per operator per batch (the A/B numerator of the bench's "
    "dispatches_per_batch)")
SEGMENT_BATCHES = REGISTRY.counter(
    "arroyo_segment_batches_total",
    "batches entering a planned stateless run (fused or not) by "
    "job/task — the denominator of dispatches_per_batch")
WATERMARK_LAG_SECONDS = REGISTRY.gauge(
    "arroyo_worker_watermark_lag_seconds",
    "wall-clock seconds the subtask's effective watermark trails now "
    "(refreshed at scrape time)")
BARRIER_ALIGNMENT_SECONDS = REGISTRY.gauge(
    "arroyo_worker_barrier_alignment_seconds",
    "seconds the subtask's last checkpoint barrier spent aligning "
    "(first barrier arrival to all live inputs barriered)")
# State-at-scale observability (ROADMAP item 4): per-(table, kind) sizes
# refreshed at scrape time via weakref refreshers registered by each
# subtask's TableManager — the rebase/spill knobs are tuned from these.
STATE_BYTES = REGISTRY.gauge(
    "arroyo_state_bytes",
    "approximate bytes held by a state table per (task, table, kind): "
    "global tables report their last serialized size, time-key tables "
    "in-memory + spilled batch bytes (refreshed at scrape time)")
STATE_ROWS = REGISTRY.gauge(
    "arroyo_state_rows",
    "live entries per state table: KV entries for global tables, "
    "buffered rows for time-key tables (refreshed at scrape time)")
STATE_SPILLED_BYTES = REGISTRY.gauge(
    "arroyo_state_spilled_bytes",
    "bytes a time-key table currently holds in local Arrow-IPC spill "
    "files (cold batches beyond state.memory_budget_bytes)")
STATE_CHAIN_LEN = REGISTRY.gauge(
    "arroyo_state_delta_chain_len",
    "incremental global-table blob-chain length (base + deltas) per "
    "(task, table); the rebase policy (state.rebase_epochs / "
    "state.rebase_bytes_factor) bounds it")
# Fleet observatory (ISSUE 11): per-job cost attribution on multiplexed
# workers. Every family carries a `job` label so Registry.drop_job GCs a
# terminal job's series with the rest; values are rolled up from the
# job-id contextvar accounting (obs/attribution.py) by the per-worker
# pump, so shared-worker usage sums to the worker's measured busy time
# and fair-share grants can be audited against actual consumption.
JOB_ATTR_BUSY_SECONDS = REGISTRY.counter(
    "arroyo_job_attributed_busy_seconds",
    "wall seconds of useful work attributed to a job via the ambient "
    "job-id context (batch processing, watermark-driven emission, "
    "ticks) — sums across co-resident jobs to a multiplexed worker's "
    "arroyo_worker_busy_seconds total")
JOB_ATTR_CPU_SECONDS = REGISTRY.counter(
    "arroyo_job_attributed_cpu_seconds",
    "process CPU seconds apportioned to a job by the accounting pump "
    "(each flush splits the interval's process-CPU delta across jobs "
    "proportional to their attributed busy time in that interval)")
JOB_ATTR_DEVICE_SECONDS = REGISTRY.counter(
    "arroyo_job_attributed_device_seconds",
    "HOST wall seconds of the calls into jitted device programs "
    "(compiles + enqueues, not the programs' run on the device) "
    "attributed to a job — the per-job dimension of the shared-program "
    "XLA telemetry (programs are cached process-wide across jobs, so "
    "the per-program families cannot carry a job label themselves)")
JOB_ATTR_DISPATCHES = REGISTRY.counter(
    "arroyo_job_attributed_dispatches",
    "device program invocations (compile or dispatch) attributed to a "
    "job via the ambient job-id context")
JOB_ATTR_BYTES = REGISTRY.counter(
    "arroyo_job_attributed_bytes",
    "data-plane bytes (batches received by the job's subtasks) "
    "attributed to a job via the ambient job-id context")
# StateServe (ISSUE 12): the queryable-state serving tier. Every family
# carries a `job` label so Registry.drop_job GCs a stopped job's serve
# series with the rest of its metrics; the tenant label on the request
# counter is what per-tenant QPS dashboards and the noisy-neighbor
# wiring read.
SERVE_REQUEST_SECONDS = REGISTRY.histogram(
    "arroyo_serve_request_seconds",
    "gateway wall time serving one state read request (routing + cache "
    "+ worker fan-out + merge), per job")
SERVE_REQUESTS = REGISTRY.counter(
    "arroyo_serve_requests_total",
    "state read requests through the gateway per (job, tenant, outcome="
    "ok|partial|throttled|stale_route|error) — the per-tenant QPS "
    "series read quotas are audited against")
SERVE_KEYS = REGISTRY.counter(
    "arroyo_serve_keys_total",
    "individual key lookups served per job (a bulk read counts each "
    "key; the fleet harness's lookups/s gate reads this)")
SERVE_CACHE_HITS = REGISTRY.counter(
    "arroyo_serve_cache_hits_total",
    "reads answered from the controller-side read-through cache "
    "(entry's published epoch and schedule incarnation both matched)")
SERVE_CACHE_MISSES = REGISTRY.counter(
    "arroyo_serve_cache_misses_total",
    "reads that fanned out to a worker (cold key, epoch-invalidated "
    "entry, or cache disabled)")
SERVE_WORKER_RPCS = REGISTRY.counter(
    "arroyo_serve_worker_rpcs_total",
    "QueryState RPCs the gateway issued to workers per job — the "
    "follower tier's headline win: ~0 for durable jobs once followers "
    "are caught up (the fleet harness asserts it)")
# Follower read replicas (ISSUE 20): controller-hosted serving tier off
# the checkpoint stream. Every family is job-labeled so Registry.
# drop_job GCs a stopped job's replica series with the rest (the fleet
# churn test asserts it); staleness is the replica_staleness SLO input.
REPLICA_TAILS = REGISTRY.counter(
    "arroyo_replica_tails_total",
    "delta-chain suffix tails applied by followers per job (one per "
    "published epoch caught up, per mounted job)")
REPLICA_SERVED_EPOCH = REGISTRY.gauge(
    "arroyo_replica_served_epoch",
    "the epoch a job's follower currently serves at (its last fully "
    "tailed published manifest)")
REPLICA_LAG_EPOCHS = REGISTRY.gauge(
    "arroyo_replica_lag_epochs",
    "published_epoch - follower served epoch per job: 0 when caught "
    "up, transiently 1 while a tail is in flight; > max_lag_epochs "
    "routes reads worker-ward and feeds the replica_staleness SLO")
REPLICA_LOOKUPS = REGISTRY.counter(
    "arroyo_replica_lookups_total",
    "individual key lookups answered from follower views per job (the "
    "fleet harness's serve_follower_lookup_eps reads this)")
REPLICA_SUBSCRIBES = REGISTRY.counter(
    "arroyo_replica_subscribes_total",
    "follower (re)attach restores per job — 1 at mount, +1 per "
    "post-death reattach (each re-resolves latest.json from storage; "
    "see the follower_serves_unpublished_epoch model mutant)")
# Watchtower (ISSUE 13): retained history + per-job SLO engine. The
# alert counter is job-labeled (drop_job GCs it); published-epoch is the
# gauge the checkpoint-age SLO watches for stalls; the trace-drop
# counter makes flight-recorder ring overflow visible without catching
# /debug/trace at the right moment.
TRACE_DROPPED_SPANS = REGISTRY.counter(
    "arroyo_trace_dropped_spans_total",
    "flight-recorder spans dropped because the per-process ring buffer "
    "(obs.trace_buffer_spans) was full — sustained drops mean the "
    "recording of the next incident is incomplete; the watchtower's "
    "trace_drops rule alerts on the windowed drop rate")
JOB_PUBLISHED_EPOCH = REGISTRY.gauge(
    "arroyo_job_published_epoch",
    "the job's last PUBLISHED checkpoint epoch (set by the controller "
    "watchtower each sample) — the checkpoint-age SLO fires when this "
    "stops advancing on a durable job")
WATCH_ALERTS = REGISTRY.counter(
    "arroyo_watch_alerts_total",
    "watchtower alert transitions per (job, rule, event=firing|cleared)")
# Conservation ledger (ISSUE 19): per-edge epoch attestation auditing.
# Every family carries a `job` label so Registry.drop_job GCs a terminal
# job's audit series with the rest; the breach counter additionally
# carries the breach kind (digest_mismatch|count_mismatch|flow_violation|
# rewind_behind_commit|zombie_generation) and is what the watchtower's
# `conservation` SLO rule and the retained-history allowlist read.
AUDIT_EPOCHS = REGISTRY.counter(
    "arroyo_audit_epochs_reconciled_total",
    "checkpoint epochs whose per-edge attestations the controller "
    "reconciler joined at manifest publish, per job")
AUDIT_EDGES_VERIFIED = REGISTRY.counter(
    "arroyo_audit_edges_verified_total",
    "per-epoch edge attestations that matched on both sides (sender "
    "row count + commutative digest == receiver's), per job")
AUDIT_BREACHES = REGISTRY.counter(
    "arroyo_audit_breaches_total",
    "conservation breaches flagged by the reconciler per (job, kind): "
    "attestation joins that diverged, flow-consistency violations, and "
    "recovery-conservation breaches (rewind-behind-commit / "
    "zombie-generation append) — each names its exact (edge, epoch)")
LOOP_LAG_SECONDS = REGISTRY.histogram(
    "arroyo_worker_loop_lag_seconds",
    "event-loop scheduling lag sampled by the accounting pump (sleep-"
    "overshoot of a loop_lag_interval timer): how long a ready task "
    "waits for the multiplexed worker loop — the noisy-neighbor signal")


class RateWindow:
    """Fixed 5-minute window of (t, value) samples for UI rates
    (reference: job_metrics.rs:188-265). Backed by a deque — the old
    list + pop(0) trim was O(n) per add on long-running jobs — and
    hard-capped at MAX_SAMPLES so a hot producer can't grow it without
    bound inside the time window."""

    WINDOW = 300.0
    MAX_SAMPLES = 4096

    def __init__(self):
        self.samples: deque[tuple[float, float]] = deque()

    def add(self, value: float, now: float | None = None):
        now = time.monotonic() if now is None else now
        self.samples.append((now, value))
        cutoff = now - self.WINDOW
        while self.samples and self.samples[0][0] < cutoff:
            self.samples.popleft()
        while len(self.samples) > self.MAX_SAMPLES:
            self.samples.popleft()

    def rate(self) -> float:
        if len(self.samples) < 2:
            return 0.0
        (t0, v0), (t1, v1) = self.samples[0], self.samples[-1]
        return (v1 - v0) / (t1 - t0) if t1 > t0 else 0.0
