"""Bottleneck doctor (ISSUE 11): per-job ranked limiting-factor verdicts.

Combines the fleet observatory's signals — per-job busy ratio,
backpressure, queue depth, watermark lag, the device dispatch floor,
padding waste, event-loop lag, and per-job attributed cost shares —
into one ranked verdict naming the limiting operator and the suspected
cause:

  host-bound       the job is busy and nearly all of it is host python/
                   arrow work (ROADMAP item 1's decode/pack overlap is
                   the fix);
  device-bound     the job is busy and its time sits inside jitted
                   device programs (dispatch floor / padding waste are
                   the levers);
  exchange-bound   the keyed shuffle (data-plane frames or the mesh
                   collective) dominates the phase ledger;
  starved          the job is idle with empty queues, no backpressure
                   and an uncontended loop: upstream has nothing for it;
  noisy-neighbor   the job is idle *because the shared worker is not*:
                   a co-resident tenant holds the loop (high loop lag +
                   a dominant attributed-busy share) — named explicitly
                   so operators know who to throttle.

The same `diagnose()` runs online (`GET /api/v1/jobs/{id}/doctor`,
`/debug/doctor?job=`) against the live registry, and offline
(`tools/trace_report.py --doctor`) against signals reconstructed from a
Perfetto trace dump.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import timeline

# signal thresholds: a busy ratio above BUSY_HIGH reads as "the job is
# the bottleneck of itself"; loop lag above LAG_FLOOR_MS reads as loop
# contention; a co-resident tenant above NEIGHBOR_SHARE of attributed
# busy is a nameable neighbor
BUSY_HIGH = 0.5
LAG_FLOOR_MS = 20.0
NEIGHBOR_SHARE = 0.5
# steady-state dispatch wall above this reads as "paying the dispatch
# floor" (the round-11 ledger put the per-op floor at ~2ms)
DISPATCH_FLOOR_MS = 1.5


def _windowed_overlay(sig: dict, job_id: str, ops: Dict[str, dict]) -> None:
    """Re-point the busy/neighbor signals at the retained metric
    history (ISSUE 13): cumulative attributed totals describe a job's
    LIFETIME average, but the doctor is asked about NOW — overlay
    windowed deltas (`watch.window` lookback, the same
    `history.Series.delta` rate path the SLO engine and autoscaler
    read) wherever the history has coverage, keeping the cumulative
    values as the no-history fallback."""
    from ..config import config
    from .history import HISTORY

    win = float(config().watch.window)
    busy_series = HISTORY.get("arroyo_job_attributed_busy_seconds")
    deltas: Dict[str, float] = {}
    covered = 0.0
    for s in busy_series:
        d = s.delta(win)
        if d is None:
            continue
        pts = s.window(win)
        covered = max(covered, pts[-1][0] - pts[0][0])
        job = s.label("job")
        deltas[job] = deltas.get(job, 0.0) + d
    if not deltas or covered <= 0:
        return
    sig["windowed"] = True
    sig["window_s"] = round(min(win, covered), 3)
    busy_s = deltas.get(job_id, 0.0)
    sig["busy_s"] = round(busy_s, 4)
    sig["busy_ratio"] = round(
        min(1.0, busy_s / sig["window_s"]), 4
    ) if sig["window_s"] > 0 else 0.0
    neighbors = [
        {"job": j, "busy_s": round(d, 4)}
        for j, d in deltas.items() if j not in (job_id, "") and d > 0
    ]
    neighbors.sort(key=lambda n: -n["busy_s"])
    others = sum(n["busy_s"] for n in neighbors)
    sig["neighbors"] = neighbors[:8]
    sig["neighbor_top_share"] = round(
        neighbors[0]["busy_s"] / (busy_s + others), 4
    ) if neighbors and (busy_s + others) > 0 else 0.0
    dev = 0.0
    for s in HISTORY.get("arroyo_job_attributed_device_seconds",
                         job=job_id):
        d = s.delta(win)
        if d is not None:
            dev += d
    if dev:
        sig["device_s"] = round(dev, 4)
    # per-task busy: windowed where the series has coverage
    for s in HISTORY.get("arroyo_worker_busy_seconds", job=job_id):
        d = s.delta(win)
        task = s.label("task")
        if d is not None and task in ops:
            ops[task]["busy_s"] = round(d, 4)
    sig["operators"] = sorted(ops.values(),
                              key=lambda o: -o.get("busy_s", 0.0))


def collect(job_id: str, registry=None) -> dict:
    """Gather one job's doctor signals from this process's registry,
    the attribution accounting, the timeline ledger — and, where the
    watchtower history tier has coverage, WINDOWED rates instead of
    lifetime cumulatives (see _windowed_overlay)."""
    from ..metrics import REGISTRY, hist_quantiles
    from . import attribution

    registry = registry or REGISTRY
    attribution.ACCOUNTING.flush()
    snap = registry.snapshot()

    def per_task(name: str, field: str, ops: Dict[str, dict],
                 hist_q: Optional[str] = None):
        for labels, value in snap.get(name, []):
            if labels.get("job") != job_id or "task" not in labels:
                continue
            ent = ops.setdefault(labels["task"], {"task": labels["task"]})
            if isinstance(value, dict):
                q = hist_quantiles(value)
                ent[field] = round(1e3 * q.get(hist_q or "p95", 0.0), 3)
            else:
                ent[field] = round(float(value), 4)

    ops: Dict[str, dict] = {}
    per_task("arroyo_worker_busy_seconds", "busy_s", ops)
    per_task("arroyo_worker_backpressure", "backpressure", ops)
    per_task("arroyo_worker_watermark_lag_seconds", "watermark_lag_s", ops)
    per_task("arroyo_worker_batch_processing_seconds", "batch_p95_ms", ops)
    queue_depth = 0.0
    for labels, value in snap.get("arroyo_worker_queue_size", []):
        if labels.get("job") == job_id:
            queue_depth = max(queue_depth, float(value))

    summary = attribution.ACCOUNTING.summary()
    mine = summary["jobs"].get(job_id, {})
    window = mine.get("window_s") or 0.0
    busy_s = mine.get("busy", 0.0)
    neighbors = [
        {"job": j, "busy_s": e.get("busy", 0.0)}
        for j, e in summary["jobs"].items()
        if j not in (job_id, "(unattributed)") and e.get("busy", 0.0) > 0
    ]
    neighbors.sort(key=lambda n: -n["busy_s"])
    others = sum(n["busy_s"] for n in neighbors)

    dispatch_p50 = 0.0
    dispatches = 0
    for _labels, h in snap.get("arroyo_device_dispatch_seconds", []):
        dispatches += int(h.get("count", 0))
        dispatch_p50 = max(
            dispatch_p50, hist_quantiles(h).get("p50", 0.0)
        )
    padding = max(
        (float(v) for _l, v in snap.get("arroyo_device_padding_waste", [])),
        default=0.0,
    )

    # self seconds: a phase's duration less the phases booked inside it,
    # so the shares below add up however finely a batch is split
    phases = {
        p: t["self_s"]
        for p, t in timeline.phase_totals(job_id).items()
    }
    sig = {
        "job": job_id,
        "window_s": round(window, 3),
        "busy_s": round(busy_s, 4),
        "busy_ratio": round(busy_s / window, 4) if window > 0 else 0.0,
        "device_s": round(mine.get("device", 0.0), 4),
        "operators": sorted(ops.values(),
                            key=lambda o: -o.get("busy_s", 0.0)),
        "backpressure": max(
            (o.get("backpressure", 0.0) for o in ops.values()), default=0.0
        ),
        "queue_depth": queue_depth,
        "watermark_lag_s": max(
            (o.get("watermark_lag_s", 0.0) for o in ops.values()),
            default=0.0,
        ),
        "phases": phases,
        "dispatch_p50_ms": round(1e3 * dispatch_p50, 3),
        "dispatches": dispatches,
        "padding_waste": round(padding, 4),
        "loop_lag_ms_p99": summary.get("loop_lag_ms", {}).get("p99", 0.0),
        "neighbors": neighbors[:8],
        "neighbor_top_share": round(
            neighbors[0]["busy_s"] / (busy_s + others), 4
        ) if neighbors and (busy_s + others) > 0 else 0.0,
        "attribution_coverage": summary.get("coverage", 1.0),
    }
    _windowed_overlay(sig, job_id, ops)
    return sig


def diagnose(sig: dict) -> dict:
    """Rank the five causes against one job's signals and name the
    limiting operator. Pure function of the signal dict so the offline
    (trace-dump) and online paths cannot drift."""
    busy = float(sig.get("busy_ratio") or 0.0)
    phases = sig.get("phases") or {}
    # named work alone: the recorder's own lists say what is a wait and
    # what encloses (`loop.idle` and `loop.run` would swamp every share)
    skip = timeline.WAITS + timeline.ENCLOSING
    phase_total = sum(
        v for p, v in phases.items() if p not in skip
    ) or 1e-9
    device_s = float(sig.get("device_s") or phases.get("dispatch", 0.0))
    busy_s = float(sig.get("busy_s") or 0.0) or phase_total
    device_share = min(1.0, device_s / busy_s) if busy_s > 0 else 0.0
    exchange_share = phases.get("exchange", 0.0) / phase_total
    lag_ms = float(sig.get("loop_lag_ms_p99") or 0.0)
    lag_factor = min(1.0, lag_ms / LAG_FLOOR_MS)
    neighbor_share = float(sig.get("neighbor_top_share") or 0.0)
    bp = float(sig.get("backpressure") or 0.0)
    pressure = max(bp, min(1.0, float(sig.get("queue_depth") or 0.0) / 4.0))

    scores = {
        # busy and mostly host work: the job's own python/arrow path is
        # the wall (decode/pack/emit dominate the ledger)
        "host-bound": busy * (1.0 - device_share) * (1.0 - exchange_share),
        # busy and inside jitted programs; paying the dispatch floor or
        # shipping padding amplifies the verdict
        "device-bound": busy * device_share * (
            1.0 + (0.5 if float(sig.get("dispatch_p50_ms") or 0.0)
                   >= DISPATCH_FLOOR_MS else 0.0)
            + min(0.5, float(sig.get("padding_waste") or 0.0))
        ),
        # the keyed shuffle dominates the phase ledger, or downstream
        # queues are full (the classic backpressure chain)
        "exchange-bound": max(exchange_share, bp) * max(busy, 0.3),
        # idle with an idle worker: upstream simply has nothing for it
        "starved": (1.0 - busy) * (1.0 - lag_factor)
        * (1.0 - neighbor_share) * (1.0 - pressure),
        # idle because a co-resident tenant holds the shared loop: only
        # scores when a neighbor actually dominates attributed busy AND
        # the loop shows contention
        "noisy-neighbor": (1.0 - busy) * neighbor_share
        * (0.4 + 0.6 * lag_factor)
        * (1.0 if neighbor_share >= NEIGHBOR_SHARE else 0.5),
    }
    ranked = sorted(
        ({"cause": c, "score": round(s, 4)} for c, s in scores.items()),
        key=lambda e: -e["score"],
    )
    top = ranked[0]
    operators = sig.get("operators") or []
    limiting = operators[0]["task"] if operators else None
    if top["cause"] == "exchange-bound" and operators:
        # under backpressure the slow consumer, not the busiest producer,
        # is the limiting operator: pick the most backpressured task's
        # downstream-most sibling (highest backpressure reading)
        limiting = max(
            operators, key=lambda o: o.get("backpressure", 0.0)
        )["task"]
    verdict = {
        "cause": top["cause"],
        "score": top["score"],
        "operator": limiting,
        "confidence": round(
            top["score"] / (top["score"] + ranked[1]["score"] + 1e-9), 3
        ),
    }
    if top["cause"] == "noisy-neighbor" and sig.get("neighbors"):
        verdict["suspect"] = sig["neighbors"][0]["job"]
    detail = {
        "host-bound": "host python/arrow work dominates; overlap "
                      "decode/pack with in-flight dispatch (ROADMAP 1)",
        "device-bound": "time sits inside jitted programs; check the "
                        "dispatch floor and padding waste",
        "exchange-bound": "the keyed shuffle / downstream queues limit "
                          "throughput",
        "starved": "idle with empty queues on an uncontended worker; "
                   "upstream produces too little",
        "noisy-neighbor": "idle while a co-resident tenant holds the "
                          "shared worker loop",
    }[top["cause"]]
    verdict["detail"] = detail
    return {"job": sig.get("job"), "verdict": verdict, "ranked": ranked,
            "signals": sig}


def report(job_id: str) -> dict:
    """collect + diagnose: the REST/debug doctor payload."""
    return diagnose(collect(job_id))


def signals_from_trace(events: List[dict], job_id: str) -> dict:
    """Reconstruct doctor signals from a (merged) Perfetto/Chrome trace
    dump: phase.* events carry the ledger, loop.lag events the loop
    contention, and per-job phase sums stand in for attributed busy.
    Enough to render the verdict offline when only artifacts survive."""
    phases: Dict[str, float] = {}
    by_job: Dict[str, float] = {}
    lags: List[float] = []
    t_min, t_max = None, None
    for ev in events:
        if ev.get("ph") != "X" or not ev.get("name", "").startswith("phase."):
            continue
        args = ev.get("args") or {}
        job = args.get("job", "")
        phase = ev["name"][len("phase."):]
        if phase == "loop.lag":
            lags.append((ev.get("dur") or 0.0) / 1e6)
            continue
        if phase in timeline.WAITS or phase == "loop.run":
            continue            # a wait is not work; the loop's own
                                # stretch encloses every job's
        # self time where the dump carries it: nested phases add up
        dur_s = (args.get("self", ev.get("dur")) or 0.0) / 1e6
        by_job[job] = by_job.get(job, 0.0) + dur_s
        ts = ev.get("ts", 0.0)
        t_min = ts if t_min is None else min(t_min, ts)
        t_max = ts + ev.get("dur", 0.0) if t_max is None else max(
            t_max, ts + ev.get("dur", 0.0))
        if job == job_id:
            phases[phase] = phases.get(phase, 0.0) + dur_s
    window = (t_max - t_min) / 1e6 if t_min is not None else 0.0
    busy_s = by_job.get(job_id, 0.0)
    neighbors = sorted(
        ({"job": j, "busy_s": round(s, 4)} for j, s in by_job.items()
         if j not in (job_id, "")),
        key=lambda n: -n["busy_s"],
    )
    others = sum(n["busy_s"] for n in neighbors)
    lags.sort()
    return {
        "job": job_id,
        "window_s": round(window, 3),
        "busy_s": round(busy_s, 4),
        "busy_ratio": round(busy_s / window, 4) if window > 0 else 0.0,
        "device_s": phases.get("dispatch", 0.0),
        "operators": [],
        "backpressure": 0.0,
        "queue_depth": 0.0,
        "watermark_lag_s": 0.0,
        "phases": {p: round(v, 6) for p, v in phases.items()},
        "dispatch_p50_ms": 0.0,
        "dispatches": 0,
        "padding_waste": 0.0,
        "loop_lag_ms_p99": round(
            1e3 * lags[min(len(lags) - 1, int(0.99 * len(lags)))], 3
        ) if lags else 0.0,
        "neighbors": neighbors[:8],
        "neighbor_top_share": round(
            neighbors[0]["busy_s"] / (busy_s + others), 4
        ) if neighbors and (busy_s + others) > 0 else 0.0,
        "offline": True,
    }
