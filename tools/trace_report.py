#!/usr/bin/env python3
"""Flight-recorder trace merger / summarizer (ISSUE 4, extended by the
ISSUE 11 fleet observatory).

Merge Chrome trace-event JSON dumps from multiple processes (each
worker's and the controller's `/debug/trace`, or the REST
`/api/v1/jobs/{id}/traces`) into one Perfetto-loadable file, and print a
per-trace tree summary (span counts, phase durations, orphaned spans,
chaos fire events).

Usage:
  python tools/trace_report.py dump1.json dump2.json --out merged.json
  python tools/trace_report.py merged.json --summarize
  python tools/trace_report.py merged.json --job job7 --out job7.json
  python tools/trace_report.py merged.json --doctor job7
  python tools/trace_report.py --golden-ft --perfetto --out ft.json
  python tools/trace_report.py audit.json reports.json --audit

--golden-ft runs the golden windowed-aggregate fault-tolerance cycle
(embedded cluster, seeded chaos faults, recovery from checkpoints) and
writes its flight recording — CI uploads this on red runs; with
--perfetto the recording additionally carries the batch-phase timeline
ledger as named per-(job, phase) tracks. --job filters any operation to
one tenant's events; --doctor renders the bottleneck-doctor verdict
OFFLINE from a dump (phase.* events reconstruct the signals), so a CI
artifact is enough to name the limiting factor after the fact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def load_events(paths: List[str]) -> List[dict]:
    events: List[dict] = []
    seen = set()
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        for ev in doc.get("traceEvents", []):
            # dedupe spans that appear in several dumps (same span_id);
            # metadata and instant events without ids always pass through
            sid = (ev.get("args") or {}).get("span_id")
            key = (sid, ev.get("ts")) if sid else None
            if key is not None:
                if key in seen:
                    continue
                seen.add(key)
            events.append(ev)
    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    return events


def merge(paths: List[str]) -> dict:
    return {"traceEvents": load_events(paths), "displayTimeUnit": "ms"}


def filter_job(events: List[dict], job_id: str) -> List[dict]:
    """One tenant's events: spans by `{job_id}/` trace-id prefix, phase
    ledger entries by their `job` arg, metadata rows kept (they name
    tracks)."""
    prefix = f"{job_id}/"
    out = []
    for ev in events:
        if ev.get("ph") == "M":
            out.append(ev)
            continue
        args = ev.get("args") or {}
        tid = args.get("trace_id") or ""
        if tid.startswith(prefix) or args.get("job") == job_id:
            out.append(ev)
    return out


def group_traces(events: List[dict]) -> Dict[str, List[dict]]:
    """trace_id -> complete spans (ph == 'X')."""
    out: Dict[str, List[dict]] = defaultdict(list)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        tid = (ev.get("args") or {}).get("trace_id")
        if tid:
            out[tid].append(ev)
    return out


def tree_stats(spans: List[dict]) -> dict:
    """Connectivity + duration stats for one trace's spans."""
    by_id = {(s.get("args") or {}).get("span_id"): s for s in spans}
    roots, orphans = [], []
    for s in spans:
        parent = (s.get("args") or {}).get("parent_id")
        if parent is None:
            roots.append(s)
        elif parent not in by_id:
            orphans.append(s)
    by_cat: Dict[str, float] = defaultdict(float)
    for s in spans:
        by_cat[s.get("cat", "?")] += s.get("dur", 0.0)
    slowest = sorted(spans, key=lambda s: -s.get("dur", 0.0))[:5]
    return {
        "spans": len(spans),
        "roots": [s["name"] for s in roots],
        "orphans": len(orphans),
        "connected": len(roots) == 1 and not orphans,
        "duration_ms": round(
            max(s.get("dur", 0.0) for s in roots) / 1e3, 3
        ) if roots else None,
        "by_cat_ms": {k: round(v / 1e3, 3) for k, v in sorted(by_cat.items())},
        "slowest": [
            {"name": s["name"], "dur_ms": round(s.get("dur", 0.0) / 1e3, 3)}
            for s in slowest
        ],
    }


def summarize(events: List[dict], out=None) -> None:
    chaos_fires = [
        ev for ev in events
        if ev.get("ph") == "i" and ev.get("name", "").startswith("chaos.fire")
    ]
    traces = group_traces(events)
    print(f"{len(events)} events, {len(traces)} traces, "
          f"{len(chaos_fires)} chaos fires", file=out)
    for tid in sorted(traces):
        st = tree_stats(traces[tid])
        flag = "tree" if st["connected"] else (
            f"{len(st['roots'])} roots, {st['orphans']} orphans"
        )
        print(f"\n== {tid} [{flag}] {st['spans']} spans, "
              f"{st['duration_ms']} ms", file=out)
        print(f"   by cat: {st['by_cat_ms']}", file=out)
        for s in st["slowest"]:
            print(f"   slow: {s['name']} {s['dur_ms']} ms", file=out)
    for ev in chaos_fires:
        print(f"\nchaos: {ev['name']} @ {ev.get('ts')} "
              f"{ev.get('args')}", file=out)


def latency_summary(report: dict, out=None) -> None:
    """Pretty-print one /debug/latency (or REST /jobs/{id}/latency) dump:
    per-operator + end-to-end marker quantiles, per-program XLA compile/
    dispatch stats, padding waste per rung, and the recompile-cause log."""

    def series(title, rows):
        print(f"\n== {title}", file=out)
        if not rows:
            print("   (no samples)", file=out)
            return
        for r in rows:
            qs = " ".join(
                f"{q}={r[f'{q}_ms']}ms" for q in ("p50", "p95", "p99")
                if f"{q}_ms" in r
            )
            print(f"   {r.get('job')}/{r.get('task')}: "
                  f"n={r['samples']} mean={r['mean_ms']}ms {qs}", file=out)

    series("operator latency (marker transit source->operator)",
           report.get("operators", []))
    series("end-to-end latency (marker transit source->sink)",
           report.get("end_to_end", []))
    dev = report.get("device", {})
    progs = dev.get("programs", {})
    if progs:
        print("\n== device programs", file=out)
        for name, p in sorted(progs.items()):
            dq = p.get("dispatch_quantiles", {})
            print(f"   {name}: compiles={p.get('compiles', 0)} "
                  f"compile_s={p.get('compile_s_total', 0)} "
                  f"dispatches={p.get('dispatches', 0)} "
                  f"dispatch_p95={dq.get('p95', 'n/a')}s "
                  f"cache={p.get('cache_hit', 0)}h/"
                  f"{p.get('cache_miss', 0)}m", file=out)
    waste = [w for w in dev.get("padding_waste", []) if w.get("waste")]
    if waste:
        print("\n== padding waste (last dispatch per program/rung)",
              file=out)
        for w in waste:
            print(f"   {w['program']} rung={w['rung']}: "
                  f"{100.0 * w['waste']:.1f}%", file=out)
    recompiles = dev.get("recompiles", [])
    if recompiles:
        print(f"\n== recompile causes ({len(recompiles)})", file=out)
        for r in recompiles[-20:]:
            print(f"   {r['program']} #{r['nth_compile']} [{r['cause']}] "
                  f"rung={r['rung']} {r['compile_s']}s sig={r['signature']}",
                  file=out)


def doctor_summary(events: List[dict], job_id: str, out=None) -> int:
    """Offline bottleneck doctor: reconstruct signals from a dump's
    phase.* events and render the ranked verdict. Returns 0 when a
    verdict could be produced, 1 when the dump carries no phase ledger
    for the job (nothing to diagnose)."""
    from arroyo_tpu.obs import doctor

    sig = doctor.signals_from_trace(events, job_id)
    if not sig["phases"] and not sig["neighbors"]:
        print(f"no phase-ledger events for job {job_id!r} in the dump "
              "(export with fmt=perfetto / --perfetto)", file=out)
        return 1
    rep = doctor.diagnose(sig)
    v = rep["verdict"]
    print(f"== doctor: {job_id}", file=out)
    print(f"   verdict: {v['cause']} (score {v['score']}, confidence "
          f"{v['confidence']})", file=out)
    if v.get("suspect"):
        print(f"   suspect: {v['suspect']}", file=out)
    print(f"   {v['detail']}", file=out)
    for r in rep["ranked"]:
        print(f"   {r['cause']:<15} {r['score']}", file=out)
    print(f"   busy_ratio={sig['busy_ratio']} window_s={sig['window_s']} "
          f"loop_lag_ms_p99={sig['loop_lag_ms_p99']}", file=out)
    if sig["phases"]:
        print("   phases: " + " ".join(
            f"{p}={s:.4f}s" for p, s in sorted(sig["phases"].items())
        ), file=out)
    for n in sig["neighbors"][:5]:
        print(f"   neighbor {n['job']}: busy={n['busy_s']}s", file=out)
    return 0


def audit_report(paths: List[str], out=None) -> int:
    """Offline conservation reconciliation (ISSUE 19). Accepts two
    artifact shapes per input file:

      * a `/debug/audit` (or `GET /api/v1/jobs/{id}/audit`) payload —
        the reconciler's own status, rendered as-is;
      * a raw checkpoint-report dump (a JSON list of
        {job_id, task_id, epoch, audit} dicts, in arrival order) —
        REPLAYED through a fresh Reconciler, so a CI artifact of the
        reports is enough to re-derive the breach verdict after the
        fact, intake fencing included.

    Prints a per-edge attestation table per job and points at the first
    divergence. Returns 1 when any breach is present, 0 when the ledger
    is clean."""
    from arroyo_tpu.obs import audit as audit_mod

    jobs: Dict[str, dict] = {}
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        if isinstance(doc, list):
            job_id = next(
                (r.get("job_id") for r in doc if r.get("job_id")),
                os.path.basename(p),
            )
            rec = audit_mod.Reconciler(job_id)
            # replay in arrival order: an epoch reconciles (and becomes
            # the published horizon) once a later epoch starts reporting,
            # which is exactly when the controller's pipelined publish
            # would have sealed it
            pending: Dict[int, Dict[str, dict]] = {}
            published = 0
            for r in doc:
                if r.get("audit") is None:
                    continue
                epoch = int(r["epoch"])
                for done in sorted(e for e in pending if e < epoch):
                    rec.reconcile(done, {
                        t: rr.get("audit")
                        for t, rr in pending.pop(done).items()
                    })
                    published = max(published, done)
                if rec.intake(r.get("task_id", "?"), epoch, r["audit"],
                              published or None):
                    continue
                pending.setdefault(epoch, {})[r.get("task_id", "?")] = r
            for done in sorted(pending):
                rec.reconcile(done, {
                    t: rr.get("audit") for t, rr in pending[done].items()
                })
            jobs[job_id] = rec.status()
        elif "jobs" in doc:
            jobs.update(doc["jobs"])
        elif doc.get("job"):
            jobs[doc["job"]] = doc
    if not jobs:
        print("no audit payloads found in the inputs", file=out)
        return 1
    breached = False
    for job_id, st in sorted(jobs.items()):
        print(f"== audit: {job_id}", file=out)
        print(f"   incarnation={st.get('incarnation')} "
              f"epochs_reconciled={st.get('epochs_reconciled', 0)} "
              f"edges_verified={st.get('edges_verified', 0)} "
              f"rows_attested={st.get('rows_attested', 0)}", file=out)
        edges = st.get("edges") or {}
        if edges:
            print(f"   {'edge':<24} {'epoch':>5} "
                  f"{'tx rows':>8} {'rx rows':>8}  digest ok", file=out)
            for edge, v in sorted(edges.items()):
                tx, rx = v.get("tx") or [0, 0], v.get("rx") or [0, 0]
                print(f"   {edge:<24} {v.get('epoch', 0):>5} "
                      f"{tx[0]:>8} {rx[0]:>8}  "
                      f"{'ok' if v.get('ok') else 'DIVERGED'}", file=out)
        breaches = st.get("breaches") or []
        if breaches:
            breached = True
            first = min(breaches, key=lambda b: (b.get("epoch", 0),
                                                 b.get("ts", 0)))
            print(f"   BREACHES ({len(breaches)}):", file=out)
            for b in breaches:
                print(f"     [{b.get('kind')}] edge={b.get('edge')} "
                      f"epoch={b.get('epoch')}: {b.get('detail')}",
                      file=out)
            print(f"   first divergence: epoch {first.get('epoch')} "
                  f"edge {first.get('edge')} [{first.get('kind')}]",
                  file=out)
        else:
            print("   conservation ledger clean", file=out)
    return 1 if breached else 0


def run_golden_ft(out_path: str, perfetto: bool = False) -> int:
    """Run the golden windowed-agg fault-tolerance cycle (embedded
    cluster + seeded faults + recovery) and write its flight recording.
    Returns 0 when the drill passed AND the checkpoint traces recorded."""
    from arroyo_tpu import obs
    from arroyo_tpu.chaos import drill

    import tempfile

    obs.reset()
    with tempfile.TemporaryDirectory() as tmp:
        res = drill.run_drill(
            drill.DEFAULT_DRILL_QUERIES[0], seed=20260804, workdir=tmp,
            plan_factory=drill.fast_plan, throttle=400.0,
        )
    spans = obs.recorder().snapshot()
    doc = obs.perfetto_trace(spans) if perfetto else obs.chrome_trace(spans)
    doc["drill"] = {"passed": res.passed, "error": res.error,
                    "restarts": res.restarts,
                    "fired": res.comparable_log}
    with open(out_path, "w") as f:
        json.dump(doc, f)
    print(f"golden FT cycle: passed={res.passed} restarts={res.restarts} "
          f"spans={len(spans)} -> {out_path}")
    summarize(doc["traceEvents"])
    return 0 if res.passed and spans else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("inputs", nargs="*", help="Chrome trace JSON dumps")
    ap.add_argument("--out", help="write the merged trace JSON here")
    ap.add_argument("--summarize", action="store_true",
                    help="print per-trace tree summaries")
    ap.add_argument("--golden-ft", action="store_true",
                    help="run the golden fault-tolerance cycle and dump "
                         "its flight recording (requires --out)")
    ap.add_argument("--latency", action="store_true",
                    help="treat inputs as /debug/latency dumps and print "
                         "the device-tier observatory summary")
    ap.add_argument("--job", help="filter every operation to one job's "
                                  "events (spans by trace-id prefix, "
                                  "phase entries by their job arg)")
    ap.add_argument("--perfetto", action="store_true",
                    help="with --golden-ft: include the batch-phase "
                         "timeline ledger in the recording (named "
                         "per-(job, phase) tracks)")
    ap.add_argument("--doctor", metavar="JOB",
                    help="render the bottleneck-doctor verdict OFFLINE "
                         "from the input dumps' phase-ledger events")
    ap.add_argument("--audit", action="store_true",
                    help="treat inputs as conservation-ledger artifacts "
                         "(/debug/audit payloads or raw checkpoint-report "
                         "dumps) and reconcile them offline: per-edge "
                         "attestation table + first-divergence pointer")
    args = ap.parse_args(argv)
    if args.golden_ft:
        if not args.out:
            ap.error("--golden-ft requires --out")
        return run_golden_ft(args.out, perfetto=args.perfetto)
    if args.latency:
        if not args.inputs:
            ap.error("no latency dumps given")
        for p in args.inputs:
            with open(p) as f:
                report = json.load(f)
            print(f"--- {p}")
            latency_summary(report)
        return 0
    if not args.inputs:
        ap.error("no input dumps given")
    if args.audit:
        return audit_report(args.inputs)
    doc = merge(args.inputs)
    if args.job:
        doc["traceEvents"] = filter_job(doc["traceEvents"], args.job)
    if args.doctor:
        return doctor_summary(doc["traceEvents"], args.doctor)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f)
        print(f"merged {len(args.inputs)} dumps "
              f"({len(doc['traceEvents'])} events) -> {args.out}")
    if args.summarize or not args.out:
        summarize(doc["traceEvents"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
