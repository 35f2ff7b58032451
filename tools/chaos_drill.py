#!/usr/bin/env python
"""Run seeded exactly-once chaos drills against the embedded cluster.

    python tools/chaos_drill.py --list
        Enumerate every registered fault point (name, seam, effect).
        New injection seams MUST register here (arroyo_tpu/chaos/plan.py
        FAULT_POINTS); tests/test_chaos.py fails if a chaos.fire() call
        site and the registry ever disagree.

    python tools/chaos_drill.py --seed 20260804 --out CHAOS_DRILL.json
        The acceptance drill: for each golden query (default: one
        windowed aggregate, one join, one updating query) run fault-free,
        then under a seeded plan that SIGKILLs a worker mid-window, drops
        a data-plane connection, and fails a manifest CAS write; require
        byte-identical canonical sink output. Writes the results AND the
        fired-fault log to --out (commit it alongside the change).

    python tools/chaos_drill.py --fast
        The smoke drill the default test suite runs: 1 golden, 2 faults.

    python tools/chaos_drill.py --kafka
        Exactly-once through the transactional kafka sink (in-memory
        protocol-shaped fake broker) under worker kill + manifest CAS
        loss.

    python tools/chaos_drill.py --rescale
        Exactly-once through an AUTOSCALER-triggered rescale: a worker
        SIGKILL lands mid-rescale and a later rescale fails between its
        durable stop checkpoint and the reschedule; output must be
        byte-identical and the decision audit log is written next to
        the results.

    python tools/chaos_drill.py --failover
        ISSUE 17 acceptance: SIGKILL the primary under load with a hot
        standby armed and tailing; the standby must promote with zero
        cold restarts, sub-500ms gap (failover.promote span, recorded
        in the drill extras) and byte-identical output — then the
        standby-also-dies variant kills BOTH workers and requires the
        cold-restore fallback. With --plan, the serialized
        counterexample (e.g. promote_while_primary_alive's heartbeat
        blackout from tools/model_check.py --trace-dir) replays against
        the armed fleet: the standby promotes over an alive-but-silent
        primary and the fenced zombie must not double-emit.

    python tools/chaos_drill.py --plan COUNTEREXAMPLE.json
        Replay a model-checker counterexample (tools/model_check.py
        --trace-dir) — or any serialized FaultPlan — against the real
        embedded cluster: the golden drill runs under exactly that fault
        schedule. Accepts either a bare FaultPlan JSON or a
        counterexample payload with a "fault_plan" key. On fixed code
        the drill passes byte-identical; were the modeled bug live,
        this is the plan that demonstrates it end-to-end.

    python tools/chaos_drill.py --state-bloat
        ROADMAP item 4 acceptance: session state grows ~10x during the
        run, a worker is SIGKILLed mid-upload (storage latency widens
        the in-flight flush window), and the drill requires
        byte-identical output AND ~flat checkpoint capture time +
        delta byte RATE (bytes per second of epoch wall time) as state
        grows (<= 2x early-run medians;
        a full-snapshot design shows ~10x on both).

    python tools/chaos_drill.py --shared
        ISSUE 16 acceptance: two tenants whose scans fingerprint
        identically mount ONE shared host scan, a worker SIGKILL lands
        mid-checkpoint, and each tenant's output must be byte-identical
        to its own SOLO unshared fault-free run. With --plan, the
        serialized counterexample (e.g. the sharedplan model's
        leaked_barrier_across_tenants kill schedule from
        tools/model_check.py --shared --trace-dir) replays against the
        shared fleet instead of a golden query.

    python tools/chaos_drill.py --follower
        ISSUE 20 acceptance: a durable windowed pipeline with a
        follower read replica tailing its checkpoint stream, read
        continuously through the real serve gateway. Once reads route
        follower-first, the `replica.kill` seam drops the follower
        abruptly mid-tail: reads must fail over worker-ward with zero
        wrong values, the follower must reattach through the full
        _subscribe path (re-resolving latest.json — never an in-memory
        epoch), reads must come back follower-sourced, staleness stays
        <= 1 checkpoint interval throughout, and the sink output is
        byte-identical to the replica-off fault-free run.

    python tools/chaos_drill.py --starvation
        ROADMAP double-emit watch item: blocking `runner.stall` hits
        (params.block — a UDF that never yields) wedge one tenant's
        input loop and starve the shared event loop while heartbeat and
        checkpoint cadences are squeezed around the stall width, with
        the interleaving sanitizer (ARROYO_RACE_SANITIZER machinery)
        recording every shared-state access. Requires byte-identical
        output for BOTH tenants, no (key, window) row emitted twice,
        zero restarts, and a sanitizer-clean log; on failure the access
        log + Perfetto trace land in the workdir.

    python tools/chaos_drill.py --pipeline
        A stateless chain fused into ONE segment on small batches, a
        worker SIGKILL and a dropped connection land mid-stream;
        requires byte-identical output vs the UNFUSED fault-free run, a
        real recovery and every fault fired. (Every standard
        drill is also a fused-vs-unfused A/B: clean references run with
        segment fusion OFF, faulted runs keep the fused default.)
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# keep drills off any real accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true",
                    help="enumerate registered fault points and exit")
    ap.add_argument("--seed", type=int, default=20260804)
    ap.add_argument("--queries", type=str, default="",
                    help="comma-separated golden query names")
    ap.add_argument("--fast", action="store_true",
                    help="smoke drill: 1 golden, 2 quickly-detected faults")
    ap.add_argument("--kafka", action="store_true",
                    help="also run the transactional-kafka exactly-once drill")
    ap.add_argument("--rescale", action="store_true",
                    help="also run the autoscaler-rescale drill: worker "
                    "kill mid-automatic-rescale + reschedule failure, "
                    "byte-identical output required")
    ap.add_argument("--state-bloat", action="store_true",
                    help="also run the state-bloat drill: 10x state "
                    "growth + SIGKILL mid-upload; requires byte-identical "
                    "output and ~flat capture time / delta bytes")
    ap.add_argument("--pipeline", action="store_true",
                    help="also run the fused-segment drill: a stateless "
                    "chain fused into one segment, SIGKILL + connection "
                    "drop mid-stream; requires byte-identical output "
                    "vs the UNFUSED clean run and a real recovery")
    ap.add_argument("--shared", action="store_true",
                    help="also run the shared-plan fleet drill: two "
                    "tenants mount ONE shared scan, a worker SIGKILL "
                    "lands mid-checkpoint; each tenant's output must be "
                    "byte-identical to its SOLO unshared run (with "
                    "--plan: the counterexample replays against the "
                    "shared fleet instead of a golden)")
    ap.add_argument("--failover", action="store_true",
                    help="also run the hot-standby failover drill: "
                    "SIGKILL the primary with a standby armed "
                    "(sub-500ms promotion, byte-identical output) plus "
                    "the standby-also-dies cold-restore fallback (with "
                    "--plan: replay the counterexample against the "
                    "armed fleet)")
    ap.add_argument("--follower", action="store_true",
                    help="also run the follower-replica drill: kill the "
                    "follower abruptly mid-tail via the replica.kill "
                    "seam; requires worker-ward failover with zero wrong "
                    "values, a full _subscribe reattach off latest.json, "
                    "staleness <= 1 checkpoint interval throughout, and "
                    "byte-identical sink output")
    ap.add_argument("--starvation", action="store_true",
                    help="also run the event-loop starvation drill: "
                    "blocking runner.stall hits on one tenant under "
                    "squeezed heartbeat/checkpoint cadences with the "
                    "race sanitizer recording shared-state accesses; "
                    "requires byte-identical output, no duplicated "
                    "(key, window) row, zero restarts, and a "
                    "sanitizer-clean interleaving log (ROADMAP "
                    "double-emit watch item)")
    ap.add_argument("--no-golden", action="store_true",
                    help="skip the golden-query drills; run only the "
                    "specialty drills selected by the other flags")
    ap.add_argument("--plan", type=str, default="",
                    help="run the drill under a serialized FaultPlan JSON "
                    "(bare plan or a model-check counterexample payload "
                    "with a 'fault_plan' key)")
    ap.add_argument("--out", type=str, default="",
                    help="write results + fired-fault log to this JSON file")
    ap.add_argument("--workdir", type=str, default="")
    args = ap.parse_args()

    from arroyo_tpu.chaos import FAULT_POINTS, FaultPlan
    from arroyo_tpu.chaos import drill as d

    if args.list:
        width = max(len(n) for n in FAULT_POINTS)
        for name in sorted(FAULT_POINTS):
            print(f"{name:<{width}}  {FAULT_POINTS[name]}")
        return 0

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos-drill-")
    if args.plan:
        with open(args.plan) as f:
            doc = json.load(f)
        plan_doc = doc.get("fault_plan", doc)  # payload or bare plan
        plan_text = json.dumps(plan_doc)
        trace = doc.get("trace", {})
        if trace:
            print(f"replaying counterexample: {trace.get('violation')} "
                  f"(mutant {trace.get('mutant') or 'none'}, "
                  f"{len(trace.get('events', []))} model events)")
        queries = [] if (args.shared or args.failover) else (
            [q for q in args.queries.split(",") if q.strip()]
            or [d.DEFAULT_DRILL_QUERIES[0]]
        )
        # a fresh plan per drill run: hit counters are stateful
        plan_factory = lambda seed: FaultPlan.from_json(plan_text)  # noqa: E731
    elif args.fast:
        queries = [d.DEFAULT_DRILL_QUERIES[0]]
        plan_factory = d.fast_plan
    else:
        queries = (
            [q for q in args.queries.split(",") if q.strip()]
            or list(d.DEFAULT_DRILL_QUERIES)
        )
        plan_factory = d.standard_plan

    results = [] if args.no_golden else d.run_drills(
        queries, args.seed, workdir, plan_factory=plan_factory)
    if args.kafka:
        results.append(
            d.run_kafka_drill(args.seed, os.path.join(workdir, "kafka"))
        )
    if args.rescale:
        results.append(
            d.run_rescale_drill(args.seed, os.path.join(workdir, "rescale"))
        )
    if args.state_bloat:
        results.append(
            d.run_state_bloat_drill(
                args.seed, os.path.join(workdir, "state-bloat")
            )
        )
    if args.pipeline:
        results.append(
            d.run_pipeline_drill(
                args.seed, os.path.join(workdir, "pipeline")
            )
        )
    if args.shared:
        shared_kw = {"plan_factory": plan_factory} if args.plan else {}
        results.append(
            d.run_shared_drill(
                args.seed, os.path.join(workdir, "shared"), **shared_kw
            )
        )
    if args.failover:
        fo_kw = {"plan_factory": plan_factory} if args.plan else {}
        results.append(
            d.run_failover_drill(
                args.seed, os.path.join(workdir, "failover"), **fo_kw
            )
        )
    if args.follower:
        results.append(
            d.run_follower_drill(
                args.seed, os.path.join(workdir, "follower")
            )
        )
    if args.starvation:
        results.append(
            d.run_starvation_drill(
                args.seed, os.path.join(workdir, "starvation")
            )
        )

    ok = all(r.passed for r in results)
    for r in results:
        status = "PASS" if r.passed else f"FAIL ({r.error})"
        fired = ", ".join(
            f"{e['point']}@{e['hit']}" for e in r.comparable_log
        )
        print(f"{r.query:<24} {status:<10} rows={r.rows} "
              f"restarts={r.restarts} fired=[{fired}]")

    # conservation-ledger dump (ISSUE 19): the reconciler registry in the
    # /debug/audit shape, with ring breaches folded back in for jobs whose
    # reconciler was already expunged with the job (the ring survives
    # expunge precisely for this). Consumable offline by
    # `python tools/trace_report.py <file> --audit`.
    from arroyo_tpu.obs import audit

    audit_doc = audit.status()
    ring = [b for b in audit.breaches_since(0)
            if (b.get("job") or "?") not in audit_doc["jobs"]]
    for b in ring:
        j = audit_doc["jobs"].setdefault(
            b["job"], {"job": b["job"], "breaches": []})
        j["breaches"].append(b)
        j["breach_count"] = len(j["breaches"])
    os.makedirs(workdir, exist_ok=True)
    audit_path = os.path.join(workdir, "audit_status.json")
    with open(audit_path, "w") as f:
        json.dump(audit_doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {audit_path}")

    payload = {
        "seed": args.seed,
        "mode": ("plan" if args.plan else
                 "fast" if args.fast else "standard"),
        "passed": ok,
        "results": [r.to_json() for r in results],
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    rc = main()
    # skip interpreter-exit finalizers: leaked grpc-aio servers from the
    # embedded clusters can deadlock atexit (chip_smoke.py hard-exits
    # for the same reason); all results are flushed
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
