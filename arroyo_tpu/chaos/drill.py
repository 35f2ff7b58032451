"""Exactly-once verification drills: golden queries under fault plans.

A drill runs one committed golden query (tests/golden/queries/*.sql)
twice through the REAL embedded cluster — controller + N workers over the
gRPC control plane and TCP data plane:

  1. fault-free, to establish the reference output (also cross-checked
     against the committed golden file when one exists), then
  2. under an installed `FaultPlan` with a throttled source and a fast
     checkpoint cadence, so worker kills, data-plane drops, and storage
     faults land mid-stream and force recovery from durable checkpoints.

The drill passes iff the faulted run's canonicalized sink output is
identical to the fault-free run's AND every scheduled fault actually
fired (an unfired fault means the protocol wasn't exercised — that's a
coverage failure, not a pass). The fired-fault log's comparable view is
a pure function of the plan's seed, which is the reproducibility the
acceptance criteria pin.

Debezium outputs are compared by merged net state keyed by the query's
`--pk=` header — the retract/append interleaving is timing-dependent,
the net state is not (same canonicalization as tests/test_golden.py).
"""

from __future__ import annotations

import asyncio
import dataclasses
import glob
import json
import os
import random
from typing import Callable, Dict, List, Optional

from .. import chaos
from ..utils.logging import get_logger
from .plan import FaultPlan

logger = get_logger("chaos.drill")

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_GOLDEN_DIR = os.path.join(REPO_ROOT, "tests", "golden")

# acceptance set: one windowed aggregate, one join, one updating query
DEFAULT_DRILL_QUERIES = (
    "hourly_by_event_type",   # tumbling windowed aggregate
    "offset_impulse_join",    # windowed join across two sources
    "updating_aggregate",     # updating aggregate with retractions
)


# -- golden-query plumbing (mirrors tests/test_golden.py) --------------------


def query_headers(path: str) -> Dict[str, str]:
    headers = {}
    for line in open(path):
        line = line.strip()
        if not line.startswith("--") or "=" not in line:
            break
        k, v = line[2:].split("=", 1)
        headers[k.strip()] = v.strip()
    return headers


def register_query_udfs(headers: Dict[str, str], golden_dir: str) -> None:
    if "udf" in headers:
        from ..udf import registry

        src = open(os.path.join(golden_dir, headers["udf"])).read()
        registry.register_from_source(src)


def load_query(path: str, output_path: str, golden_dir: str,
               throttle: Optional[float] = None) -> str:
    sql = open(path).read()
    sql = sql.replace("$input_dir", os.path.join(golden_dir, "inputs"))
    sql = sql.replace("$output_path", output_path)
    if throttle:
        sql = sql.replace(
            "type = 'source'",
            f"type = 'source',\n  throttle_per_sec = '{throttle}'",
        )
    return sql


def read_rows(path: str) -> List[dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def canonical(rows: List[dict]) -> List[str]:
    return sorted(json.dumps(r, sort_keys=True, default=str) for r in rows)


def merge_debezium(rows: List[dict], pk: List[str]) -> List[dict]:
    state = {}
    for env in rows:
        if env["op"] == "d":
            key = tuple(env["before"][c] for c in pk)
            state.pop(key, None)
        else:
            row = env["after"]
            state[tuple(row[c] for c in pk)] = row
    return [state[k] for k in sorted(state)]


def canonicalize_output(path: str, sql: str,
                        headers: Dict[str, str]) -> List[str]:
    rows = read_rows(path)
    if "debezium_json" in sql:
        pk = headers.get("pk", "").split(",") if headers.get("pk") else None
        assert pk, "debezium drill queries need a --pk= header"
        return canonical(merge_debezium(rows, pk))
    return canonical(rows)


# -- fault plans -------------------------------------------------------------


def standard_plan(seed: int) -> FaultPlan:
    """The acceptance plan: SIGKILL a worker mid-window, drop a data-plane
    connection, and fail a manifest CAS write — each at a seed-chosen hit
    index. Hit windows are small enough that every fault is reachable in
    a throttled multi-second run, so the full schedule always fires and
    the comparable fired log equals `plan.expected_log()`."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    # heartbeat ticks arrive every worker.heartbeat_interval across all
    # in-process workers (2 workers at 0.1s ≈ 20 hits/s): hits 8-16 land
    # the kill 0.4-0.8s in — after the job is Running, well before the
    # throttled source drains
    plan.add("worker.kill", at_hits=(rng.randint(8, 16),))
    plan.add("network.drop_connection", at_hits=(rng.randint(4, 16),))
    plan.add(
        "storage.cas_conflict",
        at_hits=(rng.randint(1, 2),),
        match={"key": "checkpoint-manifest"},
    )
    return plan


def fast_plan(seed: int) -> FaultPlan:
    """Smoke plan for the default (tier-1) suite: two quickly-detected
    faults, no heartbeat-timeout wait."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("network.drop_connection", at_hits=(rng.randint(3, 10),))
    plan.add(
        "storage.cas_conflict",
        at_hits=(1,),
        match={"key": "checkpoint-manifest"},
    )
    return plan


# -- drill execution ---------------------------------------------------------


@dataclasses.dataclass
class DrillResult:
    query: str
    seed: int
    passed: bool
    rows: int
    restarts: int
    fired: List[dict]          # full fired-fault log (wall-clock + ctx)
    comparable_log: List[dict]  # the reproducible view
    expected_log: List[dict]
    unfired: List[dict]
    error: Optional[str] = None
    # drill-specific measurements (e.g. the state-bloat flatness stats)
    extras: Optional[dict] = None
    # conservation-ledger breaches recorded DURING the drill (obs/audit.py):
    # auditing is on by default and every drill asserts audit SILENCE, so
    # this must be empty for passed=True
    audit_breaches: List[dict] = dataclasses.field(default_factory=list)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _audit_mark() -> int:
    """Snapshot the conservation-ledger breach ring before a drill run.
    The ring survives job expunge precisely so this assertion works after
    the embedded controller tears the drill jobs down."""
    from ..obs import audit

    return audit.breach_mark()


def _audit_verdict(mark: int, passed: bool, error: Optional[str]):
    """Fold conservation breaches recorded since `mark` into the drill
    verdict: a single breach fails the drill even when the sink output
    is byte-identical — silent corruption is exactly what the ledger
    exists to catch."""
    from ..obs import audit

    breaches = audit.breaches_since(mark)
    if breaches and error is None:
        b = breaches[0]
        error = (
            f"{len(breaches)} conservation breach(es); first: "
            f"[{b['kind']}] edge={b['edge']} epoch={b['epoch']}: "
            f"{b['detail']}"
        )
    return passed and not breaches, error, breaches


def _run_embedded(sql: str, job_id: str, storage_url: Optional[str],
                  n_workers: int, parallelism: int, max_restarts: int,
                  heartbeat_interval: float, heartbeat_timeout: float,
                  checkpoint_interval: float, timeout: float) -> int:
    """One job through controller + embedded workers; returns restarts.
    Raises on FAILED."""
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    async def go():
        with update(
            worker={"heartbeat_interval": heartbeat_interval},
            controller={"heartbeat_timeout": heartbeat_timeout},
            pipeline={"checkpointing": {"interval": checkpoint_interval}},
        ):
            c = await ControllerServer(
                EmbeddedScheduler(), max_restarts=max_restarts
            ).start()
            try:
                await c.submit_job(
                    job_id, sql=sql, storage_url=storage_url,
                    n_workers=n_workers, parallelism=parallelism,
                )
                state = await c.wait_for_state(
                    job_id, JobState.FINISHED, JobState.FAILED,
                    timeout=timeout,
                )
                job = c.jobs[job_id]
                if state != JobState.FINISHED:
                    raise RuntimeError(
                        f"drill job {job_id} failed: {job.failure}"
                    )
                return job.restarts
            finally:
                await c.stop()

    return asyncio.run(go())


def run_drill(
    query_name: str,
    seed: int,
    workdir: str,
    plan_factory: Callable[[int], FaultPlan] = standard_plan,
    golden_dir: str = DEFAULT_GOLDEN_DIR,
    n_workers: int = 2,
    parallelism: int = 2,
    throttle: float = 150.0,
    heartbeat_interval: float = 0.1,
    heartbeat_timeout: float = 1.5,
    checkpoint_interval: float = 0.15,
    timeout: float = 120.0,
) -> DrillResult:
    """Run one golden query fault-free, then under `plan_factory(seed)`,
    and verify byte-identical canonical sink output.

    The fault-free reference intentionally runs with SEGMENT FUSION OFF
    while the faulted run keeps the default (fusion + pipelining ON):
    every drill is therefore also a fused-vs-unfused A/B — the fused
    data plane must produce byte-identical output to the per-operator
    plan AND survive the fault schedule (ISSUE 14)."""
    from ..config import update

    query_path = os.path.join(golden_dir, "queries", f"{query_name}.sql")
    headers = query_headers(query_path)
    register_query_udfs(headers, golden_dir)
    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()

    # 1. fault-free reference through the same embedded cluster, on the
    # UNFUSED data plane (segment fusion off)
    clean_out = os.path.join(workdir, f"{query_name}-clean.json")
    clean_sql = load_query(query_path, clean_out, golden_dir)
    assert chaos.installed() is None, "a fault plan is already installed"
    with update(engine={"segment_fusion": False}):
        _run_embedded(
            clean_sql, f"drill-{query_name}-clean", None, n_workers,
            parallelism, max_restarts=0,
            heartbeat_interval=heartbeat_interval,
            heartbeat_timeout=30.0, checkpoint_interval=60.0,
            timeout=timeout,
        )
    want = canonicalize_output(clean_out, clean_sql, headers)
    if not want:
        raise RuntimeError(f"{query_name}: fault-free run produced no output")
    golden_file = os.path.join(golden_dir, "golden_outputs",
                               f"{query_name}.json")
    if os.path.exists(golden_file):
        committed = [line.strip() for line in open(golden_file)]
        if want != committed:
            raise RuntimeError(
                f"{query_name}: fault-free embedded-cluster output "
                "diverges from the committed golden — fix that before "
                "trusting any drill"
            )

    # 2. faulted run: throttled source + fast checkpoint cadence so the
    # scheduled faults land mid-stream
    fault_out = os.path.join(workdir, f"{query_name}-faulted.json")
    fault_sql = load_query(query_path, fault_out, golden_dir,
                           throttle=throttle)
    plan = chaos.install(plan_factory(seed))
    error = None
    restarts = 0
    try:
        restarts = _run_embedded(
            fault_sql, f"drill-{query_name}-faulted",
            os.path.join(workdir, f"{query_name}-ck"), n_workers,
            parallelism, max_restarts=8,
            heartbeat_interval=heartbeat_interval,
            heartbeat_timeout=heartbeat_timeout,
            checkpoint_interval=checkpoint_interval, timeout=timeout,
        )
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()

    got = canonicalize_output(fault_out, fault_sql, headers)
    passed = error is None and got == want and not plan.unfired()
    if error is None and got != want:
        error = (
            f"output diverged: {len(got)} rows vs {len(want)} fault-free"
        )
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query=query_name,
        seed=seed,
        passed=passed,
        rows=len(got),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        audit_breaches=audit_breaches,
    )


def run_drills(query_names, seed: int, workdir: str,
               plan_factory: Callable[[int], FaultPlan] = standard_plan,
               **kw) -> List[DrillResult]:
    out = []
    for i, name in enumerate(query_names):
        logger.info("drill %d/%d: %s (seed %s)", i + 1, len(query_names),
                    name, seed)
        out.append(run_drill(name, seed, os.path.join(workdir, name),
                             plan_factory=plan_factory, **kw))
    return out


# -- rescale drill (autoscaler-triggered, faulted mid-rescale) ---------------


def rescale_plan(seed: int) -> FaultPlan:
    """Faults aimed at the autoscaler's actuation path: stretch the
    decide->stop window, SIGKILL a worker inside it (the stop checkpoint
    fails, the job recovers, the autoscaler re-decides), then — on the
    rescale that survives to the generation-OVERLAP window (stop
    checkpoint durable, old generation draining, new incarnation staged
    and restoring) — SIGKILL a pool worker INSIDE that window and fail
    the promote (recovery must come back at the new parallelism). Every
    rescale.* fault implies a rescale actually triggered."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("rescale.stop_delay", at_hits=(1,),
             params={"delay": 0.8}, max_fires=1)
    # heartbeats tick every 0.1s across 2 workers (~20 hits/s): land the
    # kill around the first rescale decision (~0.9s in) so it interrupts
    # the decide/stop window the delay above holds open
    plan.add("worker.kill", at_hits=(rng.randint(16, 26),))
    # the first rescale to reach the overlap window (the staged new
    # incarnation is restoring, the old one draining): SIGKILL a pool
    # worker right there — byte-identical output is still required
    plan.add("rescale.overlap_kill", at_hits=(1,))
    # always the FIRST reschedule attempt: a rescale that survives the
    # kill may be the only one (min==max converges in a single step)
    plan.add("rescale.reschedule_fail", at_hits=(1,))
    return plan


def _measure_rescale_gap(mode: str, workdir: str,
                         timeout: float = 90.0) -> dict:
    """Output-gap probe (ISSUE 15): run a fault-free replay-impulse
    windowed pipeline, trigger ONE manual 1->2 rescale (source + window —
    the elastic-source path), and measure the RESCALING -> RUNNING wall
    time from the job's transition log plus the `rescale.overlap` span's
    own gap_ms. `mode` pins rescale.mode, so the same probe measures the
    generation-overlap path AND the stop-the-world baseline."""
    import asyncio as aio

    from .. import obs
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    os.makedirs(workdir, exist_ok=True)
    out = os.path.join(workdir, f"gap-{mode}.json")
    n = 4000
    sql = f"""
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '2000',
      message_count = '{n}', start_time = '0',
      realtime = 'true', replay = 'true'
    );
    CREATE TABLE out (k BIGINT UNSIGNED, start TIMESTAMP, cnt BIGINT) WITH (
      connector = 'single_file', path = '{out}',
      format = 'json', type = 'sink'
    );
    INSERT INTO out
    SELECT k, window.start as start, cnt FROM (
      SELECT counter % 4 as k, tumble(interval '500 millisecond') as window,
             count(*) as cnt
      FROM impulse GROUP BY 1, 2
    );
    """

    async def go():
        with update(pipeline={"checkpointing": {"interval": 0.25}},
                    rescale={"mode": mode}):
            obs.reset()
            c = await ControllerServer(EmbeddedScheduler()).start()
            try:
                await c.submit_job(
                    f"gap-{mode}", sql=sql,
                    storage_url=os.path.join(workdir, f"gap-{mode}-ck"),
                    n_workers=2, parallelism=1,
                )
                await c.wait_for_state(f"gap-{mode}", JobState.RUNNING,
                                       timeout=30)
                await aio.sleep(0.8)
                job = c.jobs[f"gap-{mode}"]
                targets = {
                    nid: 2 for nid, nd in job.graph.nodes.items()
                    if not nd.is_sink
                }
                await c.rescale_job(f"gap-{mode}", targets)
                state = await c.wait_for_state(
                    f"gap-{mode}", JobState.FINISHED, JobState.FAILED,
                    timeout=timeout,
                )
                events = list(job.events)
                spans = [
                    dict(s.get("attrs", {}))
                    for s in obs.recorder().snapshot()
                    if s.get("name") == "rescale.overlap"
                ]
                return state, job.failure, job.rescales, events, spans
            finally:
                await c.stop()

    state, failure, rescales, events, spans = asyncio.run(go())
    # RESCALING-entry -> back-to-RUNNING from the transition log: the
    # comparable gap measure across both modes (covers drain + stop
    # checkpoint + handoff; sources resume right after RUNNING)
    gaps = []
    t_resc = None
    for e in events:
        if e["to"] == "Rescaling":
            t_resc = e["time"]
        elif e["to"] == "Running" and t_resc is not None:
            gaps.append((e["time"] - t_resc) / 1e6)
            t_resc = None
    span_gaps = sorted(float(s["gap_ms"]) for s in spans if "gap_ms" in s)
    return {
        "mode": mode,
        "finished": str(state),
        "failure": failure,
        "rescales": rescales,
        "rescaling_to_running_ms": [round(g, 1) for g in sorted(gaps)],
        "overlap_gap_ms_p50": round(
            span_gaps[len(span_gaps) // 2], 1) if span_gaps else None,
        "overlap_gap_ms_max": round(span_gaps[-1], 1) if span_gaps else None,
    }


def run_rescale_drill(seed: int, workdir: str,
                      query_name: str = "hourly_by_event_type",
                      golden_dir: str = DEFAULT_GOLDEN_DIR,
                      throttle: float = 120.0,
                      timeout: float = 180.0) -> DrillResult:
    """Exactly-once through an AUTOSCALER-triggered rescale under faults.

    The reference run executes the golden fault-free. The drill run
    starts the same query at parallelism 1 with the autoscaler on and
    `autoscale.min_parallelism = 2`: the unconditional clamp makes the
    first post-warmup decision a deterministic scale-up, so a real
    automatic rescale happens mid-stream without depending on load
    timing. The fault plan kills a worker mid-rescale and fails a later
    rescale between its durable stop checkpoint and the reschedule; the
    canonical sink output must still be byte-identical to the fault-free
    run. The decision audit log is written to
    {workdir}/autoscale_decisions.json (CI uploads it on failure)."""
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    query_path = os.path.join(golden_dir, "queries", f"{query_name}.sql")
    headers = query_headers(query_path)
    register_query_udfs(headers, golden_dir)
    os.makedirs(workdir, exist_ok=True)
    # explicit audit-silence assertion (ISSUE 19): the rescale drill's
    # generation-overlap window is exactly where rewind/zombie classes
    # would surface — a breach here fails the drill outright
    audit_mark = _audit_mark()

    clean_out = os.path.join(workdir, f"{query_name}-clean.json")
    clean_sql = load_query(query_path, clean_out, golden_dir)
    assert chaos.installed() is None, "a fault plan is already installed"
    _run_embedded(
        clean_sql, "drill-rescale-clean", None, 2, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=timeout,
    )
    want = canonicalize_output(clean_out, clean_sql, headers)
    if not want:
        raise RuntimeError(f"{query_name}: fault-free run produced no output")

    fault_out = os.path.join(workdir, f"{query_name}-rescale.json")
    fault_sql = load_query(query_path, fault_out, golden_dir,
                           throttle=throttle)
    plan = chaos.install(rescale_plan(seed))
    error = None
    restarts = rescales = 0
    decisions: List[dict] = []

    async def go():
        nonlocal restarts, rescales
        with update(
            worker={"heartbeat_interval": 0.1},
            controller={"heartbeat_timeout": 1.5},
            pipeline={"checkpointing": {"interval": 0.15}},
            autoscale={
                "enabled": True, "period": 0.3, "warmup_periods": 1,
                "cooldown_periods": 2, "min_parallelism": 2,
                "max_parallelism": 2,
            },
        ):
            c = await ControllerServer(
                EmbeddedScheduler(), max_restarts=8
            ).start()
            try:
                await c.submit_job(
                    "drill-rescale-faulted", sql=fault_sql,
                    storage_url=os.path.join(workdir, "rescale-ck"),
                    n_workers=2, parallelism=1,
                )
                state = await c.wait_for_state(
                    "drill-rescale-faulted", JobState.FINISHED,
                    JobState.FAILED, timeout=timeout,
                )
                job = c.jobs["drill-rescale-faulted"]
                restarts, rescales = job.restarts, job.rescales
                decisions.extend(job.autoscale_decisions)
                if state != JobState.FINISHED:
                    raise RuntimeError(
                        f"rescale drill failed: {job.failure}"
                    )
            finally:
                await c.stop()

    try:
        asyncio.run(go())
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()
    with open(os.path.join(workdir, "autoscale_decisions.json"), "w") as f:
        json.dump(decisions, f, indent=1, default=str)

    got = canonicalize_output(fault_out, fault_sql, headers)
    passed = (error is None and got == want and not plan.unfired()
              and rescales >= 1)
    if error is None and got != want:
        error = (
            f"output diverged: {len(got)} rows vs {len(want)} fault-free"
        )
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    if error is None and rescales < 1:
        error = "the autoscaler never triggered a rescale"
    # output-gap-per-rescale probes (ISSUE 15): a fault-free 1->2
    # source+window rescale per mode — the generation-overlap gap
    # (rescale.overlap span, checkpoint interval 0.25s) with the
    # stop-the-world teardown+reschedule baseline recorded alongside
    gap_overlap = gap_stw = None
    gap_error = None
    try:
        gap_overlap = _measure_rescale_gap(
            "overlap", os.path.join(workdir, "gap"))
        gap_stw = _measure_rescale_gap(
            "stop_the_world", os.path.join(workdir, "gap"))
        if "FINISHED" not in gap_overlap["finished"]:
            gap_error = f"overlap gap probe: {gap_overlap['failure']}"
        elif gap_overlap["rescales"] < 1:
            gap_error = "overlap gap probe: no rescale happened"
        elif gap_overlap["overlap_gap_ms_p50"] is None:
            gap_error = "overlap gap probe: no rescale.overlap span"
        elif "FINISHED" not in gap_stw["finished"]:
            gap_error = f"stop-the-world gap probe: {gap_stw['failure']}"
    except Exception as e:  # noqa: BLE001 - probe failure fails the drill
        gap_error = f"gap probe crashed: {e!r}"
    if error is None and gap_error is not None:
        error, passed = gap_error, False
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query=f"rescale_{query_name}",
        seed=seed,
        passed=passed,
        rows=len(got),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        extras={
            "rescale_gap_overlap": gap_overlap,
            "rescale_gap_stop_the_world": gap_stw,
        },
        audit_breaches=audit_breaches,
    )


# -- fused-pipeline drill (ISSUE 14 acceptance) ------------------------------


PIPELINE_DRILL_SQL = """
CREATE TABLE src (
  timestamp TIMESTAMP, k BIGINT NOT NULL, v BIGINT NOT NULL
) WITH (
  connector = 'single_file', path = '$src', format = 'json',
  type = 'source'{throttle}, event_time_field = 'timestamp'
);
CREATE TABLE out (
  k BIGINT NOT NULL, s BIGINT NOT NULL, c BIGINT NOT NULL
) WITH (
  connector = 'single_file', path = '$out', format = 'json', type = 'sink'
);
INSERT INTO out
SELECT k, sum(v_eur) AS s, count(*) AS c FROM (
  SELECT k, v_eur - v_eur % 10 AS v_eur FROM (
    SELECT k % 8 AS k, v * 100 / 121 AS v_eur FROM src WHERE v > 0
  )
)
GROUP BY k, tumble(interval '2 second');
"""


def pipeline_plan(seed: int) -> FaultPlan:
    """SIGKILL a worker while the fused segment's staging queue holds an
    in-flight batch (the throttled source + per-batch cadence keeps the
    two-deep pipeline primed), plus a data-plane drop for good measure —
    recovery must replay from the last durable epoch with no event lost
    or duplicated out of the staged (not yet emitted) batches."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("worker.kill", at_hits=(rng.randint(14, 26),))
    plan.add("network.drop_connection", at_hits=(rng.randint(4, 12),))
    return plan


def run_pipeline_drill(seed: int, workdir: str, n_rows: int = 6000,
                       timeout: float = 150.0) -> DrillResult:
    """Exactly-once through a fused segment. A 3-op stateless chain
    (filter -> convert -> round) feeds a tumbling aggregate; the clean
    reference runs UNFUSED, the faulted run keeps fusion ON with small
    batches, so barriers land between the segment's batches, and a
    worker SIGKILL plus a dropped connection land mid-stream. Passes iff
    (a) canonical output is byte-identical (no event lost or
    duplicated), (b) the kill forced a real recovery, (c) every fault
    fired and (d) the conservation audit is clean."""
    from ..config import update

    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()
    src = os.path.join(workdir, "pipe-in.json")
    with open(src, "w") as f:
        for i in range(n_rows):
            mins, secs = (i // 1200) % 60, (i // 20) % 60
            f.write(json.dumps({
                "k": i % 64,
                "v": (i * 37) % 1000 + 1,
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}."
                             f"{(i % 20) * 50:03d}Z",
            }) + "\n")

    clean_out = os.path.join(workdir, "pipe-clean.json")
    clean_sql = PIPELINE_DRILL_SQL.replace("$src", src).replace(
        "$out", clean_out).format(throttle="")
    assert chaos.installed() is None, "a fault plan is already installed"
    with update(engine={"segment_fusion": False}):
        _run_embedded(
            clean_sql, "drill-pipe-clean", None, 2, 1, max_restarts=0,
            heartbeat_interval=0.1, heartbeat_timeout=30.0,
            checkpoint_interval=60.0, timeout=timeout,
        )
    want = canonicalize_output(clean_out, clean_sql, {})
    if not want:
        raise RuntimeError("pipeline drill: fault-free run had no output")

    fault_out = os.path.join(workdir, "pipe-faulted.json")
    fault_sql = PIPELINE_DRILL_SQL.replace("$src", src).replace(
        "$out", fault_out).format(
        throttle=",\n  throttle_per_sec = '1500'")
    plan = chaos.install(pipeline_plan(seed))
    error = None
    restarts = 0
    try:
        with update(engine={"segment_fusion": True},
                    pipeline={"source_batch_size": 64}):
            restarts = _run_embedded(
                fault_sql, "drill-pipe-faulted",
                os.path.join(workdir, "pipe-ck"), 2, 1, max_restarts=8,
                heartbeat_interval=0.1, heartbeat_timeout=1.5,
                checkpoint_interval=0.15, timeout=timeout,
            )
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()

    got = canonicalize_output(fault_out, fault_sql, {})
    passed = (error is None and got == want and not plan.unfired()
              and restarts >= 1)
    if error is None and got != want:
        error = f"output diverged: {len(got)} rows vs {len(want)}"
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    if error is None and restarts < 1:
        error = "the SIGKILL never forced a recovery"
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="fused_pipeline_kill",
        seed=seed,
        passed=passed,
        rows=len(got),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        audit_breaches=audit_breaches,
    )


# -- state-bloat drill (ROADMAP item 4 acceptance) ---------------------------


STATE_BLOAT_SQL = """
CREATE TABLE src (
  timestamp TIMESTAMP, k BIGINT NOT NULL
) WITH (
  connector = 'single_file', path = '$src', format = 'json',
  type = 'source'{throttle}, event_time_field = 'timestamp'
);
CREATE TABLE out (
  k BIGINT NOT NULL, c BIGINT NOT NULL
) WITH (
  connector = 'single_file', path = '$out', format = 'json', type = 'sink'
);
INSERT INTO out
SELECT k, count(*) as c FROM src
GROUP BY k, session(interval '1 hour');
"""


def state_bloat_plan(seed: int) -> FaultPlan:
    """SIGKILL a worker mid-run with storage latency widening the upload
    windows, so the kill lands while checkpoint flushes are in flight —
    recovery must come back from the last *published* epoch with the
    blob chain intact."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("storage.latency", at_hits=tuple(range(2, 40, 3)),
             match={"key": "/data/"}, params={"delay": 0.08},
             max_fires=13)
    # heartbeats tick ~20/s across 2 workers: land the kill ~1.5-2.5s in,
    # after state has grown but with plenty of run left to re-grow it
    plan.add("worker.kill", at_hits=(rng.randint(30, 50),))
    return plan


def run_state_bloat_drill(seed: int, workdir: str, n_rows: int = 6000,
                          timeout: float = 180.0) -> DrillResult:
    """ROADMAP item 4 acceptance: session state grows ~10x during the
    run (every other row opens a NEW session key; the 1-hour gap keeps
    them all open until end-of-stream), a worker is SIGKILLed mid-upload,
    and the drill asserts (a) byte-identical exactly-once output, (b)
    checkpoint CAPTURE cost stays ~flat late-run vs early-run (median of
    per-epoch max checkpoint.capture span durations, <= 2x + a small
    absolute floor), and (c) the uploaded DELTA byte RATE for the
    session table stays ~flat (median late <= 2x median early, measured
    in bytes per second of epoch wall time so a slipping checkpoint
    cadence on a loaded host doesn't masquerade as state growth; base
    blobs are the amortized rebase cost and reported separately). A
    full-snapshot design shows ~10x growth on both."""
    import time as _time

    from .. import obs
    from ..config import update

    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()
    src = os.path.join(workdir, "bloat-in.json")
    with open(src, "w") as f:
        for i in range(n_rows):
            # monotonic event time, one NEW session key per two rows:
            # live state grows linearly all run (~10x early -> late)
            mins, secs = (i // 120) % 60, (i // 2) % 60
            f.write(json.dumps({
                "k": i // 2,
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}.000Z",
            }) + "\n")

    clean_out = os.path.join(workdir, "bloat-clean.json")
    clean_sql = STATE_BLOAT_SQL.replace("$src", src).replace(
        "$out", clean_out).format(throttle="")
    assert chaos.installed() is None, "a fault plan is already installed"
    _run_embedded(
        clean_sql, "drill-bloat-clean", None, 2, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=timeout,
    )
    want = canonicalize_output(clean_out, clean_sql, {})
    if not want:
        raise RuntimeError("state-bloat: fault-free run produced no output")

    fault_out = os.path.join(workdir, "bloat-faulted.json")
    fault_sql = STATE_BLOAT_SQL.replace("$src", src).replace(
        "$out", fault_out).format(
        throttle=",\n  throttle_per_sec = '1500'")
    plan = chaos.install(state_bloat_plan(seed))
    obs.recorder().clear()
    error = None
    restarts = 0
    storage = os.path.join(workdir, "bloat-ck")
    try:
        # rebase pushed out so the full delta chain survives GC — the
        # drill measures per-epoch delta flatness from the chain files
        with update(state={"rebase_epochs": 500,
                           "max_inflight_flushes": 2}):
            restarts = _run_embedded(
                fault_sql, "drill-bloat-faulted", storage, 2, 1,
                max_restarts=8, heartbeat_interval=0.1,
                heartbeat_timeout=1.5, checkpoint_interval=0.15,
                timeout=timeout,
            )
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()

    got = canonicalize_output(fault_out, fault_sql, {})

    # (b) capture flatness from the flight recorder: per-epoch max of
    # checkpoint.capture span durations (ms), early vs late median
    spans = [
        s for s in obs.recorder().snapshot()
        if s.get("name") == "checkpoint.capture"
    ]
    by_epoch: Dict[tuple, float] = {}
    for s in spans:
        ep = s.get("attrs", {}).get("epoch")
        if ep is None:
            continue
        key = (ep, int(s["ts"] // 10_000_000))  # epoch reuse post-restore
        by_epoch[key] = max(by_epoch.get(key, 0.0), s["dur"] / 1000.0)
    ordered = [v for _k, v in sorted(by_epoch.items())]

    def _median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2] if vals else 0.0

    third = max(1, len(ordered) // 3)
    early_ms, late_ms = _median(ordered[:third]), _median(ordered[-third:])
    capture_flat = late_ms <= 2.0 * early_ms + 2.0

    # (c) delta-bytes flatness from storage.put spans in the flight
    # recording (disk listings lose epochs GC'd after the post-restore
    # rebase). Bases are exact to identify: a chain restarts per
    # generation (the -gNNNNN path component), so each generation's
    # lowest sess epoch is its base; everything else is a delta.
    per_epoch_bytes: Dict[tuple, int] = {}
    per_epoch_ts: Dict[tuple, float] = {}
    for s in obs.recorder().snapshot():
        if s.get("name") != "storage.put":
            continue
        key = s.get("attrs", {}).get("key", "")
        if "-sess-" not in key or not key.endswith(".bin"):
            continue
        try:
            epoch = int(key.split("checkpoint-")[1].split("/")[0])
            gen = key.rsplit("-g", 1)[1].split(".")[0]
        except (IndexError, ValueError):
            continue
        ek = (gen, epoch)
        per_epoch_bytes[ek] = (
            per_epoch_bytes.get(ek, 0) + int(s["attrs"].get("bytes", 0))
        )
        ts = float(s["ts"])
        per_epoch_ts[ek] = min(per_epoch_ts.get(ek, ts), ts)
    bases = {
        (g, min(e for g2, e in per_epoch_bytes if g2 == g))
        for g, _e in per_epoch_bytes
    }
    base_bytes = sum(
        v for k, v in per_epoch_bytes.items() if k in bases
    )
    # flatness is judged on the delta byte RATE (bytes per second of
    # epoch wall time), not bytes per epoch: on a loaded host the
    # checkpoint cadence slips, so a late epoch covers more wall time —
    # and therefore more throttle-paced input rows — than an early one.
    # Raw per-epoch bytes then grow with host slowness, not with state.
    # The throttled source feeds rows at a constant rate, so a
    # delta-encoded chain uploads a ~flat byte rate while a
    # full-snapshot design's rate still grows ~10x with live state.
    rate_series = []
    for g in {g for g, _e in per_epoch_bytes}:
        eps = sorted(e for g2, e in per_epoch_bytes if g2 == g)
        for a, b in zip(eps, eps[1:]):
            dur_s = (per_epoch_ts[(g, b)] - per_epoch_ts[(g, a)]) / 1e6
            if (g, a) in bases or dur_s <= 0.01:
                continue
            rate_series.append((g, a, per_epoch_bytes[(g, a)] / dur_s))
    byte_series = [r for _g, _e, r in sorted(rate_series)]
    bthird = max(1, len(byte_series) // 3)
    early_b = _median(byte_series[:bthird])
    late_b = _median(byte_series[-bthird:])
    bytes_flat = len(byte_series) >= 6 and late_b <= 2.0 * early_b + 4096

    passed = (error is None and got == want and not plan.unfired()
              and restarts >= 1 and capture_flat and bytes_flat)
    if error is None and got != want:
        error = f"output diverged: {len(got)} rows vs {len(want)}"
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    if error is None and restarts < 1:
        error = "the SIGKILL never forced a recovery"
    if error is None and not capture_flat:
        error = (f"capture p99 grew with state: early {early_ms:.2f}ms "
                 f"-> late {late_ms:.2f}ms")
    if error is None and not bytes_flat:
        error = (f"delta byte rate grew with state: "
                 f"early {early_b:.0f} B/s -> late {late_b:.0f} B/s "
                 f"({len(byte_series)} epochs)")
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="state_bloat_session",
        seed=seed,
        passed=passed,
        rows=len(got),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        extras={
            "capture_ms_early_median": round(early_ms, 3),
            "capture_ms_late_median": round(late_ms, 3),
            "delta_bps_early_median": round(early_b, 1),
            "delta_bps_late_median": round(late_b, 1),
            "rebase_base_bytes": base_bytes,
            "epochs_measured": len(byte_series),
        },
        audit_breaches=audit_breaches,
    )


# -- kafka drill (in-memory fake broker, real connector operators) -----------


KAFKA_DRILL_SQL = """
CREATE TABLE src (
  n BIGINT
) WITH (
  connector = 'kafka', bootstrap_servers = 'fake:9092', topic = 'in',
  type = 'source', format = 'json', source.offset = 'earliest'
);
CREATE TABLE dst (
  n BIGINT
) WITH (
  connector = 'kafka', bootstrap_servers = 'fake:9092', topic = 'out',
  type = 'sink', format = 'json', sink.commit_mode = 'exactly_once'
);
INSERT INTO dst SELECT n * 10 as n FROM src;
"""


def kafka_plan(seed: int) -> FaultPlan:
    """Kill a worker mid-transaction and lose a manifest CAS: the fenced
    producer epochs + 2PC commit records must still deliver each row
    exactly once through the transactional sink."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("worker.kill", at_hits=(rng.randint(8, 14),))
    plan.add(
        "storage.cas_conflict",
        at_hits=(rng.randint(1, 2),),
        match={"key": "checkpoint-manifest"},
    )
    return plan


def run_kafka_drill(seed: int, workdir: str, n_rows: int = 120,
                    timeout: float = 90.0) -> DrillResult:
    """Drive the REAL kafka connector operators over the in-memory fake
    broker through the embedded cluster under a fault plan; assert the
    transactional sink's visible (read-committed) output is exactly-once."""
    import sys

    import arroyo_tpu.connectors.kafka as kmod

    sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
    try:
        from fake_clients import FakeKafkaBroker
    finally:
        sys.path.remove(os.path.join(REPO_ROOT, "tests"))

    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    audit_mark = _audit_mark()
    broker = FakeKafkaBroker(partitions_per_topic=2)
    for i in range(n_rows):
        broker.append("in", i % 2, None, json.dumps({"n": i}).encode(),
                      committed=True, tx_id=None)

    def visible():
        out = []
        for p in sorted(broker.topic("out")):
            for m in broker.visible("out", p):
                if m.committed:
                    out.append(json.loads(m.value())["n"])
        return sorted(out)

    plan = chaos.install(kafka_plan(seed))
    orig = kmod._load_client
    kmod._load_client = lambda: broker.make_module()
    error = None
    restarts = 0

    async def go():
        with update(
            worker={"heartbeat_interval": 0.1},
            # generous timeout: a loaded CI host must not misread an
            # event-loop stall as the injected kill
            controller={"heartbeat_timeout": 2.0},
            pipeline={"checkpointing": {"interval": 0.15}},
        ):
            c = await ControllerServer(
                EmbeddedScheduler(), max_restarts=8
            ).start()
            try:
                await c.submit_job(
                    "kafka-drill", sql=KAFKA_DRILL_SQL,
                    storage_url=os.path.join(workdir, "ck"), n_workers=2,
                    parallelism=1,
                )
                await c.wait_for_state("kafka-drill", JobState.RUNNING,
                                       timeout=30)
                # wait for the transactional sink to commit every row
                import time

                deadline = time.monotonic() + timeout
                while len(visible()) < n_rows:
                    if time.monotonic() > deadline:
                        break
                    if c.jobs["kafka-drill"].state == JobState.FAILED:
                        raise RuntimeError(
                            f"kafka drill failed: "
                            f"{c.jobs['kafka-drill'].failure}"
                        )
                    await asyncio.sleep(0.05)
                await c.stop_job("kafka-drill", "checkpoint")
                await c.wait_for_state(
                    "kafka-drill", JobState.STOPPED, JobState.FAILED,
                    timeout=60,
                )
                return c.jobs["kafka-drill"].restarts
            finally:
                await c.stop()

    try:
        os.makedirs(workdir, exist_ok=True)
        restarts = asyncio.run(go())
    except Exception as e:  # noqa: BLE001
        error = repr(e)
    finally:
        kmod._load_client = orig
        chaos.clear()

    got = visible()
    want = sorted(i * 10 for i in range(n_rows))
    passed = error is None and got == want and not plan.unfired()
    if error is None and got != want:
        dupes = len(got) - len(set(got))
        error = (
            f"kafka output not exactly-once: {len(got)} visible rows "
            f"({dupes} duplicates) vs {n_rows} produced"
        )
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="kafka_exactly_once",
        seed=seed,
        passed=passed,
        rows=len(got),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        audit_breaches=audit_breaches,
    )


# -- shared-plan drill (ISSUE 16: N tenants, one scan, one kill) -------------


SHARED_DRILL_SQL = """
CREATE TABLE impulse WITH (
  connector = 'impulse', event_rate = '$rate', message_count = '$n',
  start_time = '0', realtime = 'true', replay = 'true'
);
CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
  connector = 'single_file', path = '$out', format = 'json', type = 'sink'
);
INSERT INTO out
SELECT k, cnt FROM (
  SELECT counter % $mod as k,
         tumble(interval '100 millisecond') as w, count(*) as cnt
  FROM impulse GROUP BY 1, 2
);
"""


def shared_plan(seed: int) -> FaultPlan:
    """One worker SIGKILL mid-checkpoint cadence. Same hit window as the
    sharedplan model's counterexample serialization
    (analysis/model/sharedplan.py sp_trace_to_fault_plan): heartbeat
    ticks arrive from THREE in-process workers here (host + 2 tenants at
    0.1s ≈ 30 hits/s), so hits 8-16 land ~0.3-0.6s in — both tenants
    mounted and checkpointing, the bounded scan still mid-stream. Which
    worker dies is seed-chosen; exactly-once per tenant must hold either
    way (tenant death = restore against the retained log; host death =
    durable host resume bounded by the publication gate)."""
    rng = random.Random(int(seed))
    plan = FaultPlan(seed)
    plan.add("worker.kill", at_hits=(rng.randint(8, 16),))
    return plan


def _shared_sql(out: str, mod: int, n: int, rate: int) -> str:
    return (SHARED_DRILL_SQL
            .replace("$out", out).replace("$mod", str(mod))
            .replace("$n", str(n)).replace("$rate", str(rate)))


def run_shared_drill(seed: int, workdir: str, n_rows: int = 4000,
                     rate: int = 2000, timeout: float = 120.0,
                     plan_factory: Callable[[int], FaultPlan] = shared_plan,
                     ) -> DrillResult:
    """ISSUE 16 acceptance: two tenants whose scans fingerprint
    identically mount ONE shared host scan (`__shared/<fp>`), a worker
    is SIGKILLed mid-checkpoint, and each tenant's canonicalized output
    must be byte-identical to its own SOLO unshared fault-free run. The
    drill also requires the mount to actually engage (one host, refcount
    2, observed live) and every scheduled fault to fire. Pass a
    model-checker counterexample plan via `plan_factory`
    (tools/chaos_drill.py --shared --plan FILE) to replay the
    `leaked_barrier_across_tenants` kill schedule end-to-end."""
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()
    tenants = {"ta": 3, "tb": 5}

    # 1. fault-free SOLO references, sharing OFF: the A/B is
    # shared-vs-unshared, so the reference is each tenant owning its
    # whole data plane (replay-deterministic source => identical rows)
    want: Dict[str, List[str]] = {}
    assert chaos.installed() is None, "a fault plan is already installed"
    for tid, mod in tenants.items():
        solo_out = os.path.join(workdir, f"{tid}-solo.json")
        solo_sql = _shared_sql(solo_out, mod, n_rows, rate)
        with update(sharing={"enabled": False}):
            _run_embedded(
                solo_sql, f"shared-{tid}-solo", None, 1, 1, max_restarts=0,
                heartbeat_interval=0.1, heartbeat_timeout=30.0,
                checkpoint_interval=60.0, timeout=timeout,
            )
        want[tid] = canonicalize_output(solo_out, solo_sql, {})
        if not want[tid]:
            raise RuntimeError(
                f"shared drill: solo run for {tid} produced no output"
            )

    # 2. faulted SHARED run: both tenants on one controller, sharing ON,
    # durable host + durable tenants, kill mid-checkpoint
    fault_sqls = {
        tid: _shared_sql(os.path.join(workdir, f"{tid}-shared.json"),
                         mod, n_rows, rate)
        for tid, mod in tenants.items()
    }
    plan = chaos.install(plan_factory(seed))
    error = None
    restarts = 0
    refcount_peak = 0
    host_fp = None

    async def go():
        nonlocal refcount_peak, host_fp
        c = await ControllerServer(
            EmbeddedScheduler(), max_restarts=8
        ).start()
        try:
            for tid in tenants:
                await c.submit_job(
                    tid, sql=fault_sqls[tid],
                    storage_url=os.path.join(workdir, f"{tid}-ck"),
                    n_workers=1, parallelism=1,
                )
            # the mount must actually engage: one host, refcount 2
            import time as _time

            deadline = _time.monotonic() + 15.0
            while _time.monotonic() < deadline:
                st = c.sharing.status()
                if st:
                    host_fp = next(iter(st))
                    refcount_peak = max(refcount_peak,
                                        st[host_fp]["refcount"])
                if refcount_peak >= len(tenants):
                    break
                await asyncio.sleep(0.05)
            for tid in tenants:
                await c.wait_for_state(
                    tid, JobState.FINISHED, JobState.FAILED,
                    timeout=timeout,
                )
            total = 0
            for jid, job in c.jobs.items():
                if job.state != JobState.FINISHED and not \
                        jid.startswith("__shared/"):
                    raise RuntimeError(
                        f"shared drill job {jid} failed: {job.failure}"
                    )
                total += job.restarts
            return total
        finally:
            await c.stop()

    try:
        with update(
            sharing={"enabled": True,
                     "host_storage_url": os.path.join(workdir, "host-ck")},
            worker={"heartbeat_interval": 0.1},
            controller={"heartbeat_timeout": 1.5},
            pipeline={"checkpointing": {"interval": 0.15}},
        ):
            restarts = asyncio.run(go())
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()

    got = {
        tid: canonicalize_output(
            os.path.join(workdir, f"{tid}-shared.json"),
            fault_sqls[tid], {},
        )
        for tid in tenants
    }
    diverged = [tid for tid in tenants if got[tid] != want[tid]]
    passed = (error is None and not diverged and not plan.unfired()
              and restarts >= 1 and refcount_peak >= len(tenants))
    if error is None and diverged:
        error = "per-tenant output diverged from solo runs: " + ", ".join(
            f"{tid} ({len(got[tid])} rows vs {len(want[tid])} solo)"
            for tid in diverged
        )
    if error is None and plan.unfired():
        error = f"unfired faults: {[s.describe() for s in plan.unfired()]}"
    if error is None and restarts < 1:
        error = "the SIGKILL never forced a recovery"
    if error is None and refcount_peak < len(tenants):
        error = (f"tenants never co-mounted: peak refcount "
                 f"{refcount_peak} < {len(tenants)}")
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="shared_plan_fleet",
        seed=seed,
        passed=passed,
        rows=sum(len(v) for v in got.values()),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        extras={
            "refcount_peak": refcount_peak,
            "shared_fingerprint": host_fp,
            "tenant_rows": {tid: len(v) for tid, v in got.items()},
        },
        audit_breaches=audit_breaches,
    )

# -- hot-standby failover drill (ISSUE 17 acceptance) ------------------------


FAILOVER_DRILL_SQL = """
CREATE TABLE impulse WITH (
  connector = 'impulse', event_rate = '$rate',
  message_count = '$n', start_time = '0',
  realtime = 'true', replay = 'true'
);
CREATE TABLE out (k BIGINT UNSIGNED, start TIMESTAMP, cnt BIGINT) WITH (
  connector = 'single_file', path = '$out',
  format = 'json', type = 'sink'
);
INSERT INTO out
SELECT k, window.start as start, cnt FROM (
  SELECT counter % 4 as k, tumble(interval '500 millisecond') as window,
         count(*) as cnt
  FROM impulse GROUP BY 1, 2
);
"""


def _failover_sql(out: str, n: int, rate: int) -> str:
    return (FAILOVER_DRILL_SQL
            .replace("$out", out).replace("$n", str(n))
            .replace("$rate", str(rate)))


def run_failover_drill(seed: int, workdir: str, n_rows: int = 4000,
                       rate: int = 1500, timeout: float = 120.0,
                       plan_factory: Optional[
                           Callable[[int], FaultPlan]] = None,
                       ) -> DrillResult:
    """ISSUE 17 acceptance: SIGKILL the primary under load with a hot
    standby armed.

    Three phases over the same replay-deterministic windowed pipeline:

      1. fault-free reference with failover OFF (the cold data plane).
      2. promotion: failover ON, wait for the standby to arm AND tail at
         least one published epoch, then SIGKILL-equivalent the worker
         hosting the primary. The job must finish with >= 1 promotion,
         ZERO cold restarts, no RECOVERING transition, byte-identical
         output — and the `failover.promote` span's gap_ms (detection ->
         processing released on the promoted generation) goes into the
         drill extras against the < 500 ms acceptance bar.
      3. standby-also-dies: kill the standby's worker AND the primary's.
         Promotion must be refused (stale standby) and the job must fall
         back to a cold restore — >= 1 restart, 0 promotions, still
         byte-identical. The RECOVERING -> RUNNING wall time is recorded
         as the multi-second cold baseline the gap_ms compares against.

    With `plan_factory` (tools/chaos_drill.py --failover --plan FILE, e.g.
    the serialized `promote_while_primary_alive` counterexample), phase 2
    runs under that plan INSTEAD of the targeted kill: a heartbeat
    blackout leaves the primary alive-but-silent, the standby promotes
    over it, and the fenced zombie must not double-emit — byte-identical
    output is still the bar. Phase 3 is skipped on the replay path."""
    from .. import obs
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState
    from ..state.chain_cache import CACHE

    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()

    # 1. fault-free reference, failover off
    clean_out = os.path.join(workdir, "clean.json")
    clean_sql = _failover_sql(clean_out, n_rows, rate)
    assert chaos.installed() is None, "a fault plan is already installed"
    _run_embedded(
        clean_sql, "drill-failover-clean", None, 1, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=timeout,
    )
    want = canonicalize_output(clean_out, clean_sql, {})
    if not want:
        raise RuntimeError("failover drill: fault-free run had no output")

    async def faulted(tag: str, kill: str, plan: Optional[FaultPlan]):
        """One faulted run. `kill` targets the dynamic SIGKILL at the
        'primary' worker, 'both' (standby first, then primary), or ''
        (the installed plan drives all faults). Returns (promotions,
        restarts, events, standby_epoch_at_kill)."""
        out = os.path.join(workdir, f"{tag}.json")
        fsql = _failover_sql(out, n_rows, rate)
        c = await ControllerServer(
            EmbeddedScheduler(), max_restarts=8
        ).start()
        sb_epoch = 0
        try:
            await c.submit_job(
                "drill-failover", sql=fsql,
                storage_url=os.path.join(workdir, f"{tag}-ck"),
                n_workers=1, parallelism=1,
            )
            await c.wait_for_state("drill-failover", JobState.RUNNING,
                                   timeout=30)
            job = c.jobs["drill-failover"]
            if plan is not None:
                # counterexample replay: the model's abstract worker
                # index names no real worker id — retarget every
                # worker-scoped fault at the job's PRIMARY worker (the
                # blackout must silence the primary, with the standby
                # armed, for the promotion-over-alive-primary scenario
                # to replay). Wait for the arm first: promotion needs a
                # standby to promote.
                deadline = asyncio.get_event_loop().time() + 20.0
                while asyncio.get_event_loop().time() < deadline:
                    if c.failover._standbys.get("drill-failover"):
                        break
                    await asyncio.sleep(0.05)
                if not c.failover._standbys.get("drill-failover"):
                    raise RuntimeError("standby never armed for replay")
                wid = str(job.workers[0].worker_id)
                for spec in plan.specs:
                    if (spec.point.startswith("worker.")
                            and "worker_id" not in spec.match):
                        spec.match["worker_id"] = wid
                chaos.install(plan)
            if kill:
                # the kill target is only known once the standby armed:
                # wait for the arm AND at least one tailed epoch, then
                # install the targeted worker.kill plan mid-run
                deadline = asyncio.get_event_loop().time() + 20.0
                while asyncio.get_event_loop().time() < deadline:
                    sb = c.failover._standbys.get("drill-failover")
                    if sb is not None and sb.epoch >= 1:
                        break
                    await asyncio.sleep(0.05)
                sb = c.failover._standbys.get("drill-failover")
                if sb is None or sb.epoch < 1:
                    raise RuntimeError(
                        "standby never armed/tailed before the kill window"
                    )
                sb_epoch = sb.epoch
                kp = FaultPlan(seed)
                if kill == "both":
                    for w in sb.workers:
                        kp.add("worker.kill", at_hits=(1,),
                               match={"worker_id": str(w.worker_id)})
                for w in job.workers:
                    kp.add("worker.kill", at_hits=(1,),
                           match={"worker_id": str(w.worker_id)})
                chaos.install(kp)
            state = await c.wait_for_state(
                "drill-failover", JobState.FINISHED, JobState.FAILED,
                timeout=timeout,
            )
            if state != JobState.FINISHED:
                raise RuntimeError(
                    f"failover drill ({tag}) failed: {job.failure}"
                )
            return (job.promotions, job.restarts, list(job.events),
                    sb_epoch, canonicalize_output(out, fsql, {}))
        finally:
            chaos.clear()
            await c.stop()

    def run_phase(tag, kill, plan):
        # replay cadence note: a successful checkpoint RPC refreshes the
        # controller's liveness view (_worker_call), so a heartbeat
        # blackout only trips detection when the fan-out period exceeds
        # the heartbeat timeout — the kill phases keep the fast cadence
        # (a dead worker refuses RPCs too)
        ckpt, hb_to = (1.0, 0.4) if plan is not None else (0.25, 0.5)
        with update(
            failover={"enabled": True},
            worker={"heartbeat_interval": 0.05},
            controller={"heartbeat_timeout": hb_to},
            pipeline={"checkpointing": {"interval": ckpt}},
        ):
            return asyncio.run(faulted(tag, kill, plan))

    error = None
    promotions = restarts = 0
    gap_ms: List[float] = []
    cold_ms: List[float] = []
    fb_restarts = fb_promotions = 0
    sb_epoch = 0
    replay_plan = plan_factory(seed) if plan_factory is not None else None

    # 2. promotion phase (targeted kill, or the replayed plan)
    obs.reset()
    try:
        promotions, restarts, events, sb_epoch, got = run_phase(
            "promote", "" if replay_plan is not None else "primary",
            replay_plan,
        )
        gap_ms = sorted(
            float(s["attrs"]["gap_ms"])
            for s in obs.recorder().snapshot()
            if s.get("name") == "failover.promote"
            and "gap_ms" in s.get("attrs", {})
        )
        if got != want:
            error = (f"promote phase diverged: {len(got)} rows vs "
                     f"{len(want)} fault-free")
        elif promotions < 1:
            error = "no promotion happened"
        elif replay_plan is None and restarts:
            error = f"promotion phase took {restarts} cold restarts"
        elif replay_plan is None and any(
                e["to"] == "Recovering" for e in events):
            error = "promotion phase passed through RECOVERING"
        elif not gap_ms:
            error = "no failover.promote span carried gap_ms"
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    cache = dict(CACHE.stats())

    # 3. standby-also-dies phase: cold-restore fallback (skipped on the
    # counterexample replay path)
    if error is None and replay_plan is None:
        try:
            fb_promotions, fb_restarts, events, _sbe, got = run_phase(
                "fallback", "both", None,
            )
            t_rec = None
            for e in events:
                if e["to"] == "Recovering":
                    t_rec = e["time"]
                elif e["to"] == "Running" and t_rec is not None:
                    cold_ms.append((e["time"] - t_rec) / 1e6)
                    t_rec = None
            if got != want:
                error = (f"fallback phase diverged: {len(got)} rows vs "
                         f"{len(want)} fault-free")
            elif fb_restarts < 1:
                error = "standby-also-dies never forced a cold restore"
            elif fb_promotions:
                error = "a stale standby was promoted"
        except Exception as e:  # noqa: BLE001 - recorded in the result
            error = repr(e)

    passed = error is None
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="failover_hot_standby",
        seed=seed,
        passed=passed,
        rows=len(want),
        restarts=restarts + fb_restarts,
        fired=[],
        comparable_log=[],
        expected_log=[],
        unfired=[],
        error=error,
        extras={
            "promotions": promotions,
            "standby_epoch_at_kill": sb_epoch,
            "promote_gap_ms_max": round(gap_ms[-1], 3) if gap_ms else None,
            "cold_recover_ms": [round(g, 1) for g in sorted(cold_ms)],
            "fallback_restarts": fb_restarts,
            "replayed_plan": replay_plan is not None,
            "chain_cache_hits": cache.get("hits"),
            "chain_cache_misses": cache.get("misses"),
        },
        audit_breaches=audit_breaches,
    )


# -- follower replica drill (ISSUE 20: serving-tier death mid-tail) ----------


def run_follower_drill(seed: int, workdir: str, n_rows: int = 12000,
                       rate: int = 1500, timeout: float = 120.0,
                       ) -> DrillResult:
    """ISSUE 20 acceptance: follower read-replica death mid-tail.

    One durable replay-deterministic windowed pipeline with a follower
    tailing its checkpoint stream, read continuously through the REAL
    serve gateway for the whole run:

      1. fault-free reference with the replica tier OFF — the data
         plane's byte-identical output baseline (followers are read-only
         consumers of published state, so the bar is that their
         existence, death, and reattach change NOTHING downstream).
      2. follower phase: replica ON, wait until gateway reads route
         follower-first, then fire the `replica.kill` chaos seam — the
         follower dies abruptly mid-tail (mounts dropped, no graceful
         detach). Reads must fail over worker-ward instantly (zero
         wrong values, zero non-retriable errors), the follower must
         reattach through the full _subscribe path — re-resolving
         latest.json, never an in-memory epoch (the
         follower_serves_unpublished_epoch mutant is the shortcut this
         forbids) — and reads must come back follower-sourced. Every
         read's staleness (published epoch minus served) stays <= 1
         checkpoint interval; the sink output stays byte-identical.

    The read log's source transitions (follower -> worker -> follower),
    the kill count, and the staleness ceiling land in the drill extras."""
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    os.makedirs(workdir, exist_ok=True)
    audit_mark = _audit_mark()

    # 1. fault-free reference, replica off
    clean_out = os.path.join(workdir, "clean.json")
    clean_sql = _failover_sql(clean_out, n_rows, rate)
    assert chaos.installed() is None, "a fault plan is already installed"
    _run_embedded(
        clean_sql, "drill-follower-clean", None, 1, 1, max_restarts=0,
        heartbeat_interval=0.1, heartbeat_timeout=30.0,
        checkpoint_interval=60.0, timeout=timeout,
    )
    want = canonicalize_output(clean_out, clean_sql, {})
    if not want:
        raise RuntimeError("follower drill: fault-free run had no output")

    async def faulted():
        """Follower phase. Returns (stats, canonical output)."""
        out = os.path.join(workdir, "follower.json")
        fsql = _failover_sql(out, n_rows, rate)
        c = await ControllerServer(
            EmbeddedScheduler(), max_restarts=2
        ).start()
        stats = {"follower_reads": 0, "worker_reads": 0,
                 "staleness_max": 0, "wrong": 0, "fatal": 0,
                 "kills": 0, "reattached": False}
        try:
            await c.submit_job(
                "drill-follower", sql=fsql,
                storage_url=os.path.join(workdir, "follower-ck"),
                n_workers=1, parallelism=1,
            )
            await c.wait_for_state("drill-follower", JobState.RUNNING,
                                   timeout=30)

            async def read_table():
                tabs = await c.serve.tables("drill-follower")
                for name, info in tabs.items():
                    if info.get("kind") == "window":
                        return name
                return None

            loop = asyncio.get_event_loop()
            table = None
            deadline = loop.time() + 30.0
            while table is None and loop.time() < deadline:
                table = await read_table()
                if table is None:
                    await asyncio.sleep(0.1)
            if table is None:
                raise RuntimeError("no serve table ever listed")

            async def read_once():
                """One 4-key gateway read; folds into stats, returns
                the response's source ('' on a non-200 response)."""
                resp = await c.serve.read("drill-follower", table,
                                          [0, 1, 2, 3])
                if resp.get("status") != 200:
                    if not resp.get("retriable", True):
                        stats["fatal"] += 1
                    return ""
                src = resp.get("source", "")
                key = {"follower": "follower_reads",
                       "worker": "worker_reads"}.get(src)
                if key:
                    stats[key] += 1
                stats["staleness_max"] = max(
                    stats["staleness_max"], int(resp.get("staleness", 0)))
                for r in resp.get("results", []):
                    v = r.get("value") or {}
                    cnt = next((x for f, x in v.items()
                                if f.startswith("__agg_out")
                                or f == "cnt"), None)
                    if r.get("found") and cnt is not None and cnt > rate:
                        stats["wrong"] += 1  # > 1 s of events in 500 ms
                return src

            async def wait_source(srcname: str, secs: float) -> bool:
                end = loop.time() + secs
                while loop.time() < end:
                    if await read_once() == srcname:
                        return True
                    await asyncio.sleep(0.05)
                return False

            # (a) reads go follower-first once the mount catches up
            if not await wait_source("follower", 30.0):
                raise RuntimeError(
                    f"reads never follower-routed: {c.replicas.status()}")
            # (b) abrupt follower death mid-tail via the chaos seam
            kp = FaultPlan(seed)
            kp.add("replica.kill", at_hits=(1,))
            chaos.install(kp)
            deadline = loop.time() + 20.0
            while c.replicas.kills < 1 and loop.time() < deadline:
                await read_once()
                await asyncio.sleep(0.05)
            chaos.clear()
            if c.replicas.kills < 1:
                raise RuntimeError("replica.kill never fired")
            stats["kills"] = c.replicas.kills
            # (c) worker-ward fallback serves while the follower is down
            if not await wait_source("worker", 10.0):
                raise RuntimeError(
                    "no worker-ward fallback read after the kill")
            # (d) reattach: back through _subscribe off latest.json
            stats["reattached"] = await wait_source("follower", 30.0)
            if not stats["reattached"]:
                raise RuntimeError(
                    f"follower never reattached: {c.replicas.status()}")
            # keep reading to the finish line: staleness and value
            # checks must hold for the job's whole life
            while not c.jobs["drill-follower"].state.is_terminal():
                await read_once()
                await asyncio.sleep(0.1)
            state = c.jobs["drill-follower"].state
            if state != JobState.FINISHED:
                raise RuntimeError(
                    f"follower drill job failed: "
                    f"{c.jobs['drill-follower'].failure}")
            return stats, canonicalize_output(out, fsql, {})
        finally:
            chaos.clear()
            await c.stop()

    error = None
    stats: dict = {}
    got: list = []
    try:
        with update(
            replica={"followers": 1, "reattach_backoff": 0.5},
            worker={"heartbeat_interval": 0.05},
            controller={"heartbeat_timeout": 2.0},
            pipeline={"checkpointing": {"interval": 0.25}},
        ):
            stats, got = asyncio.run(faulted())
        if got != want:
            error = (f"follower phase diverged: {len(got)} rows vs "
                     f"{len(want)} fault-free")
        elif stats["wrong"]:
            error = f"{stats['wrong']} wrong values served"
        elif stats["fatal"]:
            error = f"{stats['fatal']} non-retriable read errors"
        elif stats["staleness_max"] > 1:
            error = (f"staleness {stats['staleness_max']} epochs exceeds "
                     "one checkpoint interval")
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)

    passed = error is None
    passed, error, audit_breaches = _audit_verdict(audit_mark, passed, error)
    return DrillResult(
        query="follower_replica_kill",
        seed=seed,
        passed=passed,
        rows=len(want),
        restarts=0,
        fired=[],
        comparable_log=[],
        expected_log=[],
        unfired=[],
        error=error,
        extras=stats or None,
        audit_breaches=audit_breaches,
    )


# -- event-loop starvation drill (ISSUE 18: the double-emit watch item) ------


def starvation_plan(seed: int) -> FaultPlan:
    """Blocking `runner.stall` hits, tenant-scoped to the victim job: a
    CPU-bound UDF that never yields wedges the WHOLE shared event loop
    (params.block) on each of the victim's first 12 input items, while
    the squeezed heartbeat/checkpoint cadences keep ticking against it."""
    plan = FaultPlan(seed)
    plan.add("runner.stall", at_hits=tuple(range(1, 13)), max_fires=12,
             match={"job": "starve-victim"},
             params={"delay": 0.15, "block": True})
    return plan


def run_starvation_drill(seed: int, workdir: str, n_rows: int = 3000,
                         rate: int = 1500, timeout: float = 120.0,
                         plan_factory: Callable[[int], FaultPlan]
                         = starvation_plan) -> DrillResult:
    """ROADMAP watch item: can extreme event-loop lag double-emit a
    window without a restart? (Observed once when a rescale drill ran
    concurrently with a full-tree lint; never reproduced standalone.)

    Two tenants run the replay-deterministic 500 ms tumbling aggregate
    on one embedded cluster. The victim's input loop takes repeated
    BLOCKING stalls (`runner.stall` params.block — a UDF that never
    yields, starving heartbeat loops and the co-resident bystander),
    heartbeat and checkpoint cadences are squeezed tight around the
    stall width, and `max_restarts=0` so any heartbeat false-positive
    fails the run outright. The interleaving sanitizer
    (analysis/races/sanitizer.py) records every access to
    `@shared_state` fields live. The drill passes iff both tenants'
    outputs are byte-identical to their fault-free references, no
    (key, window) pair is emitted twice, restarts == 0, every scheduled
    stall fired, and the sanitizer saw zero conflicts. On failure the
    access log and a Perfetto trace land in the workdir (CI uploads
    them)."""
    from ..analysis.races import sanitizer
    from ..config import update
    from ..controller.controller import ControllerServer
    from ..controller.scheduler import EmbeddedScheduler
    from ..controller.state_machine import JobState

    os.makedirs(workdir, exist_ok=True)
    # explicit audit-silence assertion (ISSUE 19): this drill IS the
    # double-emit watch item's resurface detector — if extreme loop lag
    # ever re-emits a window, the conservation ledger flags the exact
    # (edge, epoch) even when the sink output happens to dedupe
    audit_mark = _audit_mark()
    tenants = ("starve-victim", "starve-bystander")

    def tenant_sql(tag: str, out: str) -> str:
        return (FAILOVER_DRILL_SQL
                .replace("$out", out)
                .replace("$n", str(n_rows))
                .replace("$rate", str(rate)))

    # 1. fault-free references (stall off, loose cadences)
    assert chaos.installed() is None, "a fault plan is already installed"
    want: Dict[str, List[str]] = {}
    for tid in tenants:
        ref_out = os.path.join(workdir, f"{tid}-ref.json")
        _run_embedded(
            tenant_sql(tid, ref_out), f"{tid}-ref", None, 1, 1,
            max_restarts=0, heartbeat_interval=0.1, heartbeat_timeout=30.0,
            checkpoint_interval=60.0, timeout=timeout,
        )
        want[tid] = canonicalize_output(ref_out, "", {})
        if not want[tid]:
            raise RuntimeError(
                f"starvation drill: reference for {tid} had no output"
            )

    # 2. faulted run: both tenants, blocking stalls on the victim,
    # heartbeat/checkpoint cadences squeezed around the stall width
    fault_outs = {tid: os.path.join(workdir, f"{tid}-stall.json")
                  for tid in tenants}
    plan = chaos.install(plan_factory(seed))
    sanitizer.reset()
    sanitizer.enable()
    error = None
    restarts = 0

    async def go():
        c = await ControllerServer(
            EmbeddedScheduler(), max_restarts=0
        ).start()
        try:
            for tid in tenants:
                await c.submit_job(
                    tid, sql=tenant_sql(tid, fault_outs[tid]),
                    storage_url=os.path.join(workdir, f"{tid}-ck"),
                    n_workers=1, parallelism=1,
                )
            total = 0
            for tid in tenants:
                state = await c.wait_for_state(
                    tid, JobState.FINISHED, JobState.FAILED, timeout=timeout,
                )
                job = c.jobs[tid]
                if state != JobState.FINISHED:
                    raise RuntimeError(
                        f"starvation drill job {tid} failed: {job.failure}"
                    )
                total += job.restarts
            return total
        finally:
            await c.stop()

    try:
        with update(
            worker={"heartbeat_interval": 0.05},
            controller={"heartbeat_timeout": 1.0},
            pipeline={"checkpointing": {"interval": 0.25},
                      "source_batch_size": 64},
        ):
            restarts = asyncio.run(go())
    except Exception as e:  # noqa: BLE001 - recorded in the result
        error = repr(e)
    finally:
        chaos.clear()
        sanitizer.disable()

    conflicts = sanitizer.conflicts()
    race_report = sanitizer.report()
    got = {tid: canonicalize_output(fault_outs[tid], "", {})
           for tid in tenants}
    dup: Dict[str, List] = {}
    for tid in tenants:
        rows = read_rows(fault_outs[tid])
        seen: Dict[tuple, int] = {}
        for r in rows:
            seen[(r.get("k"), r.get("start"))] = \
                seen.get((r.get("k"), r.get("start")), 0) + 1
        dup[tid] = sorted(k for k, n in seen.items() if n > 1)
    diverged = [tid for tid in tenants if got[tid] != want[tid]]

    if error is None and any(dup.values()):
        error = ("a window was emitted twice without a restart: " +
                 "; ".join(f"{tid}: {dup[tid]}" for tid in tenants
                           if dup[tid]))
    if error is None and diverged:
        error = "output diverged from fault-free references: " + ", ".join(
            f"{tid} ({len(got[tid])} rows vs {len(want[tid])})"
            for tid in diverged
        )
    if error is None and restarts:
        error = f"squeezed heartbeats tripped {restarts} restart(s)"
    if error is None and plan.unfired():
        error = f"unfired stalls: {[s.describe() for s in plan.unfired()]}"
    if error is None and conflicts:
        error = (f"sanitizer flagged {len(conflicts)} interleaving "
                 f"conflict(s): {conflicts[0]['detail']}")
    passed, error, audit_breaches = _audit_verdict(audit_mark,
                                                    error is None, error)
    if not passed:
        # CI failure artifacts: the full access log + a Perfetto trace
        sanitizer.dump(os.path.join(workdir, "race_access_log.json"))
        sanitizer.dump_trace(os.path.join(workdir, "race_trace.json"))
    return DrillResult(
        query="starvation_double_emit",
        seed=seed,
        passed=passed,
        rows=sum(len(v) for v in got.values()),
        restarts=restarts,
        fired=plan.fired_log(),
        comparable_log=plan.comparable_log(),
        expected_log=plan.expected_log(),
        unfired=[s.describe() for s in plan.unfired()],
        error=error,
        extras={
            "duplicate_windows": {tid: [list(k) for k in v]
                                  for tid, v in dup.items()},
            "tenant_rows": {tid: len(v) for tid, v in got.items()},
            "sanitizer": {
                "accesses": race_report["accesses"],
                "epochs": race_report["epochs"],
                "conflicts": conflicts,
            },
        },
        audit_breaches=audit_breaches,
    )
