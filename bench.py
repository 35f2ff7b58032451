#!/usr/bin/env python
"""Benchmark: Nexmark q1/q5/q7/q8 (+ the qu updating aggregate)
events/sec through the full engine.

The headline metric is q5 (hop-window COUNT per auction joined with the
per-window MAX — the reference's CI-covered nexmark_q5.sql shape), run
twice:
  * CPU baseline: window aggregation on the numpy host backend
  * device path:  window aggregation on the JAX backend (TPU when present)
q1 (stateless currency projection), q7 (per-window highest bid join),
q8 (person x auction same-window join) and qu (non-windowed GROUP BY,
the retraction-emitting updating path) run once as side metrics in the
SAME single json line, along with the mesh-path measurement
(q5_mesh{N}_eps + padding stats) and single-process + distributed
realtime latency percentiles.

Each measurement runs in its own subprocess, one after another, and
this parent never imports jax: an accelerator belongs to one process at
a time, so the parent must not hold it and a child must have exited
before the next starts (subprocess.run waits). A headline child that
fails is an `error` field and a non-zero exit — no number is
substituted for it.
"""

import argparse
import json
import os
import subprocess
import sys

# Measurement era of this harness. Bump whenever the bench host class,
# event counts, query set, or harness methodology changes in a way that
# makes old eps numbers incomparable with new ones — bench_compare.py
# refuses to gate a current run against a baseline stamped with a
# different era (ISSUE 17: pre-era baselines silently trended across
# harness changes instead of failing loudly).
PIN_ERA = "r2-shared-1core"

DDL = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark',
  event_rate = '{rate}',
  message_count = '{events}',
  start_time = '0'
);
"""

Q5 = DDL + """
SELECT AuctionBids.auction, AuctionBids.num
FROM (
  SELECT bid.auction as auction, count(*) AS num,
         hop(interval '2 second', interval '10 second') as window
  FROM nexmark WHERE bid IS NOT NULL
  GROUP BY 1, window
) AS AuctionBids
JOIN (
  SELECT max(CountBids.num) AS maxn, CountBids.window
  FROM (
    SELECT bid.auction as auction, count(*) AS num,
           hop(interval '2 second', interval '10 second') as window
    FROM nexmark WHERE bid IS NOT NULL
    GROUP BY 1, window
  ) AS CountBids
  GROUP BY CountBids.window
) AS MaxBids
ON AuctionBids.window = MaxBids.window
   AND AuctionBids.num >= MaxBids.maxn;
"""

# q1-shaped stateless chain (ISSUE 14): the currency conversion plus a
# rounding normalization stage — filter -> project -> project -> sink
# cast, which the planner chains into ONE task and the segment fusion
# pass compiles into ONE dispatch per batch (4 per batch unfused). The
# SEGSTATS line reports dispatches/batches from the arroyo_segment_*
# counters; the nightly A/B child re-runs this with
# ARROYO__ENGINE__SEGMENT_FUSION=0.
Q1 = DDL + """
CREATE TABLE sink (
  auction BIGINT, price_eur BIGINT, bidder BIGINT
) WITH (connector = 'blackhole', type = 'sink');
INSERT INTO sink
SELECT auction, price_eur, bidder FROM (
  SELECT auction, price_eur - price_eur % 10 AS price_eur, bidder FROM (
    SELECT bid.auction as auction, bid.price * 100 / 121 as price_eur,
           bid.bidder as bidder
    FROM nexmark WHERE bid IS NOT NULL
  )
);
"""

Q7 = DDL + """
SELECT W.auction, W.price, W.bidder FROM (
  SELECT bid.auction as auction, bid.price as price, bid.bidder as bidder,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE bid IS NOT NULL GROUP BY 1, 2, 3, w
) AS W JOIN (
  SELECT max(bid.price) as maxprice, tumble(interval '10 second') as w
  FROM nexmark WHERE bid IS NOT NULL GROUP BY w
) AS M ON W.w = M.w AND W.price = M.maxprice;
"""

Q8 = DDL + """
SELECT P.id, P.name FROM (
  SELECT person.id as id, person.name as name,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE person IS NOT NULL GROUP BY 1, 2, w
) AS P JOIN (
  SELECT auction.seller as seller, tumble(interval '10 second') as w,
         count(*) as c2
  FROM nexmark WHERE auction IS NOT NULL GROUP BY 1, w
) AS A ON P.id = A.seller AND P.w = A.w;
"""

# updating (non-windowed) aggregate with retraction emission: the
# engine's debezium-style path, measured per round since round 4
QU = DDL + """
CREATE TABLE sink (a BIGINT, c BIGINT, s BIGINT)
WITH (connector = 'blackhole', type = 'sink');
INSERT INTO sink
SELECT bid.auction % 1000 AS a, count(*) AS c, sum(bid.price) AS s
FROM nexmark WHERE bid IS NOT NULL GROUP BY 1;
"""

# session windows: per-bidder gap merges — the imperative-bookkeeping
# path (SessionWindowOperator), measured per round since round 5. The
# bidder space is bounded (% 500) so sessions keep extending and the
# per-segment merge/extend machinery is what gets measured.
QS = DDL + """
CREATE TABLE sink (b BIGINT, c BIGINT)
WITH (connector = 'blackhole', type = 'sink');
INSERT INTO sink
SELECT bid.bidder % 500 AS b, count(*) AS c
FROM nexmark WHERE bid IS NOT NULL
GROUP BY 1, session(interval '500 millisecond');
"""

QUERIES = {"q1": Q1, "q5": Q5, "q7": Q7, "q8": Q8, "qu": QU, "qs": QS}


def force_backend(plan, backend: str) -> None:
    """Route every backend-capable operator in the plan onto `backend`:
    anything already carrying a backend knob plus the window/updating
    aggregates."""
    for node in plan.graph.nodes.values():
        for op in node.chain:
            if "backend" in op.config or op.operator.value.endswith(
                    "aggregate"):
                op.config["backend"] = backend


def child(events: int, backend: str, query: str = "q5",
          mesh_devices: int = 0, force_device_join: bool = False) -> None:
    """Run one nexmark query; print 'RESULT <events/sec> <rows>'. With
    mesh_devices=N the window aggregates run on the N-device mesh
    execution path (ShardedAccumulator + in-step all_to_all) and a
    'MESHSTATS <rows_sent> <rows_padded> <dispatches> <updates>' line
    reports the exchange's padding overhead and the micro-batching
    amortization (device steps per engine update call)."""
    import asyncio
    import time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from arroyo_tpu.config import config
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    config().tpu.enabled = backend == "jax"
    config().pipeline.source_batch_size = 8192
    # dense loop-lag sampling: bench children run well under a minute, and
    # a p99 over a handful of 250ms probes would be pure noise
    config().obs.loop_lag_interval = 0.05
    if mesh_devices:
        config().tpu.mesh_devices = mesh_devices
    if force_device_join:
        # measure the jitted join probe's cost model off the TPU
        # (jax-CPU): the device tier, accelerator waived
        config().tpu.enabled = True
        config().tpu.require_accelerator = False
    if backend == "jax":
        # bench-only tuning (chip_smoke.py runs the defaults): keep the
        # XLA program count flat — every (bucket, capacity) pair
        # specializes update/gather/reset. One batch bucket + one
        # emission bucket + pre-sized capacity => ~6-8 programs total.
        config().tpu.shape_buckets = (8192, 65536)
        config().tpu.initial_capacity = 1 << 18
        # v5e-native narrow accumulators (counts stay exact; q5 is
        # count/max-shaped so no overflow risk at bench scales)
        config().tpu.use_32bit_accumulators = True
    # ~60s of event time so hop windows fire repeatedly mid-run
    rate = max(events // 60, 1)
    results = []
    plan = plan_query(
        QUERIES[query].format(rate=rate, events=events),
        preview_results=results,
    )
    force_backend(plan, backend)

    from arroyo_tpu.obs import attribution

    async def go():
        # fleet observatory: the accounting pump's loop-lag sampler runs
        # exactly as it would on a worker, so the bench line carries a
        # loop_lag_ms_p99 the nightly gate can pin
        attribution.ensure_pump()
        try:
            eng = Engine(plan.graph).start()
            await eng.join(600)
        finally:
            attribution.release_pump()

    t0 = time.monotonic()
    asyncio.run(go())
    dt = time.monotonic() - t0
    if mesh_devices:
        from arroyo_tpu.parallel.sharded_state import MESH_STATS

        print(f"MESHSTATS {MESH_STATS['rows_sent']} "
              f"{MESH_STATS['rows_padded']} "
              f"{MESH_STATS['dispatches']} "
              f"{MESH_STATS['updates']} "
              f"{MESH_STATS['flushes_elided']} "
              f"{MESH_STATS['rows_combined']}", flush=True)
    # device-tier observatory: in-process XLA compile count + wall time,
    # so the parent can report compile cost separately from steady-state
    # throughput (a numpy child legitimately reports 0 0)
    from arroyo_tpu.obs import device as obs_device

    progs = obs_device.summary()["programs"]
    print(f"COMPILES {sum(p.get('compiles', 0) for p in progs.values())} "
          f"{sum(p.get('compile_s_total', 0.0) for p in progs.values()):.3f}",
          flush=True)
    # fused segment runtime (ISSUE 14): stateless-chain dispatch count vs
    # batches entering planned runs — 'SEGSTATS <dispatches> <batches>
    # <max fused ops>' feeds dispatches_per_batch; with fusion off the
    # same counters carry the per-operator dispatches the run pays
    from arroyo_tpu.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    seg_disp = sum(
        v for _l, v in snap.get("arroyo_segment_dispatches_total", [])
    )
    seg_batches = sum(
        v for _l, v in snap.get("arroyo_segment_batches_total", [])
    )
    seg_ops = max(
        (v for _l, v in snap.get("arroyo_segment_fused_ops", [])),
        default=0,
    )
    print(f"SEGSTATS {int(seg_disp)} {int(seg_batches)} {int(seg_ops)}",
          flush=True)
    # per-segment ledger artifact (nightly CI uploads it on regression):
    # the device observatory's per-segment dispatch stats + the raw
    # segment counters of THIS child
    ledger_path = os.environ.get("ARROYO_SEGMENT_LEDGER")
    if ledger_path:
        from arroyo_tpu.obs import device as obs_device

        with open(ledger_path, "w") as f:
            json.dump({
                "query": query,
                "segments": obs_device.summary()["segments"],
                "seg_dispatches": int(seg_disp),
                "seg_batches": int(seg_batches),
                "recompiles": obs_device.summary()["recompiles"],
            }, f, indent=1)
    lags = sorted(attribution.ACCOUNTING.lag_samples)
    if lags:
        p99 = lags[min(len(lags) - 1, int(0.99 * len(lags)))]
        print(f"LOOPLAG {1e3 * p99:.3f} {len(lags)}", flush=True)
    print(f"RESULT {events / dt:.1f} {len(results)} {dt:.2f}", flush=True)


def state_child(events: int) -> None:
    """State-at-scale scenario (ISSUE 8): session windows over the
    nexmark bid stream keyed by auction id — the key space grows all
    run, so live session state grows while per-epoch dirty state stays
    ~constant. A checkpoint cadence runs concurrently against local
    storage; prints 'STATECK <capture_ms_p99> <bytes_per_epoch> <epochs>'
    where capture_ms_p99 comes from the checkpoint-phase histogram and
    bytes_per_epoch from the flight recorder's storage.put spans (total
    uploaded data bytes / epochs, bases included — the amortized upload
    cost the incremental snapshots + rebase policy are supposed to keep
    flat as state grows)."""
    import asyncio
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from arroyo_tpu import obs
    from arroyo_tpu.config import config
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    config().tpu.enabled = False
    config().pipeline.source_batch_size = 8192
    rate = max(events // 60, 1)
    sql = DDL.format(rate=rate, events=events) + """
    CREATE TABLE sink (a BIGINT, c BIGINT)
    WITH (connector = 'blackhole', type = 'sink');
    INSERT INTO sink
    SELECT bid.auction AS a, count(*) AS c
    FROM nexmark WHERE bid IS NOT NULL
    GROUP BY 1, session(interval '1 hour');
    """
    plan = plan_query(sql)
    force_backend(plan, "numpy")
    storage = tempfile.mkdtemp(prefix="bench-state-ck-")
    obs.recorder().clear()
    epochs = 0

    async def go():
        nonlocal epochs
        eng = Engine(plan.graph, job_id="state-bench",
                     storage_url=storage).start()
        done = asyncio.ensure_future(eng.join(600))
        while not done.done():
            await asyncio.sleep(0.1)
            if done.done():
                break
            try:
                await eng.checkpoint_and_wait()
                epochs += 1
            except Exception:  # noqa: BLE001 - racing stream end
                break
        await done

    asyncio.run(go())
    import numpy as np

    # exact capture durations from the flight recorder's span buffer —
    # the checkpoint-phase histogram's bucket-interpolated p99 snaps to
    # bucket edges (9.8ms vs 24.6ms for a one-bucket drift), far too
    # coarse to gate on
    caps = [
        s["dur"] / 1000.0 for s in obs.recorder().snapshot()
        if s.get("name") == "checkpoint.capture"
    ]
    p99_ms = float(np.percentile(np.asarray(caps), 99)) if caps else 0.0
    data_bytes = sum(
        int(s["attrs"].get("bytes", 0))
        for s in obs.recorder().snapshot()
        if s.get("name") == "storage.put"
        and "/data/" in s.get("attrs", {}).get("key", "")
    )
    per_epoch = data_bytes // max(1, epochs)
    print(f"STATECK {p99_ms:.2f} {per_epoch} {epochs}", flush=True)


def latency_child(rate: int, seconds: float, backend: str) -> None:
    """Run q5 against a REALTIME source and measure end-to-end latency:
    wall-clock arrival at the sink minus the window-end event time each
    result row became emittable. Prints 'LATENCY <p50_ms> <p99_ms> <rows>'."""
    import asyncio
    import time

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from arroyo_tpu.config import config
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    config().tpu.enabled = backend == "jax"
    events = int(rate * seconds)
    start_ns = time.time_ns()
    sql = QUERIES["q5"].format(rate=rate, events=events)
    if "start_time = '0'" not in sql:  # not assert: stripped under -O
        raise ValueError("latency bench: DDL shape changed")
    sql = sql.replace(
        "start_time = '0'",
        f"start_time = '{start_ns}', realtime = 'true'",
    )
    lat_ms = []

    class LatencySink(list):
        # the vec sink delivers rows via extend()
        def extend(self, rows):
            now = time.time_ns()
            for row in rows:
                lat_ms.append((now - row["_timestamp"].value) / 1e6)

    plan = plan_query(sql, preview_results=LatencySink())

    async def go():
        eng = Engine(plan.graph).start()
        await eng.join(seconds * 3 + 120)

    try:
        asyncio.run(go())
    finally:
        # report whatever was measured even if the engine raised. The
        # end-of-stream flush emits not-yet-complete windows whose end
        # lies in the future (negative "latency"); only steady-state
        # emissions count.
        arr = np.asarray(lat_ms)
        arr = arr[arr > 0]
        if len(arr):
            print(f"LATENCY {np.percentile(arr, 50):.1f} "
                  f"{np.percentile(arr, 99):.1f} {len(arr)}", flush=True)
        else:
            print("LATENCY nan nan 0", flush=True)


def latency_distributed(rate: int, seconds: float,
                        workers: int = 2, parallelism: int = 2):
    """Realtime q5 with source and sink in SEPARATE worker processes over
    the TCP data plane (`python -m arroyo_tpu run --scheduler process`):
    the deployment the reference's network_manager actually serves. The
    sink is the latency_file connector (per-row arrival vs window-end
    event time, flushed per batch); returns (p50_ms, p99_ms, rows) or
    None. VERDICT r3 item 6."""
    import tempfile
    import time

    events = int(rate * seconds)
    with tempfile.TemporaryDirectory() as td:
        lat_path = os.path.join(td, "lat.txt")
        sql = QUERIES["q5"].format(rate=rate, events=events)
        # no explicit start_time: the source anchors event time at its
        # OWN start, so multi-second distributed startup (process spawn,
        # plan compile) doesn't masquerade as window latency
        if "start_time = '0'" not in sql:  # not assert: stripped under -O
            raise ValueError("latency bench: DDL shape changed")
        sql = sql.replace("start_time = '0'", "realtime = 'true'")
        sink_ddl = (
            "CREATE TABLE latsink (auction BIGINT, num BIGINT) WITH ("
            f"connector = 'latency_file', path = '{lat_path}', "
            "type = 'sink');\n"
        )
        if "SELECT AuctionBids.auction" not in sql:
            raise ValueError("latency bench: q5 SELECT shape changed")
        sql = sql.replace(
            "SELECT AuctionBids.auction",
            sink_ddl + "INSERT INTO latsink SELECT AuctionBids.auction",
            1,
        )
        qfile = os.path.join(td, "q.sql")
        with open(qfile, "w") as f:
            f.write(sql)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PYTHONPATH", None)
        here = os.path.dirname(os.path.abspath(__file__))
        try:
            out = subprocess.run(
                [sys.executable, "-m", "arroyo_tpu", "run", qfile,
                 "--parallelism", str(parallelism),
                 "--workers", str(workers), "--scheduler", "process"],
                cwd=here, env=env, capture_output=True, text=True,
                timeout=seconds * 3 + 240,
            )
        except subprocess.TimeoutExpired:
            sys.stderr.write("distributed latency run timed out\n")
            return None
        if "job finished" not in out.stdout:
            sys.stderr.write(out.stdout[-1000:] + out.stderr[-2000:] + "\n")
            return None
        import numpy as np

        lats = []
        try:
            with open(lat_path) as f:
                for line in f:
                    # parallel sink subtasks append to one file: a torn
                    # line must not void the whole measurement
                    try:
                        arrival, ts = line.split()
                        ms = (int(arrival) - int(ts)) / 1e6
                    except ValueError:
                        continue
                    if ms > 0:  # end-of-stream flush emits future windows
                        lats.append(ms)
        except OSError:
            return None
        if not lats:
            return None
        arr = np.asarray(lats)
        return (float(np.percentile(arr, 50)),
                float(np.percentile(arr, 99)), len(arr))


def contention_probe(spins: int = 5):
    """Detect a contended core before measuring: time a fixed single-core
    numpy spin `spins` times (a quiet box repeats it at ~equal cost; a
    stolen core shows up as spread between the fastest and slowest spin)
    and read the 1-minute loadavg per core. Returns (contended, details)
    — the caller retries or stamps `contended: true` into the bench JSON
    (VERDICT r5 item 5: ±20% driver-run dispersion with no marker)."""
    import time

    import numpy as np

    a = np.arange(100_000, dtype=np.float64)
    times = []
    for _ in range(max(2, spins)):
        t0 = time.perf_counter()
        for _ in range(40):
            float((a * 1.0000001 + 0.5).sum())
        times.append(time.perf_counter() - t0)
    spread = max(times) / max(min(times), 1e-9)
    try:
        load = os.getloadavg()[0] / max(os.cpu_count() or 1, 1)
    except OSError:  # platform without getloadavg
        load = 0.0
    contended = spread > 1.25 or load > 1.5
    return contended, {
        "cal_spin_spread": round(spread, 3),
        "cal_loadavg_per_core": round(load, 2),
    }


def run_median(events: int, backend: str, timeout: float, env=None,
               query: str = "q5", mesh_devices: int = 0,
               force_device_join: bool = False, n: int = 3,
               max_extra: int = 2):
    """Median-of-n child runs with dispersion (VERDICT r4 item 5: the
    single-core bench host shows ±15%+ run-to-run variance, so a single
    shot can't support round-over-round deltas). When the spread of the
    initial n runs exceeds 12%, up to `max_extra` additional runs are
    taken and the reported median/spread come from the tightest
    contiguous window of n sorted runs (a transient contention spike
    shouldn't define the round's headline; every raw run value is still
    published in eps_runs). Returns the median run's dict with eps_runs
    (sorted, all runs) and eps_spread_pct added; None if every run
    failed.

    An explicit WARMUP run precedes the measured runs and is excluded
    from eps_runs/median: the first child pays XLA compiles (persistent
    cache cold), import costs and OS cache warming — BENCH_r05 measured
    a 21.4% value_spread_pct with q7's first run at 373k vs 611k steady,
    pure warmup pollution. The warmup's throughput and its in-process
    compile seconds are reported separately (warmup_eps / compile_s) so
    the compile cost stays visible instead of polluting the spread."""

    def shot():
        return run_child(events, backend, timeout, env=env, query=query,
                         mesh_devices=mesh_devices,
                         force_device_join=force_device_join)

    warmup = shot() if n > 1 else None
    runs = [r for r in (shot() for _ in range(max(1, n))) if r is not None]
    if not runs:
        if warmup is None:
            return None
        # every steady run failed but the warmup succeeded: report it
        # (marked) rather than voiding the metric
        warmup["eps_runs"] = [round(warmup["eps"], 1)]
        warmup["eps_spread_pct"] = 0.0
        warmup["warmup_only"] = True
        return warmup

    def window(rs):
        # tightest contiguous window of up to n sorted runs; lower
        # median within it (an even survivor count must not report the
        # BEST case in exactly the flaky scenarios this guards against)
        rs.sort(key=lambda r: r["eps"])
        w = min(n, len(rs))
        lo = min(
            range(len(rs) - w + 1),
            key=lambda i: rs[i + w - 1]["eps"] - rs[i]["eps"],
        )
        med = rs[lo + (w - 1) // 2]
        spread = 100.0 * (rs[lo + w - 1]["eps"] - rs[lo]["eps"]) / max(
            med["eps"], 1e-9
        )
        return med, spread

    med, spread = window(runs)
    extra = 0
    while spread > 12.0 and extra < max_extra and n > 1:
        r = shot()
        extra += 1
        if r is not None:
            runs.append(r)
            med, spread = window(runs)
    med["eps_runs"] = [round(r["eps"], 1) for r in runs]
    med["eps_spread_pct"] = round(spread, 1)
    if warmup is not None:
        med["warmup_eps"] = round(warmup["eps"], 1)
        # compile cost of the cold path (the warmup child's in-process
        # XLA compile seconds); steady children re-trace against the
        # warmed persistent cache
        if "compile_s" in warmup:
            med["compile_s"] = warmup["compile_s"]
            med["compiles"] = warmup.get("compiles", 0)
    return med


def run_child(events: int, backend: str, timeout: float, env=None,
              query: str = "q5", mesh_devices: int = 0,
              force_device_join: bool = False):
    cmd = [sys.executable, os.path.abspath(__file__), "--child", backend,
           "--events", str(events), "--query", query]
    if mesh_devices:
        cmd += ["--mesh-devices", str(mesh_devices)]
    if force_device_join:
        cmd += ["--force-device-join"]
    try:
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, env=env
        )
    except subprocess.TimeoutExpired:
        return None
    result = None
    stats = None
    compiles = None
    segstats = None
    loop_lag = None
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            parts = line.split()
            result = {"eps": float(parts[1]), "rows": int(parts[2]),
                      "secs": float(parts[3])}
        elif line.startswith("MESHSTATS "):
            parts = line.split()
            stats = tuple(int(p) for p in parts[1:])
        elif line.startswith("COMPILES "):
            parts = line.split()
            compiles = (int(parts[1]), float(parts[2]))
        elif line.startswith("SEGSTATS "):
            parts = line.split()
            segstats = tuple(int(p) for p in parts[1:])
        elif line.startswith("LOOPLAG "):
            parts = line.split()
            loop_lag = (float(parts[1]), int(parts[2]))
    if result is None:
        sys.stderr.write(out.stderr[-2000:] + "\n")
        return None
    if stats is not None:
        result["rows_sent"], result["rows_padded"] = stats[0], stats[1]
        if len(stats) >= 4:
            result["dispatches"], result["updates"] = stats[2], stats[3]
        if len(stats) >= 5:
            result["flushes_elided"] = stats[4]
        if len(stats) >= 6:
            result["rows_combined"] = stats[5]
    if compiles is not None:
        result["compiles"], result["compile_s"] = compiles
    if segstats is not None and len(segstats) >= 2 and segstats[1]:
        result["seg_dispatches"], result["seg_batches"] = segstats[:2]
        result["dispatches_per_batch"] = round(
            segstats[0] / segstats[1], 3
        )
        if len(segstats) >= 3:
            result["seg_fused_ops"] = segstats[2]
    if loop_lag is not None:
        result["loop_lag_ms_p99"], result["loop_lag_samples"] = loop_lag
    return result


def fleet_main(args):
    """Run tools/fleet_harness.py as a child (fresh interpreter: the
    harness hosts controller + pooled workers + REST server in-process)
    and emit its metrics as a bench JSON line with the contention stamp
    every other bench number carries."""
    contended, cal = contention_probe()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "fleet_harness.py"),
           "--jobs", str(args.fleet_jobs), "--pool", str(args.fleet_pool)]
    if getattr(args, "fleet_shared", False):
        # shared-plan A/B (ISSUE 16): same child, different scenario —
        # its fleet_shared_* keys ride the same bench line and gate
        # against BENCH_BASELINE.json like every other fleet_* key
        cmd.append("--shared-fleet")
    out = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=900,
    )
    report = {}
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            report = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if not report:
        sys.stderr.write(out.stderr[-2000:] + "\n")
    # no "value" key: the fleet line is gated against the SAME
    # BENCH_BASELINE.json as the q-suite line, and bench_compare gates
    # every key present in both docs — a fleet "value" would collide
    # with the q5 headline
    print(json.dumps({
        "metric": ("fleet_shared_agg_eps"
                   if getattr(args, "fleet_shared", False)
                   else "fleet_jobs_per_controller"),
        "unit": ("events/s" if getattr(args, "fleet_shared", False)
                 else "jobs"),
        "contended": contended,
        **cal,
        **{k: v for k, v in report.items() if k.startswith("fleet_")},
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=1_000_000)
    ap.add_argument("--child", choices=["numpy", "jax"])
    ap.add_argument("--query", choices=sorted(QUERIES), default="q5")
    ap.add_argument("--timeout", type=float, default=420.0)
    # mesh side-measurement: q5 on an N-virtual-device CPU mesh so the
    # all_to_all execution path has a throughput number every round
    # (VERDICT r3 item 2). 0 disables.
    ap.add_argument("--mesh", type=int, default=8)
    ap.add_argument("--mesh-devices", type=int, default=0)
    ap.add_argument("--force-device-join", action="store_true")
    ap.add_argument("--state-child", action="store_true")
    ap.add_argument("--latency-child", choices=["numpy", "jax"])
    ap.add_argument("--latency-rate", type=int, default=50_000)
    # 36s realtime: ~17 hop-window closings x ~1.6 qualifying rows per
    # window, so the latency percentiles rest on >= 20 samples (measured:
    # 24s yields 18-19; VERDICT r4 item 7)
    ap.add_argument("--latency-seconds", type=float, default=36.0)
    # median-of-n for every CPU measurement (single-shot numbers on the
    # 1-core bench host swing ±15%+; VERDICT r4 item 5)
    ap.add_argument("--repeats", type=int, default=3)
    # fleet churn harness (ISSUE 10): drive N concurrent tiny pipelines
    # through the REST API against one controller + shared worker pool
    # and report jobs_per_controller / idle CPU per job / API p99 —
    # printed as its own bench JSON line (gateable by bench_compare
    # against the fleet_* keys in BENCH_BASELINE.json)
    ap.add_argument("--fleet", action="store_true")
    ap.add_argument("--fleet-jobs", type=int, default=100)
    ap.add_argument("--fleet-pool", type=int, default=2)
    # shared-plan fleet A/B (ISSUE 16): N tenants on one shared source
    # scan vs unshared — emits fleet_shared_agg_eps /
    # fleet_unshared_agg_eps (pinned + gated like the other fleet keys)
    ap.add_argument("--fleet-shared", action="store_true")
    args = ap.parse_args()
    if args.fleet or args.fleet_shared:
        fleet_main(args)
        return
    if args.state_child:
        state_child(args.events)
        return
    if args.latency_child:
        latency_child(args.latency_rate, args.latency_seconds,
                      args.latency_child)
        return
    if args.child:
        child(args.events, args.child, args.query, args.mesh_devices,
              args.force_device_join)
        return

    # contended-host detection BEFORE measuring: retry a couple of times
    # while the box settles, then stamp whatever state the measurements
    # actually ran under into the JSON (VERDICT r5 item 5)
    import time

    contended, cal = contention_probe()
    for _ in range(2):
        if not contended:
            break
        time.sleep(10)
        contended, cal = contention_probe()

    cpu_env = dict(os.environ)
    cpu_env["JAX_PLATFORMS"] = "cpu"
    baseline = run_median(args.events, "numpy", args.timeout, env=cpu_env,
                          force_device_join=args.force_device_join,
                          n=args.repeats)
    # the device path stays single-shot (its compiles are reported, not
    # amortized over repeats); the numpy children above have exited, so
    # this child is the only process that can claim an accelerator
    device = run_child(args.events, "jax", args.timeout,
                       force_device_join=args.force_device_join)
    failed = [name for name, r in (("numpy baseline", baseline),
                                   ("device", device)) if r is None]
    if failed:
        print(json.dumps({
            "metric": "nexmark_q5_events_per_sec", "value": 0,
            "unit": "events/s", "vs_baseline": None,
            "baseline_cpu_eps": round(baseline["eps"], 1)
            if baseline is not None else None,
            "error": " and ".join(failed) + " child failed "
            "(its stderr is above)",
        }))
        sys.exit(1)
    # side metrics run on the jax backend in the caller's environment,
    # single-shot, like the device child
    side_backend = "jax"
    sides = {}
    for q in ("q1", "q7", "q8", "qu", "qs"):
        # half the events: side metrics, not the headline measurement
        r = run_median(args.events // 2, side_backend, args.timeout,
                       query=q, n=1,
                       force_device_join=args.force_device_join)
        # 0 = that query failed/timed out (distinguishable from "not run")
        sides[f"{q}_eps"] = round(r["eps"], 1) if r is not None else 0
        if r is not None and "eps_runs" in r:
            sides[f"{q}_eps_runs"] = r["eps_runs"]
        if r is not None and "warmup_eps" in r:
            sides[f"{q}_warmup_eps"] = r["warmup_eps"]
        if r is not None and "compile_s" in r:
            sides[f"{q}_compile_s"] = r["compile_s"]
        if q == "q1" and r is not None and "dispatches_per_batch" in r:
            sides["q1_dispatches_per_batch"] = r["dispatches_per_batch"]
            sides["q1_fused_ops"] = r.get("seg_fused_ops", 0)
    # fused-segment A/B (ISSUE 14): re-run the q1 stateless chain with
    # plan-time segment fusion OFF — same child, one env knob, always on
    # the HOST tier (numpy + cpu env) so the pair is apples-to-apples
    # even when the side metrics ran on the jax backend. The
    # fused/unfused dispatches_per_batch pair pins the >=3x dispatch
    # collapse; the eps pair is the fusion-on gain on this host.
    seg_env = dict(cpu_env)
    seg_env["ARROYO__ENGINE__SEGMENT_FUSION"] = "0"
    r_off = run_median(args.events // 2, "numpy", args.timeout,
                       env=seg_env, query="q1", n=args.repeats)
    if r_off is not None:
        sides["q1_fusion_off_eps"] = round(r_off["eps"], 1)
        if "eps_runs" in r_off:
            sides["q1_fusion_off_eps_runs"] = r_off["eps_runs"]
        if "dispatches_per_batch" in r_off:
            sides["q1_unfused_dispatches_per_batch"] = r_off[
                "dispatches_per_batch"]
    # the q1_eps side metric above ran on jax: add the host-tier fused
    # reference so the fusion-on/off eps pair shares a backend
    r_on = run_median(args.events // 2, "numpy", args.timeout,
                      env=cpu_env, query="q1", n=args.repeats)
    if r_on is not None:
        sides["q1_fusion_on_eps"] = round(r_on["eps"], 1)
        if "eps_runs" in r_on:
            sides["q1_fusion_on_eps_runs"] = r_on["eps_runs"]
        if "dispatches_per_batch" in r_on:
            sides["q1_dispatches_per_batch"] = r_on[
                "dispatches_per_batch"]
            sides["q1_fused_ops"] = r_on.get("seg_fused_ops", 0)
    # mesh execution path: q5 on an N-virtual-device CPU mesh (the
    # all_to_all + ShardedAccumulator path the dryrun only
    # correctness-checks). FULL headline event count: the mesh number
    # is compared against the single-process headline, so it must be
    # measured at the same size — and at the path's current speed a
    # quarter-size run is ~60% fixed process startup (jax init + one
    # python-side trace per cached XLA program), which would understate
    # steady-state throughput ~2.4x.
    if args.mesh >= 2:
        mesh_env = dict(cpu_env)
        # force the virtual device count to --mesh even when the caller's
        # XLA_FLAGS already pins one (a stale smaller count would make
        # the child raise and the metric read 0)
        import re
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            mesh_env.get("XLA_FLAGS", ""),
        ).strip()
        mesh_env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.mesh}"
        ).strip()
        # median-of-n; the persistent XLA cache makes runs 2..n warm, so
        # the median reflects steady-state rather than compile time
        r = run_median(args.events, "jax", args.timeout, env=mesh_env,
                       mesh_devices=args.mesh, n=args.repeats)
        sides[f"q5_mesh{args.mesh}_eps"] = (
            round(r["eps"], 1) if r is not None else 0
        )
        # mesh throughput is measured on VIRTUAL CPU devices (XLA host
        # platform) — it validates the sharded execution path, not
        # accelerator hardware; mirror side_backend so JSON consumers
        # can never mistake it for a TPU number (VERDICT r5 weak #7)
        sides["mesh_backend"] = "cpu-virtual"
        if r is not None and "eps_runs" in r:
            sides[f"q5_mesh{args.mesh}_eps_runs"] = r["eps_runs"]
        if r is not None and "warmup_eps" in r:
            sides[f"q5_mesh{args.mesh}_warmup_eps"] = r["warmup_eps"]
        if r is not None and "compile_s" in r:
            sides[f"q5_mesh{args.mesh}_compile_s"] = r["compile_s"]
        if r is not None and "rows_sent" in r:
            shipped = r["rows_sent"] + r["rows_padded"]
            sides["mesh_rows_sent"] = r["rows_sent"]
            sides["mesh_rows_padded"] = r["rows_padded"]
            sides["mesh_padding_ratio"] = round(
                r["rows_padded"] / max(1, shipped), 3
            )
            if "dispatches" in r:
                # device steps per engine update call: the micro-batching
                # amortization (tpu.mesh_flush_rows + read-elision)
                sides["mesh_dispatches"] = r["dispatches"]
                sides["mesh_updates"] = r["updates"]
            if "flushes_elided" in r:
                sides["mesh_flushes_elided"] = r["flushes_elided"]
            if "rows_combined" in r:
                # rows collapsed by the host combiner before packing
                # (rows_sent counts post-combine shipped rows)
                sides["mesh_rows_combined"] = r["rows_combined"]
    # state-at-scale side scenario (ISSUE 8): session state grows all
    # run while a checkpoint cadence uploads incrementally; reports
    # capture p99 + amortized upload bytes per epoch, gated by
    # tools/bench_compare.py (both lower-is-better). Median-of-n with
    # published runs arrays: wall-time p99s wobble run-to-run, and the
    # gate derives its threshold from the measured spread.
    # Fixed event count: the scenario needs enough wall time for a
    # meaningful number of checkpoint epochs even at CI smoke scale.
    st_cmd = [sys.executable, os.path.abspath(__file__), "--state-child",
              "--events", "400000"]
    st_runs = []
    for _ in range(max(1, args.repeats)):
        try:
            out = subprocess.run(st_cmd, capture_output=True, text=True,
                                 timeout=args.timeout, env=cpu_env)
        except subprocess.TimeoutExpired:
            sys.stderr.write("state child timed out\n")
            continue
        for line in out.stdout.splitlines():
            if line.startswith("STATECK "):
                _, p99, per_epoch, epochs = line.split()
                if epochs != "0":
                    st_runs.append(
                        (float(p99), int(per_epoch), int(epochs))
                    )
    if st_runs:
        st_runs.sort()
        med = st_runs[(len(st_runs) - 1) // 2]
        sides["checkpoint_capture_ms_p99"] = med[0]
        sides["checkpoint_capture_ms_p99_runs"] = [r[0] for r in st_runs]
        sides["checkpoint_bytes_per_epoch"] = med[1]
        sides["checkpoint_bytes_per_epoch_runs"] = sorted(
            r[1] for r in st_runs
        )
        sides["state_ckpt_epochs"] = med[2]
    # end-to-end latency (realtime q5; includes the source watermark delay)
    lat_cmd = [sys.executable, os.path.abspath(__file__),
               "--latency-child", side_backend,
               "--latency-rate", str(args.latency_rate),
               "--latency-seconds", str(args.latency_seconds)]
    try:
        # child's own join deadline is seconds*3+120; give startup slack
        out = subprocess.run(lat_cmd, capture_output=True, text=True,
                             timeout=args.latency_seconds * 3 + 240)
        got = False
        for line in out.stdout.splitlines():
            if line.startswith("LATENCY "):
                _, p50, p99, rows = line.split()
                if rows != "0":
                    sides["q5_p50_ms"] = float(p50)
                    sides["q5_p99_ms"] = float(p99)
                    sides["q5_lat_samples"] = int(rows)
                got = True
        if not got:
            sys.stderr.write(out.stderr[-2000:] + "\n")
    except subprocess.TimeoutExpired:
        sys.stderr.write("latency child timed out\n")
    # distributed-mode latency: same realtime q5, but operators split
    # across worker processes over the TCP data plane. parallelism=1 so
    # the recurring metric tracks the low-variance single-TCP-hop
    # deployment (p2's ~1 row per hop window makes its p99 noise);
    # guarded — a failed side measurement must not void the bench
    try:
        dist = latency_distributed(args.latency_rate, args.latency_seconds,
                                   workers=2, parallelism=1)
    except Exception as e:  # noqa: BLE001 - side metric only
        sys.stderr.write(f"distributed latency failed: {e}\n")
        dist = None
    if dist is not None:
        sides["q5_p50_ms_dist"] = round(dist[0], 1)
        sides["q5_p99_ms_dist"] = round(dist[1], 1)
        sides["q5_lat_samples_dist"] = dist[2]
    # fleet observatory (ISSUE 11): loop-lag p99 of the instrumented CPU
    # headline run, plus the attribution-overhead check — one extra
    # UNinstrumented q5 run (attribution + timeline off via the config
    # env layer) against the instrumented median. Both gated by
    # bench_compare (loop lag regresses upward; overhead is gated in
    # absolute percentage points — the acceptance bar is < 2% cost).
    if "loop_lag_ms_p99" in baseline:
        sides["loop_lag_ms_p99"] = baseline["loop_lag_ms_p99"]
        sides["loop_lag_samples"] = baseline.get("loop_lag_samples", 0)
    attr_env = dict(cpu_env)
    attr_env["ARROYO__OBS__ATTRIBUTION"] = "0"
    attr_env["ARROYO__OBS__TIMELINE_EVENTS"] = "0"
    r_off = run_child(args.events, "numpy", args.timeout, env=attr_env,
                      force_device_join=args.force_device_join)
    if r_off is not None:
        sides["q5_attr_off_eps"] = round(r_off["eps"], 1)
        sides["attr_overhead_pct"] = round(
            max(0.0, 100.0 * (1.0 - baseline["eps"] / r_off["eps"])), 2
        )
    # watchtower overhead (ISSUE 13): one more UNinstrumented q5 run with
    # the history tier + SLO engine off — the headline median already runs
    # with watch on (the default), so the delta IS the watchtower's cost.
    # Same absolute-points gate class as attr_overhead_pct (<= 2% bar).
    watch_env = dict(cpu_env)
    watch_env["ARROYO__WATCH__ENABLED"] = "0"
    r_woff = run_child(args.events, "numpy", args.timeout,
                       env=watch_env,
                       force_device_join=args.force_device_join)
    if r_woff is not None:
        sides["q5_watch_off_eps"] = round(r_woff["eps"], 1)
        sides["watch_overhead_pct"] = round(
            max(0.0, 100.0 * (1.0 - baseline["eps"] / r_woff["eps"])),
            2,
        )
    # conservation ledger (ISSUE 19): one more UNinstrumented q5 run with
    # the always-on audit ledger off — the headline median runs with
    # auditing on (the default), so the delta IS the attestation cost
    # (per-batch commutative hashing + per-epoch seal/drain/report).
    # Same absolute-points gate class as attr_overhead_pct; the ISSUE 19
    # acceptance target is <= 3%.
    audit_env = dict(cpu_env)
    audit_env["ARROYO__AUDIT__ENABLED"] = "0"
    r_aoff = run_child(args.events, "numpy", args.timeout,
                       env=audit_env,
                       force_device_join=args.force_device_join)
    if r_aoff is not None:
        sides["q5_audit_off_eps"] = round(r_aoff["eps"], 1)
        sides["audit_overhead_pct"] = round(
            max(0.0, 100.0 * (1.0 - baseline["eps"] / r_aoff["eps"])),
            2,
        )
    print(json.dumps({
        "metric": "nexmark_q5_events_per_sec",
        "pin_era": PIN_ERA,
        "value": round(device["eps"], 1),
        "unit": "events/s",
        # which backend produced the q1/q7/q8/latency side metrics
        "side_backend": side_backend,
        "vs_baseline": round(device["eps"] / baseline["eps"], 3),
        "baseline_cpu_eps": round(baseline["eps"], 1),
        # XLA compile seconds inside the single-shot device child
        **({"value_compile_s": device["compile_s"]}
           if "compile_s" in device else {}),
        "events": args.events,
        "result_rows": device["rows"],
        # host contention state the measurements ran under (calibration
        # spin + loadavg; measurements proceeded regardless — consumers
        # should discount dispersion when contended is true)
        "contended": contended,
        **cal,
        **sides,
    }))


if __name__ == "__main__":
    main()
