#!/usr/bin/env python
"""Mesh hot-path stage budget: profile q5 on the N-virtual-device CPU
mesh and split wall time into XLA dispatch vs host packing vs directory
work (VERDICT round-5 item 4: "no profile says how much of the remaining
gap is XLA-CPU dispatch floor vs removable host work").

The measurement drives the existing `/debug/profile` admin endpoint
(arroyo_tpu/utils/admin.py): the child process runs the same q5 mesh
workload as `bench.py --mesh N` with the admin server on an ephemeral
port; the parent captures a windowed cProfile over the steady state
(after a warmup run has paid all XLA compiles) and buckets the pstats
rows into stages. Output is one JSON line plus an optional markdown
table. It profiles the CPU virtual mesh; nothing it prints is a device
number.

Usage:
    python tools/mesh_profile.py [--events 2000000] [--mesh 8]
                                 [--seconds 10] [--markdown]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -------------------------------------------------------------- child

def child(events: int, mesh: int, linger: float) -> None:
    """Run q5 on the mesh with the admin server up. Protocol on stdout:
    ADMIN <port>, MEASURING (engine started, steady state), then the
    bench-compatible MESHSTATS / RESULT lines."""
    import asyncio
    import time

    sys.path.insert(0, REPO)
    import bench
    from arroyo_tpu.config import config
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query
    from arroyo_tpu.utils.admin import serve_admin

    # mirror bench.py's mesh child settings exactly: the budget must
    # describe the same configuration the benchmark measures
    config().tpu.enabled = True
    config().pipeline.source_batch_size = 8192
    config().tpu.mesh_devices = mesh
    config().tpu.shape_buckets = (8192, 65536)
    config().tpu.initial_capacity = 1 << 18
    config().tpu.use_32bit_accumulators = True

    def plan(n_events: int):
        rate = max(n_events // 60, 1)
        results: list = []
        p = plan_query(
            bench.QUERIES["q5"].format(rate=rate, events=n_events),
            preview_results=results,
        )
        bench.force_backend(p, "jax")
        return p

    # warmup: pay every XLA compile (programs persist in-process) so the
    # profiled window sees steady-state dispatch, not compilation
    warm = plan(max(events // 10, 20_000))

    async def run_warm():
        eng = Engine(warm.graph).start()
        await eng.join(600)

    asyncio.run(run_warm())
    print("WARMED", flush=True)

    measured = plan(events)

    async def run_measured():
        runner, port = await serve_admin("mesh-profile", port=0)
        print(f"ADMIN {port}", flush=True)
        t0 = time.monotonic()
        eng = Engine(measured.graph).start()
        print("MEASURING", flush=True)
        await eng.join(600)
        dt = time.monotonic() - t0
        from arroyo_tpu.parallel.sharded_state import MESH_STATS

        print(f"MESHSTATS {MESH_STATS['rows_sent']} "
              f"{MESH_STATS['rows_padded']} "
              f"{MESH_STATS['dispatches']} "
              f"{MESH_STATS['updates']} "
              f"{MESH_STATS['flushes_elided']} "
              f"{MESH_STATS['rows_combined']}", flush=True)
        # device-tier observatory (ISSUE 6): per-program dispatch-time
        # quantiles + per-rung padding waste, folded into the stage
        # budget so the mesh refactor has a before/after ledger
        from arroyo_tpu.obs import device as obs_device

        summ = obs_device.summary()
        print("DEVICE " + json.dumps({
            "programs": summ["programs"],
            "padding_waste": summ["padding_waste"],
            # fused segment runtime (ISSUE 14): per-segment dispatch
            # stats by tier + fused-op counts, so the BASELINE ledger
            # carries a per-segment row set next to the device programs
            "segments": summ["segments"],
        }), flush=True)
        print(f"RESULT {events / dt:.1f} 0 {dt:.2f}", flush=True)
        if linger > 0:
            # keep the loop (and the in-flight /debug/profile capture)
            # alive if the run finished before the window closed
            await asyncio.sleep(linger)
        if runner is not None:
            await runner.cleanup()

    asyncio.run(run_measured())


# -------------------------------------------------------------- parse

# sharded_state.py hosts both the directory facade and the accumulator;
# split its rows by function name so "directory work" and "host packing"
# stay separate stages
_DIR_FUNCS = {
    "assign", "owners_for", "take_bin", "take_bin_arrays", "bin_entries",
    "bin_entries_multi", "items", "keys_for_slots", "slots_for_keys",
    "remove", "peek_bin", "bins_up_to", "live_bins", "alloc_slot",
    "alloc_slots", "free_slot", "free_slots", "required_capacity",
    "entries_arrays", "n_live", "by_bin", "swap_to_native",
}

_ROW_RE = re.compile(
    r"^\s*(\S+)\s+([\d.]+)\s+[\d.]+\s+([\d.]+)\s+[\d.]+\s+(.+)$"
)


def classify(loc: str) -> str:
    l = loc.strip()
    if ("method 'poll'" in l or "method 'select'" in l or "epoll" in l
            or "_run_once" in l or "Event.wait" in l
            or "method 'acquire' of '_thread.lock'" in l):
        return "idle"
    if "directory.py" in l or "ops/native.py" in l or "arroyo_native" in l:
        return "directory"
    if "sharded_state.py" in l:
        fn = l.rsplit("(", 1)[-1].rstrip(")")
        return "directory" if fn in _DIR_FUNCS else "host_packing"
    if "jax" in l or "jaxlib" in l or "xla" in l:
        return "xla_dispatch"
    if "aggregates.py" in l:
        return "host_packing"
    if "numpy" in l or l.startswith("{method") and (
            "of 'numpy" in l or "ndarray" in l):
        return "numpy_kernels"
    if ("windows.py" in l or "updating.py" in l or "joins.py" in l
            or "operators/" in l):
        return "operator_host"
    if "pyarrow" in l or "expressions.py" in l or "schema.py" in l:
        return "sql_arrow"
    return "other"


def parse_profile(text: str) -> dict:
    """pstats table -> {stage: tottime seconds}."""
    stages: dict = {}
    for line in text.splitlines():
        m = _ROW_RE.match(line)
        if not m or m.group(4).startswith("filename:"):
            continue
        tottime = float(m.group(2))
        if tottime <= 0:
            continue
        stage = classify(m.group(4))
        stages[stage] = stages.get(stage, 0.0) + tottime
    return stages


def budget_from_stages(stages: dict) -> dict:
    """Normalize to a stage budget over the ACTIVE profiled time (idle —
    the event loop waiting with no work — is excluded and reported)."""
    idle = stages.pop("idle", 0.0)
    active = sum(stages.values())
    budget = {
        k: {"seconds": round(v, 3),
            "pct": round(100.0 * v / active, 1) if active else 0.0}
        for k, v in sorted(stages.items(), key=lambda kv: -kv[1])
    }
    return {"active_seconds": round(active, 3),
            "idle_seconds": round(idle, 3), "stages": budget}


# -------------------------------------------------------------- parent

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=2_000_000)
    ap.add_argument("--mesh", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="profile capture window")
    ap.add_argument("--markdown", action="store_true",
                    help="also print a markdown table")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--linger", type=float, default=0.0)
    args = ap.parse_args()
    if args.child:
        child(args.events, args.mesh, args.linger)
        return 0

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={args.mesh}"
    ).strip()

    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--events", str(args.events), "--mesh", str(args.mesh),
           "--linger", str(args.seconds + 3.0)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            stderr=subprocess.PIPE, cwd=REPO, env=env)

    port = None
    profile_text: list = []
    capture: list = [None]

    def grab(p: int):
        import urllib.request

        url = (f"http://127.0.0.1:{p}/debug/profile"
               f"?seconds={args.seconds}&limit=800")
        try:
            with urllib.request.urlopen(url, timeout=args.seconds + 60) as r:
                capture[0] = r.read().decode()
        except Exception as e:  # noqa: BLE001 - reported below
            capture[0] = None
            sys.stderr.write(f"profile capture failed: {e}\n")

    t = None
    result = None
    stats = None
    device = None
    assert proc.stdout is not None
    for line in proc.stdout:
        line = line.strip()
        if line.startswith("ADMIN "):
            port = int(line.split()[1])
        elif line == "MEASURING" and port is not None:
            t = threading.Thread(target=grab, args=(port,), daemon=True)
            t.start()
        elif line.startswith("RESULT "):
            parts = line.split()
            result = {"eps": float(parts[1]), "secs": float(parts[3])}
        elif line.startswith("DEVICE "):
            try:
                device = json.loads(line[len("DEVICE "):])
            except json.JSONDecodeError:
                device = None
        elif line.startswith("MESHSTATS "):
            parts = [int(x) for x in line.split()[1:]]
            shipped = parts[0] + parts[1]
            stats = {
                "rows_sent": parts[0], "rows_padded": parts[1],
                "padding_ratio": round(parts[1] / max(1, shipped), 3),
                "dispatches": parts[2], "updates": parts[3],
                "flushes_elided": parts[4] if len(parts) > 4 else 0,
                "rows_combined": parts[5] if len(parts) > 5 else 0,
            }
    if t is not None:
        t.join(args.seconds + 90)
    proc.wait(120)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr.read()[-3000:] + "\n")
        return 1
    if capture[0] is None:
        sys.stderr.write("no profile captured (run too short for the "
                         "window? raise --events or lower --seconds)\n")
        return 1
    budget = budget_from_stages(parse_profile(capture[0]))
    out = {
        "metric": "q5_mesh_stage_budget",
        "mesh_devices": args.mesh,
        "events": args.events,
        "profile_seconds": args.seconds,
        **({"q5_mesh_eps": round(result["eps"], 1),
            "run_seconds": result["secs"]} if result else {}),
        **({"mesh_stats": stats} if stats else {}),
        **({"device_telemetry": device} if device else {}),
        **budget,
    }
    print(json.dumps(out))
    if args.markdown:
        print()
        print("| stage | seconds | % of active |")
        print("|---|---|---|")
        for k, v in budget["stages"].items():
            print(f"| {k} | {v['seconds']} | {v['pct']}% |")
        print(f"\nActive profiled time {budget['active_seconds']}s over a "
              f"{args.seconds}s window (idle {budget['idle_seconds']}s); "
              f"q5_mesh{args.mesh} "
              f"{out.get('q5_mesh_eps', 'n/a')} ev/s.")
        if device:
            # the observatory's per-program ledger: dispatch floor +
            # padding waste per rung beside the host-stage budget. The
            # exchange column is arroyo_device_exchange_seconds — the
            # keyed-shuffle collective's own time, which REPLACES the
            # old host-exchange stage rows of earlier BASELINE rounds
            # (those costs now live in the route/step programs)
            print("\n| program | compiles | compile s | dispatches "
                  "| dispatch p50/p95 | exchange s (n) | cache h/m |")
            print("|---|---|---|---|---|---|---|")
            for name, p in sorted(device.get("programs", {}).items()):
                dq = p.get("dispatch_quantiles", {})
                ex = (f"{p.get('exchange_s_total', 0)} "
                      f"({p.get('exchange_dispatches', 0)})"
                      if p.get("exchange_dispatches") else "-")
                print(f"| {name} | {p.get('compiles', 0)} "
                      f"| {p.get('compile_s_total', 0)} "
                      f"| {p.get('dispatches', 0)} "
                      f"| {dq.get('p50', 'n/a')}/{dq.get('p95', 'n/a')} s "
                      f"| {ex} "
                      f"| {p.get('cache_hit', 0)}/"
                      f"{p.get('cache_miss', 0)} |")
            waste = [w for w in device.get("padding_waste", [])
                     if w.get("waste")]
            if waste:
                print("\n| program | rung | padding waste |")
                print("|---|---|---|")
                for w in waste:
                    print(f"| {w['program']} | {w['rung']} "
                          f"| {100.0 * w['waste']:.1f}% |")
            segs = device.get("segments", {})
            if segs:
                # per-segment ledger (ISSUE 14): one row per fused
                # segment program — how many operator dispatches each
                # batch no longer pays, and what the single dispatch
                # costs
                print("\n| segment | fused ops | dispatches "
                      "| total s | p50/p95 |")
                print("|---|---|---|---|---|")
                for name, s in sorted(segs.items()):
                    n = s.get("host_dispatches")
                    if not n:
                        continue
                    q = s.get("host_quantiles", {})
                    print(f"| {name} | {s.get('fused_ops', '?')} "
                          f"| {n} | {s.get('host_s_total', 0)} "
                          f"| {q.get('p50', 'n/a')}/"
                          f"{q.get('p95', 'n/a')} s |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
