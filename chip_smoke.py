#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that arroyo_tpu still starts and
computes the right answers on the chip.

Drives the normal path — SQL -> planner -> controller -> worker -> device
tier, i.e. `python -m arroyo_tpu run q.sql [--state-dir ...]` — in THIS
process (the process that first touches jax owns the chip; nothing here
starts a child that needs it), checks every result against the committed
goldens or against the host (numpy) tier on the same SQL, and fails
unless the device programs each phase exists for actually recorded calls.

  1. jax.devices(): platform other than `tpu` is a non-zero exit.
  2. native/slotdir.cpp rebuilt from source (no stray binary is trusted).
  3. five committed goldens through the device tiers.
  4. NEXmark q5, durable (state dir, >= 3 completed checkpoints), at
     --events (default 10,000,000; 60 s of event time), generator
     proportions untouched, defaults + pipeline.source_batch_size=8192;
     result set equal to the host tier's.
  5. q1, q7, q8, qu at 2,000,000 events likewise (q1 is stateless: its
     output is compared, no device program is required of it).
  6. with >= 4 devices: q5 again on a 4-chip mesh (device exchange, state
     sharded over four distinct devices, results equal to step 4's).

The last stdout line is one JSON object: {"ok": true, "device": {...}}.
Any failing phase is a non-zero exit and no such line.

`--rehearsal` runs the same phases at tiny sizes on XLA's CPU backend
(tier-1 uses it); every result line then says `platform=cpu, rehearsal`.
It is never the default and proves nothing about a chip.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# Everything the smoke sets away from the defaults, printed at start.
# The window programs run at the shipped tpu.* defaults (shape_buckets,
# initial_capacity = 4096, 64-bit accumulators).
NON_DEFAULTS = {
    # the batch size every record in this repo was taken at
    "ARROYO__PIPELINE__SOURCE_BATCH_SIZE": "8192",
    # short enough that >= 3 epochs complete during the sized q5 run
    "ARROYO__PIPELINE__CHECKPOINTING__INTERVAL": "5s",
}

GOLDENS = ("nexmark_q5", "sliding_window_end", "windowed_inner_join",
           "updating_aggregate", "every_aggregate")

DDL = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '{rate}',
  message_count = '{events}', start_time = '0'
);
CREATE TABLE sink ({cols}) WITH ({sink}, type = 'sink');
INSERT INTO sink
"""
# append-only results: the two-phase-commit parquet sink; the updating
# query's retract/append stream needs the debezium envelope
PARQUET = "connector = 'filesystem', path = '{out}', format = 'parquet'"
DEBEZIUM = ("connector = 'single_file', path = '{out}/out.json', "
            "format = 'debezium_json'")

# The NEXmark texts of bench.py (q5 is the reference's nexmark_q5.sql
# shape), each writing to a sink that keeps its rows.
QUERIES = {
    "q5": ("auction BIGINT, num BIGINT", """
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
"""),
    "q1": ("auction BIGINT, price_eur BIGINT, bidder BIGINT", """
SELECT auction, price_eur, bidder FROM (
  SELECT auction, price_eur - price_eur % 10 AS price_eur, bidder FROM (
    SELECT bid.auction as auction, bid.price * 100 / 121 as price_eur,
           bid.bidder as bidder
    FROM nexmark WHERE bid IS NOT NULL
  )
);
"""),
    "q7": ("auction BIGINT, price BIGINT, bidder BIGINT", """
SELECT W.auction, W.price, W.bidder FROM (
  SELECT bid.auction as auction, bid.price as price, bid.bidder as bidder,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE bid IS NOT NULL GROUP BY 1, 2, 3, w
) AS W JOIN (
  SELECT max(bid.price) as maxprice, tumble(interval '10 second') as w
  FROM nexmark WHERE bid IS NOT NULL GROUP BY w
) AS M ON W.w = M.w AND W.price = M.maxprice;
"""),
    "q8": ("id BIGINT, name TEXT", """
SELECT P.id, P.name FROM (
  SELECT person.id as id, person.name as name,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE person IS NOT NULL GROUP BY 1, 2, w
) AS P JOIN (
  SELECT auction.seller as seller, tumble(interval '10 second') as w,
         count(*) as c2
  FROM nexmark WHERE auction IS NOT NULL GROUP BY 1, w
) AS A ON P.id = A.seller AND P.w = A.w;
"""),
    "qu": ("a BIGINT, c BIGINT, s BIGINT", """
SELECT bid.auction % 1000 AS a, count(*) AS c, sum(bid.price) AS s
FROM nexmark WHERE bid IS NOT NULL GROUP BY 1;
"""),
}


def nexmark_sql(q: str, events: int, out: str) -> str:
    """~60 s of event time whatever the size, as in bench.py."""
    cols, select = QUERIES[q]
    sink = (DEBEZIUM if q == "qu" else PARQUET).format(out=out)
    return DDL.format(rate=max(events // 60, 1), events=events, cols=cols,
                      sink=sink) + select

# The device programs each query exists to exercise (obs/device.py
# program names). Calls = compiles + dispatches: InstrumentedJit books
# the first call of a shape signature under `compiles`.
AGG = ("agg.update", "agg.gather", "agg.reset")
JOIN = ("join.phase1", "join.phase2")
REQUIRED = {
    "q5": AGG, "q7": AGG, "q8": AGG,  # + JOIN in q7 or q8, see run()
    "qu": ("agg.update", "agg.gather"),
    "q5-mesh": ("mesh.route",),
}


class SmokeFailure(Exception):
    """A phase did not hold; the smoke exits non-zero."""


class Smoke:
    def __init__(self, args, jax):
        self.args = args
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.tag = (f"platform={dev.platform} kind={dev.device_kind!r} "
                    f"count={len(jax.devices())}")
        if args.rehearsal:
            self.tag = "platform=cpu, rehearsal"
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.results: dict = {}   # label -> sizes and times, printed
        self.calls: dict = {}     # label -> device programs it called

    def say(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", flush=True)

    # -- running a query through the user's entry point ---------------------

    def run_cli(self, sql: str, label: str, state_dir=None,
                parallelism: int = 1) -> float:
        """`python -m arroyo_tpu run <file> [--state-dir d]`, in this
        process. Returns wall seconds; raises unless the job FINISHED."""
        from arroyo_tpu.__main__ import main as cli

        qfile = os.path.join(self.work, f"{label}.sql")
        with open(qfile, "w") as f:
            f.write(sql)
        argv = ["run", qfile, "--parallelism", str(parallelism)]
        if state_dir:
            argv += ["--state-dir", state_dir]
        t0 = time.monotonic()
        rc = cli(argv)
        dt = time.monotonic() - t0
        if rc != 0:
            raise SmokeFailure(f"{label}: `arroyo_tpu run` returned {rc}")
        return dt

    @contextlib.contextmanager
    def tier(self, device: bool, **tpu):
        """Config for one run: the host tier is tpu.enabled = false (numpy
        never touches the chip); the device tier is the default config,
        plus whatever the phase must set and print."""
        from arroyo_tpu.config import update

        if not device:
            tpu = {"enabled": False}
        elif self.args.rehearsal:
            # engage the device tiers on XLA's CPU backend
            tpu = {**tpu, "require_accelerator": False}
        with update(tpu=tpu):
            yield

    # -- device-work proof ----------------------------------------------------

    @staticmethod
    def program_calls() -> dict:
        from arroyo_tpu.obs import device as obs_device

        return {
            name: {"compiles": p.get("compiles", 0),
                   "compile_s": p.get("compile_s_total", 0.0),
                   "dispatches": p.get("dispatches", 0),
                   "dispatch_s": p.get("dispatch_s_total", 0.0)}
            for name, p in obs_device.summary()["programs"].items()
        }

    def prove(self, label: str, before: dict, required) -> dict:
        """Programs that recorded calls since `before`; fails unless every
        required program (a trailing '.' = name prefix) is among them."""
        after = self.program_calls()
        zero = {"compiles": 0, "compile_s": 0.0, "dispatches": 0,
                "dispatch_s": 0.0}
        delta = {}
        for name, a in after.items():
            b = before.get(name, zero)
            d = {k: a.get(k, 0) - b.get(k, 0) for k in a}
            if d["compiles"] + d["dispatches"]:
                delta[name] = d
        for name in sorted(delta):
            d = delta[name]
            self.say(f"  {label} {name}: compiles={d['compiles']} "
                     f"compile_s={d['compile_s']:.2f} "
                     f"dispatches={d['dispatches']} "
                     f"dispatch_host_s={d['dispatch_s']:.2f}")
        for req in required:
            hits = [n for n in delta
                    if (n.startswith(req) if req.endswith(".") else n == req)]
            if not hits:
                raise SmokeFailure(
                    f"{label}: no device call recorded for program "
                    f"{req!r} — the phase ran on the host tier")
        return delta

    # -- outputs ------------------------------------------------------------

    @staticmethod
    def read_parquet(path: str):
        """All rows a filesystem/parquet sink committed under `path`, and
        their (rows, order-insensitive digest)."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        from arroyo_tpu.obs.audit import batch_fingerprint

        if glob.glob(os.path.join(path, "*.tmp")):
            raise SmokeFailure(f"{path}: sink left uncommitted .tmp files")
        files = sorted(glob.glob(os.path.join(path, "*.parquet")))
        if not files:
            raise SmokeFailure(f"{path}: sink committed no file")
        table = pa.concat_tables([pq.read_table(f) for f in files])
        rows = digest = 0
        for b in table.combine_chunks().to_batches():
            n, d = batch_fingerprint(b)
            rows += n
            digest = (digest + d) % (1 << 64)
        return table, rows, digest

    def compare(self, label: str, dev_path: str, host_path: str) -> None:
        """Complete result sets, device tier against host tier: sorted
        row-for-row equality, with rows + digest as the printed identity."""
        dev, d_rows, d_dig = self.read_parquet(dev_path)
        host, h_rows, h_dig = self.read_parquet(host_path)
        keys = [(c, "ascending") for c in dev.column_names]
        same = (d_rows, d_dig) == (h_rows, h_dig) and dev.sort_by(
            keys).equals(host.sort_by(keys))
        self.say(f"{label}: device rows={d_rows} host rows={h_rows} "
                 f"digest={d_dig:#018x} equal={same}")
        if not same or d_rows == 0:
            raise SmokeFailure(
                f"{label}: device tier output differs from the host "
                f"tier's (rows {d_rows} vs {h_rows}, digest {d_dig:#x} "
                f"vs {h_dig:#x})")

    def compare_updating(self, label: str, dev_path: str,
                         host_path: str) -> None:
        """An updating stream's retract/append sequence depends on flush
        timing; its net state (the golden harness's debezium replay)
        does not."""
        import test_golden as tg

        dev, host = (
            tg.merge_debezium(tg.read_rows(os.path.join(p, "out.json")),
                              ["a"])
            for p in (dev_path, host_path))
        self.say(f"{label}: device net keys={len(dev)} host net keys="
                 f"{len(host)} equal={dev == host}")
        if dev != host or not dev:
            raise SmokeFailure(f"{label}: device tier net state differs "
                               f"from the host tier's")

    # -- phases -------------------------------------------------------------

    def goldens(self) -> None:
        sys.path.insert(0, os.path.join(HERE, "tests"))
        import test_golden as tg

        from arroyo_tpu.ops._jax import float64_is_ieee

        for name in GOLDENS:
            qpath = os.path.join(tg.GOLDEN, "queries", f"{name}.sql")
            want = [ln.strip() for ln in open(os.path.join(
                tg.GOLDEN, "golden_outputs", f"{name}.json"))]
            out = os.path.join(self.work, f"golden_{name}.json")
            sql = tg.load_query(qpath, out)
            tg.register_query_udfs(tg.query_headers(qpath))
            # the join golden's bins are a few rows: drop the row floor
            # so it reaches the device probe
            tpu = ({"device_join_min_rows": 0}
                   if name == "windowed_inner_join" else {})
            before = self.program_calls()
            with self.tier(True, **tpu):
                dt = self.run_cli(sql, f"golden_{name}", parallelism=2)
            got = tg.canonicalize_output(out, sql)
            self.say(f"golden {name}: rows={len(got)} equal={got == want} "
                     f"wall_s={dt:.1f}"
                     + (" (tpu.device_join_min_rows=0)" if tpu else ""))
            if got != want:
                diff = next((f"got {g[:300]} want {w[:300]}"
                             for g, w in zip(got, want) if g != w), "")
                raise SmokeFailure(
                    f"golden {name}: {len(got)} rows differ from the "
                    f"committed {len(want)}: {diff}")
            required = JOIN if tpu else ("agg.update", "agg.gather")
            if name == "every_aggregate" and not float64_is_ieee():
                # avg/var/regr keep float64 state, which a TPU cannot hold
                # exactly: the operator takes the numpy tier there by rule
                # (ops/aggregates.float_state_stays_on_host) and only the
                # result is checked
                self.say(f"golden {name}: float64 accumulators, host tier "
                         f"by rule (no IEEE float64 on this device)")
                required = ()
            self.prove(f"golden {name}", before, required)

    def nexmark(self, q: str, events: int, durable: bool = False,
                label: str = "", **tpu) -> str:
        """One sized device-tier run of query `q`; returns its output dir."""
        label = label or q
        out = os.path.join(self.work, f"out_{label}")
        sql = nexmark_sql(q, events, out)
        state = os.path.join(self.work, f"state_{label}") if durable else None
        before = self.program_calls()
        with self.tier(True, **tpu):
            dt = self.run_cli(sql, label, state_dir=state)
        self.say(f"{label}: device tier {events} events wall_s={dt:.1f} "
                 f"events_per_s={events / dt:.0f} (information, not a "
                 f"benchmark metric)")
        self.results[label] = {"events": events, "wall_s": round(dt, 2)}
        # q1 is stateless (a fused segment, host by design): no entry
        delta = self.calls[label] = self.prove(label, before,
                                               REQUIRED.get(label, ()))
        self.results[label]["compile_s"] = round(
            sum(d["compile_s"] for d in delta.values()), 2)
        if durable:
            epochs = [json.load(open(p))["epoch"] for p in glob.glob(
                os.path.join(state, "*", "latest.json"))]
            done = max(epochs, default=0)
            self.say(f"{label}: completed checkpoints={done}")
            self.results[label]["checkpoints"] = done
            if done < 3 and not self.args.rehearsal:
                raise SmokeFailure(
                    f"{label}: {done} checkpoints completed, need >= 3")
        return out

    def host_reference(self, q: str, events: int) -> str:
        out = os.path.join(self.work, f"ref_{q}")
        sql = nexmark_sql(q, events, out)
        before = self.program_calls()
        with self.tier(False):
            dt = self.run_cli(sql, f"ref_{q}")
        if self.prove(f"ref_{q}", before, ()):
            raise SmokeFailure(f"{q}: the host-tier reference dispatched "
                               f"device programs")
        self.say(f"{q}: host tier (tpu.enabled=false) {events} events "
                 f"wall_s={dt:.1f}")
        return out

    def mesh(self, events: int, one_chip_out: str) -> None:
        n = self.device["count"]
        if n < 4:
            self.say(f"mesh: skipped, {n} device(s)")
            return
        from arroyo_tpu.parallel import sharded_state

        made = []
        init = sharded_state.ShardedAccumulator.__init__

        def recording_init(acc, *a, **k):
            init(acc, *a, **k)
            made.append(acc)

        # on chips `auto` must resolve to the device exchange and the
        # salted mesh tier by itself; a virtual CPU mesh resolves to
        # host_fed / single, so the rehearsal asks for what chips get
        tpu = ({"mesh_exchange": "device", "mesh_salted_tier": "mesh"}
               if self.args.rehearsal else {})
        sharded_state.ShardedAccumulator.__init__ = recording_init
        try:
            out = self.nexmark("q5", events, durable=True, label="q5-mesh",
                               mesh_devices=4, **tpu)
        finally:
            sharded_state.ShardedAccumulator.__init__ = init
        if not made:
            raise SmokeFailure("q5-mesh: no mesh accumulator was built")
        for acc in made:
            devs = {s.device for arr in acc.state
                    for s in arr.addressable_shards}
            self.say(f"q5-mesh: accumulator exchange={acc._exchange} "
                     f"shards on {sorted(d.id for d in devs)}")
            if acc._exchange != "device":
                raise SmokeFailure(
                    f"q5-mesh: exchange resolved to {acc._exchange!r}, "
                    f"not 'device'")
            if len(devs) != 4:
                raise SmokeFailure(
                    f"q5-mesh: state lives on {len(devs)} device(s), not 4")
        self.compare("q5-mesh vs one chip", out, one_chip_out)

    def run(self) -> None:
        a = self.args
        self.goldens()
        q5_out = self.nexmark("q5", a.events, durable=True)
        self.compare("q5", q5_out, self.host_reference("q5", a.events))
        side = 20_000 if a.rehearsal else 2_000_000
        for q in ("q1", "q7", "q8", "qu"):
            out = self.nexmark(q, side)
            (self.compare_updating if q == "qu" else self.compare)(
                q, out, self.host_reference(q, side))
        probed = [q for q in ("q7", "q8")
                  if all(p in self.calls[q] for p in JOIN)]
        self.say(f"device join probe reached by: {probed or 'neither'}")
        if not probed and not a.rehearsal:
            # at rehearsal size every join bin is below
            # tpu.device_join_min_rows; the join golden covers the probe
            raise SmokeFailure("neither q7 nor q8 reached the device join "
                               "probe (join.phase1/join.phase2)")
        self.mesh(a.events, q5_out)


def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--events", type=int, default=10_000_000,
                    help="q5 message_count (event_rate = events / 60): the "
                    "one cut a time limit may force, never below 2,000,000")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at 20,000 events; never a chip "
                    "result")
    args = ap.parse_args()
    if args.rehearsal:
        args.events = 20_000
    elif args.events < 2_000_000:
        ap.error("q5 is never cut below 2,000,000 events")
    t_start = time.monotonic()
    for k, v in NON_DEFAULTS.items():
        os.environ.setdefault(k, v)

    # first thing: the device. Through the package bootstrap, so x64 and
    # the compile cache are configured before anything can compile
    from arroyo_tpu.ops._jax import get_jax

    jax = get_jax()
    dev = jax.devices()[0]
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}", flush=True)
    print(f"compile cache: {cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
          f"), {cache_entries(cache_dir)} entries at start", flush=True)
    if args.rehearsal:
        if dev.platform != "cpu":
            print("--rehearsal is the CPU rehearsal; this process got "
                  f"platform={dev.platform}", file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform={dev.platform}); nothing was "
              "run. `--rehearsal` is the CPU rehearsal.", file=sys.stderr)
        return 2

    # the native slot directory, rebuilt from what git holds: an untracked
    # binary in the tree is never trusted
    from arroyo_tpu.ops.native import native_build_module

    cxx = subprocess.run(["g++", "--version"], capture_output=True,
                         text=True, check=True).stdout.splitlines()[0]
    built = native_build_module().build(force=True)
    print(f"native: rebuilt {os.path.relpath(built, HERE)} with {cxx}",
          flush=True)
    print("non-defaults: " + " ".join(
        f"{k}={os.environ[k]}" for k in NON_DEFAULTS)
        + f"; q5 events={args.events} (event_rate={args.events // 60})"
        + ("" if args.events == 10_000_000 or args.rehearsal
           else " — CUT from 10,000,000"), flush=True)

    smoke = Smoke(args, jax)
    try:
        smoke.run()
    finally:
        shutil.rmtree(smoke.work, ignore_errors=True)
    total = smoke.program_calls()
    smoke.say("all programs (compiles/compile_s, dispatches/host-side "
              "dispatch_s): " + "; ".join(
        f"{n} c={p['compiles']}/{p['compile_s']:.1f}s "
        f"d={p['dispatches']}/{p['dispatch_s']:.1f}s"
        for n, p in sorted(total.items())
        if p["compiles"] + p["dispatches"]))
    smoke.say(
        f"total: compiles={sum(p['compiles'] for p in total.values())} "
        f"compile_s={sum(p['compile_s'] for p in total.values()):.1f} "
        f"cache entries at end={cache_entries(cache_dir)} "
        f"wall_s={time.monotonic() - t_start:.0f}")
    smoke.say("runs: " + json.dumps(smoke.results))
    print(json.dumps({"ok": True, "device": smoke.device,
                      **({"rehearsal": True} if args.rehearsal else {})}),
          flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        code = 1
    except SystemExit as e:  # argparse
        code = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001 - top level: report, exit non-zero
        import traceback

        traceback.print_exc()
        code = 1
    # an embedded cluster ran here: leaked grpc-aio finalizers can hang a
    # normal interpreter exit
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
