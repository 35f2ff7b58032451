#!/usr/bin/env python
"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: `BENCHMARK.json` names the cell's
configuration and traffic; `configs/<config>.json` names the SQL text, the
plain reference, the settings away from the defaults and those that hold
for set-up alone (`setup_settings`, put back where the window starts);
`traffic/<traffic>.json` holds the mode and the rates; each metric is a
module `end_to_end/<name>.py` or `layer_metrics/<name>.py` with
`read(run)`. This file knows none of them by name.

The run happens in THIS process (the process that touches jax owns the
chip): SQL -> planner -> controller -> embedded worker -> device tier, the
path `python -m arroyo_tpu run q.sql --state-dir d` takes. Set-up builds
the native slot directory, starts the job durable on a fresh state
directory and feeds the traffic's warm-up; then the window measures for
exactly `--seconds` of wall (a timer closes it: counters, epochs and the
trace are read at the deadline, whatever the engine is busy with); then the
source ends, the job must FINISH, and outside the window the plain
reference is computed and compared.

Without a TPU the command exits non-zero. `--rehearsal` is the CPU
rehearsal tests use: tiny, tagged `platform=cpu, rehearsal`, never a
device number.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)



class BenchFailure(Exception):
    """The run cannot give a result; exit non-zero, print no result line."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchFailure(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell of BENCHMARK.json with its configuration and traffic."""

    def __init__(self, name: str, bench_file: str | None = None):
        self.bench = load_json(
            bench_file or os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchFailure(
                f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.config_dir = os.path.dirname(os.path.join(ROOT, cfg["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", f"{self.entry['traffic']}.json"))

    def metrics(self, group: str):
        """The cell's metrics of `end_to_end` or `per_layer`."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


class Run:
    """What one run recorded; the object every metric reader gets."""

    def __init__(self, cell: Cell, args, device: dict):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.rehearsal = bool(args.rehearsal)
        self.device = device
        self.feed = None
        self.setup_s = None
        self.window_s = None          # the window's measured length
        self.events_in_window = None
        self.start = {}               # counters at the window's start
        self.end = {}                 # ... and at its end
        self.spans = []               # program spans inside the window
        self.closes = []              # one dict per close due in the window
        self.trace = None             # trace_reduce.Summary of the window
        self.peaks = None             # the device kind's entry of peaks.json
        self.job_seconds = None
        self.t_job0 = None            # when `arroyo_tpu run` was called
        self.t_job0_ns = None         # ... on the wall clock
        self.epoch_at_start = 0       # completed epochs when the window began
        self.checkpoints = None       # epochs completed inside the window
        self.flow = {}                # {task: (rows received, rows sent)}
        self.stated_interval_s = None  # the cadence `correct` holds it to
        self.slow_barriers = False    # a control run: barriers at 1/4 of it


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, section by section."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


def put_back(live, before, over: dict) -> None:
    """Give every value that `over` set on the live configuration the value
    it has in `before`."""
    for k, v in over.items():
        if isinstance(v, dict):
            put_back(getattr(live, k), getattr(before, k), v)
        else:
            setattr(live, k, getattr(before, k))


def counters() -> dict:
    """The program's counters this benchmark reads, at one instant (the
    window's end reads them from the timer's thread: the registry's
    snapshot takes its locks)."""
    from arroyo_tpu.obs import device as obs_device
    from arroyo_tpu.parallel.sharded_state import MESH_STATS

    programs = {
        name: {"compiles": p.get("compiles", 0),
               "dispatches": p.get("dispatches", 0)}
        for name, p in obs_device.summary()["programs"].items()}
    return {"t": time.monotonic(), "t_ns": time.time_ns(),
            "cpu_s": time.process_time(),
            "programs": programs, "mesh": dict(MESH_STATS)}


def task_flow() -> dict:
    """{task: (rows received, rows sent)} from the program's per-task row
    counters (`arroyo_worker_messages_recv` / `_sent`), read once the job
    has ended. One job runs in this process."""
    from arroyo_tpu.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    flow: dict = {}
    for i, name in enumerate(("arroyo_worker_messages_recv",
                              "arroyo_worker_messages_sent")):
        for labels, value in snap.get(name, []):
            flow.setdefault(labels.get("task"), [0, 0])[i] = int(value)
    return {task: tuple(rs) for task, rs in flow.items()}


def last_epoch(state_dir: str) -> int:
    """The job's last completed checkpoint epoch (`latest.json`), 0 if none."""
    epochs = [load_json(p)["epoch"] for p in glob.glob(
        os.path.join(state_dir, "*", "latest.json"))]
    return max(epochs, default=0)


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def memory_peak(jax) -> int:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class Tracer:
    """A profiler trace of the whole window, from its start to its measured
    end (a close of these queries takes the host long enough that a short
    slice reads whatever phase it falls into), taken on a thread of its own
    so the engine's loop never waits for it."""

    def __init__(self, jax, run: Run, keep: str | None):
        self.jax = jax
        self.run = run
        self.dir = keep or tempfile.mkdtemp(prefix="bench_trace_")
        self.keep = bool(keep)
        self.thread = None
        self.error = None
        self.window_over = threading.Event()

    def start(self, at_most: float) -> None:
        self.thread = threading.Thread(
            target=self._take, args=(at_most,), name="bench-tracer",
            daemon=True)
        self.thread.start()

    def _take(self, at_most: float) -> None:
        try:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.window_over.wait(at_most)
            self.jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported by finish()
            self.error = e

    def finish(self):
        import trace_reduce

        if self.thread is None:
            raise BenchFailure("the trace was never started")
        self.thread.join(timeout=300)
        if self.thread.is_alive() or self.error is not None:
            raise BenchFailure(f"profiler trace failed: {self.error!r}")
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise BenchFailure(f"no .xplane.pb under {self.dir}")
        try:
            summary = trace_reduce.reduce(max(files, key=os.path.getmtime))
        except trace_reduce.NoDevicePlane:
            if not self.run.rehearsal:
                raise
            summary = None      # a CPU trace has no device plane
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return summary


def run_job(cell: Cell, run: Run, feed, jax, keep_trace) -> None:
    """Set-up, window and drain: one `arroyo_tpu run` in this process."""
    from arroyo_tpu import obs
    from arroyo_tpu.__main__ import main as cli
    from arroyo_tpu.config import update

    import feed as feed_mod

    feed_id = f"bench{os.getpid()}"
    feed_mod.FEEDS[feed_id] = feed
    feed_mod.register()
    work = tempfile.mkdtemp(prefix="bench_run_")
    state = os.path.join(work, "state")
    feed.published_epoch = lambda: last_epoch(state)
    tracer = Tracer(jax, run, keep_trace) if run.traced else None
    if tracer is not None:
        feed.annotate = jax.profiler.TraceAnnotation

    live = before = None        # the program's configuration, once set

    def window_start():
        # what the configuration sets for set-up alone ends here
        put_back(live, before, cell.config.get("setup_settings", {}))
        run.setup_s = time.monotonic() - T_PROCESS_START
        run.start = counters()
        run.epoch_at_start = last_epoch(state)
        if tracer is not None:
            tracer.start(at_most=2 * run.seconds + 60)

    def window_end():
        run.end = counters()
        run.checkpoints = last_epoch(state) - run.epoch_at_start
        if tracer is not None:
            tracer.window_over.set()

    feed.on_window_start = window_start
    feed.on_window_end = window_end
    settings = dict(cell.config.get("settings", {}))
    if run.rehearsal:
        for section, values in cell.config.get(
                "rehearsal_settings", {}).items():
            settings[section] = {**settings.get(section, {}), **values}
        settings["tpu"] = {**settings.get("tpu", {}),
                           "require_accelerator": False}
    # the cadence the run is held to: the configuration's stated interval
    # (a rehearsal's window is seconds long and states its own)
    pipeline = settings.setdefault("pipeline", {})
    run.stated_interval_s = float(
        pipeline.get("checkpointing", {}).get("interval")
        if run.rehearsal
        else cell.config["guarantees"]["checkpoint_interval_s"])
    if run.slow_barriers:
        pipeline["checkpointing"] = {**pipeline.get("checkpointing", {}),
                                     "interval": 4 * run.stated_interval_s}
    try:
        with open(os.path.join(cell.config_dir, cell.config["sql"])) as f:
            sql = f.read().replace("{feed}", feed_id)
        qfile = os.path.join(work, "query.sql")
        with open(qfile, "w") as f:
            f.write(sql)
        feed.start()
        t0 = run.t_job0 = time.monotonic()
        run.t_job0_ns = time.time_ns()
        with update(**settings) as before:
            pass                # the settings of the window, to go back to
        with update(**merged(
                settings, cell.config.get("setup_settings", {}))) as live:
            interval = float(live.pipeline.checkpointing.interval)
            if not run.slow_barriers and interval != run.stated_interval_s:
                raise BenchFailure(
                    f"checkpoint interval {interval} s is not the "
                    f"configuration's {run.stated_interval_s} s")
            rc = cli(["run", qfile, "--state-dir", state])
        run.job_seconds = time.monotonic() - t0
        if rc != 0:
            raise BenchFailure(f"`arroyo_tpu run` returned {rc}")
        if feed.t_window_end is None:
            raise BenchFailure("the job ended before the window did")
        run.window_s = feed.t_window_end - feed.t_window_start
        run.events_in_window = feed.n_window_end - feed.n_window_start
        run.spans = [
            s for s in obs.recorder().snapshot()
            if s.get("name") == "checkpoint.capture"]
        run.flow = task_flow()
        if tracer is not None:
            run.trace = tracer.finish()
    finally:
        feed.close()
        feed_mod.FEEDS.pop(feed_id, None)
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal (tests): tiny, never a chip result")
    ap.add_argument("--control", choices=("drop", "dup", "cadence"),
                    default=None,
                    help="a control run, which breaks one stated guarantee: "
                    "one event drawn from the seed among those the reference "
                    "reads (a bid, unless it declares more) is lost (drop) "
                    "or delivered twice (dup) at the source, or barriers go "
                    "out at a quarter of the stated cadence; `correct` must "
                    "come out false")
    ap.add_argument("--benchmark-file", default=None,
                    help="entries read from this file instead of "
                    "BENCHMARK.json (tests rehearse files that no cell "
                    "uses yet)")
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler's files in this directory")
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.benchmark_file)

    # the device first, through the program's bootstrap so that x64 and
    # the compile cache (JAX_COMPILATION_CACHE_DIR or <repo>/.jax_cache)
    # are configured before anything can compile
    from arroyo_tpu.ops._jax import get_jax

    jax = get_jax()
    device = device_info(jax)
    if args.rehearsal:
        if device["platform"] != "cpu":
            raise BenchFailure("--rehearsal is the CPU rehearsal; got "
                               f"platform={device['platform']}")
    elif device["platform"] != "tpu":
        raise BenchFailure(
            f"no TPU (platform={device['platform']}); nothing was run. "
            "`--rehearsal` is the CPU rehearsal.")
    if device["count"] < cell.chips:
        raise BenchFailure(f"cell {cell.name} needs {cell.chips} chips, "
                           f"jax has {device['count']}")
    tag = ("platform=cpu, rehearsal" if args.rehearsal else
           f"platform={device['platform']} kind={device['kind']!r} "
           f"count={device['count']}")

    def say(msg: str) -> None:
        print(f"[{tag}] {msg}", flush=True)

    from arroyo_tpu.ops.native import native_build_module

    native_build_module().build()

    import check
    import feed as feed_mod

    traffic = feed_mod.Traffic.from_dict(
        {**cell.traffic, **(cell.traffic.get("rehearsal", {})
                            if args.rehearsal else {})})
    reference = load_module("reference", cell.config["reference"])
    fault = None
    if args.control in ("drop", "dup"):
        fault = check.pick_fault(args.control, traffic, args.seed,
                                 check.reads_of(reference))
        say(f"control: {fault.kind} event {fault.event}")
    feed = feed_mod.Feed(traffic, args.seed, args.seconds, fault)
    feed.watermark_delay_ns = int(
        cell.config.get("watermark_delay_s", 1.0) * feed_mod.NS)
    feed.schedule = check.schedule_of(reference, feed)
    feed.long_stall_s = float(
        cell.config.get("setup_long_stall_s", feed.long_stall_s))
    run = Run(cell, args, device)
    run.feed = feed
    run.slow_barriers = args.control == "cadence"
    if run.slow_barriers:
        say("control: barriers at a quarter of the stated cadence")
    if not args.rehearsal:
        run.peaks = load_json(os.path.join(HERE, "peaks.json")).get(
            device["kind"])
        if run.peaks is None:
            raise BenchFailure(
                f"device kind {device['kind']!r} is not in peaks.json")

    run_job(cell, run, feed, jax, args.keep_trace)
    device["memory_peak_bytes"] = memory_peak(jax)
    say(f"set-up: to the source's first poll "
        f"{feed.t_first_poll - T_PROCESS_START:.2f} s (imports, device, "
        f"native build, plan, job start), warm-up feed "
        f"{feed.t_warm_fed - feed.t_first_poll:.2f} s, until its last close "
        f"arrived {feed.t_window_start - feed.t_warm_fed:.2f} s; after the "
        f"window {run.job_seconds - (feed.t_window_end - run.t_job0):.2f} s")
    say(f"setup_s={run.setup_s:.3f} window_s={run.window_s:.3f} "
        f"job_s={run.job_seconds:.1f} events_in_window="
        f"{run.events_in_window} starved_polls={feed.starved} "
        f"checkpoints={run.checkpoints} "
        f"longest_loop_stall_s={feed.longest_stall_s:.2f}")

    verdict = check.judge(run, reference, cell.config, say)
    run.closes = verdict.closes

    group = "per_layer" if args.trace else "end_to_end"
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in cell.metrics(group):
        if args.rehearsal and m["source"] == "device_trace":
            continue            # a CPU run gives no device number
        value = load_module(
            "layer_metrics" if args.trace else "end_to_end",
            m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": verdict.correct, "attempted": verdict.attempted,
            "failed": verdict.failed, "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        line["breakdown"] = run.trace.breakdown()
    if args.rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


def entry(argv=None) -> None:
    """`main`, then out: a failure is a non-zero exit and no result line."""
    try:
        code = main(argv)
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        code = 2
    except SystemExit as e:  # argparse
        code = e.code if isinstance(e.code, int) else 2
    except BaseException:  # noqa: BLE001 - top level: report, exit non-zero
        import traceback

        traceback.print_exc()
        code = 1
    # an embedded cluster ran here: leaked grpc-aio finalizers can hang a
    # normal interpreter exit (chip_smoke.py and tools/chaos_drill.py do
    # the same)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    entry()
