"""The phase ledger (`arroyo_tpu/obs/timeline.py`, ISSUE 24): the `phase`
primitive and its nesting, the bucket store that answers for a whole
benchmark window, what the Perfetto export carries, the profiler
annotation, and the counts `InstrumentedJit` books at the device
boundary."""

import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from arroyo_tpu import obs
from arroyo_tpu.obs import timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    obs.reset()
    yield
    obs.reset()


def test_nested_phases_self_time_is_duration_less_children():
    with timeline.phase("process", job="j", task="3-0", annotate=False):
        time.sleep(0.01)
        with timeline.phase("dir.assign", n=100):
            time.sleep(0.02)
        with timeline.phase("close.emit") as outer:
            with timeline.phase("emit", n=7):
                time.sleep(0.01)
            t0 = time.perf_counter()
            time.sleep(0.005)
            waited = time.perf_counter() - t0
            timeline.note("queue.wait", waited)      # a duration in hand
            outer.n = 7
    e = {x["phase"]: x for x in timeline.snapshot("j")}
    # leaves inherit the enclosing phase's task and job
    assert {x["task"] for x in e.values()} == {"3-0"}
    assert e["dir.assign"]["self"] == e["dir.assign"]["dur"] >= 0.02e6
    assert e["close.emit"]["n"] == 7 and e["dir.assign"]["n"] == 100
    assert e["close.emit"]["self"] == pytest.approx(
        e["close.emit"]["dur"] - e["emit"]["dur"] - waited * 1e6, abs=1.0)
    assert e["process"]["self"] == pytest.approx(
        e["process"]["dur"] - e["dir.assign"]["dur"] - e["close.emit"]["dur"],
        abs=1.0)
    assert 0.01e6 <= e["process"]["self"] < e["process"]["dur"]
    t = timeline.phase_totals("j")
    assert t["process"]["self_s"] == pytest.approx(
        e["process"]["self"] / 1e6, abs=1e-5)
    # self times of one task's phases add up to the enclosing duration
    assert sum(v["self_s"] for v in t.values()) == pytest.approx(
        t["process"]["total_s"], abs=1e-4)


@pytest.mark.parametrize("ring", [64, 100_000])
def test_totals_equal_a_brute_force_sum_ring_overflow_or_not(
        monkeypatch, ring):
    """50,000 synthetic entries across 60 s of a fake wall clock: the
    buckets answer for any interval, whatever the ring still holds."""
    from arroyo_tpu.config import update

    rng = np.random.default_rng(24)
    t_base = 1_790_000_000.0
    ends = np.sort(rng.uniform(0.0, 60.0, 50_000)) + t_base
    durs = rng.uniform(1e-6, 5e-3, len(ends))
    names = rng.choice(["dir.assign", "agg.pack", "close.combine"], len(ends))
    tasks = rng.choice(["3-0", "6-0"], len(ends))
    ns = rng.integers(0, 8192, len(ends))
    clock = iter(ends)
    monkeypatch.setattr(timeline, "time", types.SimpleNamespace(
        time=lambda: next(clock), perf_counter=time.perf_counter))
    with update(obs={"timeline_events": ring}):
        timeline.clear()
        for name, dur, task, n in zip(names, durs, tasks, ns):
            timeline.note(str(name), float(dur), job="syn", task=str(task),
                          n=int(n))
        assert len(timeline.snapshot()) == min(ring, len(ends))

        def brute(t0, t1, task=None):
            b = timeline.BUCKET_US
            lo, hi = -(-int(t0 * 1e6) // b), -(-int(t1 * 1e6) // b)
            bucket = (ends * 1e6).astype(np.int64) // b
            m = (bucket >= lo) & (bucket < hi)
            if task is not None:
                m &= tasks == task
            return {str(p): (int((m & (names == p)).sum()),
                             float(durs[m & (names == p)].sum()),
                             float(durs[m & (names == p)].max()),
                             int(ns[m & (names == p)].sum()))
                    for p in np.unique(names[m])}

        for t0, t1, task in [(t_base, t_base + 60.5, None),
                             (t_base + 7.3, t_base + 52.3, None),
                             (t_base + 7.3, t_base + 52.3, "6-0"),
                             (t_base + 30.1, t_base + 30.9, "3-0")]:
            got = timeline.totals(t0 * 1e6, t1 * 1e6, task=task)
            want = brute(t0, t1, task)
            assert set(got) == set(want)
            for p, (count, total, longest, n) in want.items():
                assert got[p]["count"] == count and got[p]["n"] == n
                assert got[p]["total_s"] == pytest.approx(total, abs=2e-6)
                assert got[p]["max_s"] == pytest.approx(longest, abs=2e-6)
        # the covered length is the interval's, within one bucket
        whole = timeline.totals(t_base * 1e6, (t_base + 60.5) * 1e6)
        assert sum(v["count"] for v in whole.values()) == len(ends)
        assert timeline.phase_totals("syn") == whole


def test_n_and_key_survive_the_perfetto_export():
    with timeline.phase("close.combine", job="jp", task="3-0", n=61_234,
                        key=1_790_000_010_000_000_000):
        with timeline.phase("agg.read", n=5, annotate=False):
            pass
    doc = obs.perfetto_trace([])
    ev = {e["name"]: e for e in doc["traceEvents"] if e.get("cat") == "phase"}
    args = ev["phase.close.combine"]["args"]
    assert args["n"] == 61_234 and args["key"] == 1_790_000_010_000_000_000
    assert args["task"] == "3-0" and args["job"] == "jp"
    assert args["self"] == pytest.approx(
        ev["phase.close.combine"]["dur"] - ev["phase.agg.read"]["dur"],
        abs=1.0)
    assert ev["phase.agg.read"]["args"]["n"] == 5


def test_expunge_job_and_reset_clear_the_bucket_store():
    for job in ("keep", "drop"):
        for _ in range(5):
            timeline.note("process", 0.001, job=job, task="1-0", n=3)
    assert timeline.phase_totals("drop")["process"]["count"] == 5
    obs.expunge_job("drop")
    assert timeline.phase_totals("drop") == {}
    assert timeline.snapshot("drop") == []
    now = time.time() * 1e6
    kept = timeline.totals(now - 60e6, now + 1e6)
    assert kept["process"]["count"] == 5 and kept["process"]["n"] == 15
    obs.reset()
    assert timeline.phase_totals() == {} and timeline.snapshot() == []
    assert timeline.totals(now - 60e6, now + 1e6) == {}


def test_the_bucket_store_is_bounded_by_cells(monkeypatch):
    monkeypatch.setattr(timeline, "_MAX_CELLS", 50)
    t = [1_790_000_000.0]

    def tick():
        t[0] += 0.3                 # every entry lands in a new bucket
        return t[0]

    monkeypatch.setattr(timeline, "time", types.SimpleNamespace(
        time=tick, perf_counter=time.perf_counter))
    for i in range(400):
        timeline.note(f"p{i % 4}", 0.001, job="b", task="1-0")
    assert timeline._N_CELLS <= 52
    assert sum(len(c) for _b, c in timeline._BUCKETS) == timeline._N_CELLS
    assert sum(v["count"] for v in timeline.phase_totals("b").values()) <= 52


class _SpyAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`."""

    session = False
    made = []

    def __init__(self, name):
        self.name = name
        self.entered = self.exited = False
        _SpyAnnotation.made.append(self)

    @staticmethod
    def is_enabled():
        return _SpyAnnotation.session

    def __enter__(self):
        self.entered = True

    def __exit__(self, *exc):
        self.exited = True


def test_a_phase_annotates_only_inside_a_profiler_session(monkeypatch):
    monkeypatch.setattr(timeline, "_TRACEME", _SpyAnnotation)
    _SpyAnnotation.made.clear()
    _SpyAnnotation.session = False
    with timeline.phase("dir.assign", job="a", n=1):
        pass
    assert _SpyAnnotation.made == []             # no session, no object
    _SpyAnnotation.session = True
    with timeline.phase("process", job="a", annotate=False):
        with timeline.phase("dir.assign", n=1):
            pass
        with timeline.phase("queue.wait", annotate=False):
            pass
    # leaves only: an enclosing phase or a wait would cover every idle gap
    assert [(a.name, a.entered, a.exited) for a in _SpyAnnotation.made] == [
        ("dir.assign", True, True)]
    assert timeline.phase_totals("a")["dir.assign"]["count"] == 2


def test_the_real_annotation_is_inert_without_a_session():
    import jax

    assert jax.profiler.TraceAnnotation.is_enabled() is False
    assert timeline._annotation() is None
    assert timeline._TRACEME is jax.profiler.TraceAnnotation


def test_a_phase_does_not_import_jax_in_a_process_that_has_not():
    code = (
        "import sys\n"
        "from arroyo_tpu.obs import timeline\n"
        "with timeline.phase('process', job='j', task='1-0', "
        "annotate=False):\n"
        "    with timeline.phase('dir.assign', n=3):\n"
        "        pass\n"
        "assert timeline.phase_totals('j')['dir.assign']['n'] == 3\n"
        "assert timeline._TRACEME is None\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_disabled_ledger_books_nothing():
    from arroyo_tpu.config import update

    with update(obs={"timeline_events": 0}):
        with timeline.phase("dir.assign", job="off", n=1) as ph:
            assert timeline.open_phase() is None
        assert ph.n == 1
        timeline.note("process", 0.001, job="off")
    assert timeline.phase_totals("off") == {}


def test_real_and_padded_rows_are_booked_at_the_device_boundary():
    """`InstrumentedJit(rows=, rung=)`: per program in the summary, and on
    the ledger entry of the enclosing `agg.enqueue`."""
    from arroyo_tpu.obs import device as obs_device
    from arroyo_tpu.ops.aggregates import Accumulator, AggSpec

    obs_device.reset()
    before = dict(obs_device.summary()["programs"].get("agg.update", {}))
    acc = Accumulator([AggSpec("count", None, "n")], capacity=4096)
    sizes = [5, 700, 1024, 1500]
    with timeline.phase("process", job="rows", task="3-0", annotate=False):
        for n in sizes:
            acc.update(np.arange(n, dtype=np.int64) % 100, {})
        acc.reset_slots(np.arange(100, dtype=np.int64))
        got = acc.gather(np.arange(100, dtype=np.int64))
    assert int(got[0].sum()) == 0                 # reset before the read
    t = timeline.phase_totals("rows")
    assert t["agg.enqueue"]["count"] == len(sizes)
    assert t["agg.enqueue"]["n"] == sum(sizes) == t["agg.pack"]["n"]
    padded = t["agg.enqueue"]["padded"]
    assert padded >= sum(sizes) and padded % 256 == 0
    assert t["agg.read"]["n"] == 100 and t["agg.reset"]["count"] == 1
    prog = obs_device.summary()["programs"]["agg.update"]
    assert prog["rows"] - before.get("rows", 0) == sum(sizes)
    assert prog["padded_rows"] - before.get("padded_rows", 0) == padded
    # `dispatch_s` is the host's enqueue time: it fits inside the phase
    assert t["dispatch"]["total_s"] <= (
        t["agg.enqueue"]["total_s"] + t["agg.gather"]["total_s"]
        + t["agg.reset"]["total_s"] + 1e-3)


def test_the_audit_fingerprint_of_a_sent_batch_is_a_leaf_inside_emit():
    """`audit.attest` (ISSUE 26): the conservation ledger's fingerprint of
    a batch, booked where a sender attests it (`EdgeSender._send_data`,
    inside `emit`; the receiver books the same name before `process`), so
    that `emit`'s self time is partition + put again."""
    import asyncio

    import pyarrow as pa

    from arroyo_tpu.graph.logical import EdgeType
    from arroyo_tpu.operators.collector import Collector, EdgeSender
    from arroyo_tpu.operators.queues import BatchQueue
    from arroyo_tpu.schema import StreamSchema

    schema = StreamSchema.from_fields([("k", pa.int64())])
    batch = pa.RecordBatch.from_arrays(
        [pa.array(range(100)), pa.array([0] * 100, pa.timestamp("ns"))],
        schema=schema.schema)

    async def send():
        queues = [BatchQueue(8, 1 << 20), BatchQueue(8, 1 << 20)]
        queues[0].audit_edge = "1:0->2:0"   # the wiring stamped one of two
        out = Collector([EdgeSender(EdgeType.FORWARD, schema, [q])
                         for q in queues], task_id="1-0", job_id="att")
        await out.collect(batch)

    asyncio.run(send())
    t = timeline.phase_totals()     # the job is the ambient one: none here
    assert t["audit.attest"]["count"] == 1 and t["audit.attest"]["n"] == 100
    assert t["emit"]["n"] == 100
    assert t["emit"]["self_s"] == pytest.approx(
        t["emit"]["total_s"] - t["audit.attest"]["total_s"], abs=3e-6)
    by_task = timeline.totals(task="1-0")
    assert by_task["audit.attest"]["count"] == 1     # the sender's task


def test_a_computed_fingerprint_is_a_count_inside_audit_attest():
    """`audit.fp` (ISSUE 27): one `note` with no duration per fingerprint
    actually computed, inside the `audit.attest` that observed the batch
    (the sender's inside `emit`). A fan-out of one batch object to two
    stamped edges is two observations and one computation; `audit.attest`
    keeps its count, its rows and its seconds."""
    import asyncio

    import pyarrow as pa

    from arroyo_tpu.graph.logical import EdgeType
    from arroyo_tpu.obs import audit
    from arroyo_tpu.operators.collector import Collector, EdgeSender
    from arroyo_tpu.operators.queues import BatchQueue
    from arroyo_tpu.schema import StreamSchema

    schema = StreamSchema.from_fields([("k", pa.int64())])
    batch = pa.RecordBatch.from_arrays(
        [pa.array(range(100)), pa.array([0] * 100, pa.timestamp("ns"))],
        schema=schema.schema)

    async def send():
        queues = [BatchQueue(8, 1 << 20), BatchQueue(8, 1 << 20)]
        queues[0].audit_edge, queues[1].audit_edge = "1:0->2:0", "1:0->5:0"
        out = Collector([EdgeSender(EdgeType.FORWARD, schema, [q])
                         for q in queues], task_id="1-0", job_id="fp")
        await out.collect(batch)
        return [await q.recv() for q in queues]

    got = asyncio.run(send())
    assert got[0] is batch and got[1] is batch
    t = timeline.totals(task="1-0")     # inherited from the enclosing `emit`
    assert t["audit.attest"]["count"] == 2 and t["audit.attest"]["n"] == 200
    assert t["audit.fp"]["count"] == 1 and t["audit.fp"]["n"] == 100
    assert t["audit.fp"]["total_s"] == 0.0
    # a count takes nothing from the self time of the phase around it
    assert t["audit.attest"]["self_s"] == t["audit.attest"]["total_s"] > 0
    assert t["emit"]["self_s"] == pytest.approx(
        t["emit"]["total_s"] - t["audit.attest"]["total_s"], abs=3e-6)
    status = audit.status()
    assert (status["fingerprints_observed"],
            status["fingerprints_computed"]) == (2, 1)


def test_the_jitted_function_names_the_trace_reduction_maps():
    """`benchmark/trace_reduce.py` finds the device programs by the module
    names XLA derives from these four functions' names: a rename here
    makes `agg_update_call_us` and the breakdown read null."""
    import jax.numpy as jnp

    from arroyo_tpu.ops import device_join
    from arroyo_tpu.ops.aggregates import Accumulator, AggSpec

    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import trace_reduce
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))

    def module_name(jitted, *args):
        text = jitted.lower(*args).as_text()
        return text.split("module @", 1)[1].split(" ", 1)[0]

    acc = Accumulator([AggSpec("count", None, "n")], capacity=1024)
    slots = jnp.zeros(256, dtype=jnp.int64)
    acc.reset_slots(np.arange(4, dtype=np.int64))    # builds _reset_fn
    phase1, phase2_at = device_join._build_fns()
    mat = np.zeros((1024, 1), dtype=np.int64)
    order, lo, offs = phase1.fn(mat, mat, np.int64(1), np.int64(1))
    phase2_at(1024, order, lo, offs)
    phase2 = phase2_at.__closure__  # the size-keyed cache lives in there
    impl = next(c.cell_contents for c in phase2
                if isinstance(c.cell_contents, dict))[1024]
    names = {
        module_name(acc._update_fn.fn, acc.state, slots, slots): "agg.update",
        module_name(acc._reset_fn.fn, acc.state, slots): "agg.reset",
        module_name(phase1.fn, mat, mat, np.int64(1), np.int64(1)):
            "join.phase1",
        module_name(impl.fn, order, lo, offs): "join.phase2",
    }
    assert names == trace_reduce.MODULE_NAMES
    assert {acc._update_fn.program, acc._reset_fn.program, phase1.program,
            impl.program} == set(trace_reduce.MODULE_NAMES.values())


# -- ISSUE 38: thread CPU beside wall time, the loop's own time, every runner
# item inside an enclosure of its task, a compile with a name ----------------


def _spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sum(range(200))


@pytest.fixture
def every_edge(monkeypatch):
    """The thread CPU clock read at every edge of a phase, whatever a
    reading costs on this machine (`timeline.cpu_every_s`)."""
    monkeypatch.setattr(timeline, "_CPU_EVERY_S", 0.0)


def test_a_dear_cpu_clock_is_read_on_a_grid_and_the_seconds_still_add_up(
        monkeypatch):
    """Where a reading of the thread's CPU clock is dear (the chip's host:
    6 us, steps of 10 ms) a thread reads it on a grid of its own: far fewer
    readings than edges, a frame's children and its self CPU still
    partition its own exactly, a phase longer than the grid reads true and
    a kind of short phases gets the CPU of the stretch it runs in."""
    import threading

    reads = []
    real = time.thread_time

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(time, "thread_time", counted)
    monkeypatch.setattr(timeline, "_CPU_EVERY_S", 0.002)
    clock = time.pthread_getcpuclockid(threading.get_ident())
    c0 = time.clock_gettime(clock)
    with timeline.phase("process", job="dear", annotate=False):
        for _ in range(300):
            with timeline.phase("win.keys"):
                _spin(0.0004)
            with timeline.phase("agg.pack"):
                _spin(0.0008)
        for _ in range(5):
            with timeline.phase("agg.read"):
                time.sleep(0.03)            # a wait, ten grid steps long
            with timeline.phase("serve.seal"):
                _spin(0.03)                 # and work as long, no edge in it
    c1 = time.clock_gettime(clock)
    t = timeline.phase_totals("dear")
    edges = 2 * sum(v["count"] for v in t.values())
    assert len(reads) <= t["process"]["total_s"] / 0.001 + 2
    assert len(reads) < edges / 2
    # exactly: the enclosure's CPU is its children's and its own
    assert t["process"]["cpu_s"] == pytest.approx(
        t["win.keys"]["cpu_s"] + t["agg.pack"]["cpu_s"]
        + t["agg.read"]["cpu_s"] + t["serve.seal"]["cpu_s"]
        + t["process"]["self_cpu_s"], abs=2e-5)
    assert t["process"]["cpu_s"] == pytest.approx(c1 - c0, abs=0.004)
    # a long phase reads true to a grid step an edge, waiting or working:
    # its CPU is its own, not the enclosure's that reads the clock next
    assert t["agg.read"]["total_s"] > 0.15 > 0.03 > t["agg.read"]["cpu_s"]
    assert t["serve.seal"]["cpu_s"] == pytest.approx(
        t["serve.seal"]["total_s"], abs=5 * 2 * 0.003)
    assert t["process"]["self_cpu_s"] < 0.04
    # the short leaves share the stretch's CPU by the time each holds: the
    # grid favours neither the long one nor the one that comes first
    leaves = t["win.keys"]["cpu_s"] + t["agg.pack"]["cpu_s"]
    assert leaves == pytest.approx(
        t["win.keys"]["total_s"] + t["agg.pack"]["total_s"], rel=0.1)
    assert t["agg.pack"]["cpu_s"] / leaves == pytest.approx(2 / 3, abs=0.12)


def test_a_sleeping_phase_books_wall_and_a_spinning_one_books_both(every_edge):
    with timeline.phase("asleep", job="cpu"):
        time.sleep(0.05)
    with timeline.phase("spinning", job="cpu"):
        _spin(0.05)
    t = timeline.phase_totals("cpu")
    assert t["asleep"]["total_s"] >= 0.05 and t["asleep"]["cpu_s"] < 0.01
    assert t["spinning"]["total_s"] >= 0.05
    # on a core all the while, but for what the OS took away
    assert 0.03 < t["spinning"]["cpu_s"] <= t["spinning"]["total_s"] * 1.01
    ring = {e["phase"]: e for e in timeline.snapshot("cpu")}
    assert ring["spinning"]["cpu"] == pytest.approx(
        t["spinning"]["cpu_s"] * 1e6, abs=1.0)


def test_self_cpu_is_cpu_less_the_childrens_and_a_note_carries_its_own(every_edge):
    with timeline.phase("process", job="nest", task="3-0", annotate=False):
        _spin(0.04)
        with timeline.phase("dir.assign"):
            _spin(0.03)
        time.sleep(0.02)                 # wall the enclosure does not burn
        timeline.note("dispatch", 0.004, cpu_s=0.003)
        timeline.note("dir.new", 0.0, n=5)           # a count: no CPU
        timeline.note("queue.wait", 0.001)           # a wait books none
        with timeline.phase("emit", annotate=False):
            # a wait that awaits: the thread runs other tasks' work in it,
            # which is neither the wait's CPU nor its parent's own
            with timeline.phase("queue.wait", annotate=False):
                _spin(0.03)
    t = timeline.phase_totals("nest")
    assert t["queue.wait"]["total_s"] > 0.03 > 0.01 > t["emit"]["self_cpu_s"]
    assert t["dispatch"]["cpu_s"] == t["dispatch"]["self_cpu_s"] == 0.003
    assert t["dir.new"]["cpu_s"] == t["queue.wait"]["cpu_s"] == 0.0
    assert t["dir.assign"]["self_cpu_s"] == t["dir.assign"]["cpu_s"] > 0.02
    assert t["process"]["self_cpu_s"] == pytest.approx(
        t["process"]["cpu_s"] - t["dir.assign"]["cpu_s"] - 0.003
        - t["emit"]["cpu_s"], abs=1e-5)
    assert 0.02 < t["process"]["self_cpu_s"] < t["process"]["self_s"] - 0.015
    doc = obs.perfetto_trace([])
    args = {e["name"]: e["args"] for e in doc["traceEvents"]
            if e.get("cat") == "phase"}
    assert args["phase.dispatch"]["cpu"] == pytest.approx(3000.0)
    assert args["phase.dir.assign"]["cpu"] > 20_000


def _run_loop(main):
    """`main` on a loop the program's one factory made, with the accounting
    pump ticking: what every role of `__main__.py` runs under."""
    import asyncio

    from arroyo_tpu.obs import attribution

    async def pumped():
        attribution.ensure_pump()
        try:
            return await main()
        finally:
            attribution.release_pump()

    return asyncio.run(pumped(), loop_factory=timeline.event_loop)


def test_every_role_of_the_cli_runs_on_the_one_timed_loop():
    import inspect

    from arroyo_tpu import __main__ as cli

    src = inspect.getsource(cli)
    assert src.count("asyncio.run(") == 1       # `_serve`, for all six
    assert src.count("return _serve(_") == 6
    assert "loop_factory=event_loop" in inspect.getsource(cli._serve)
    loop = timeline.event_loop()
    try:
        assert isinstance(loop._selector, timeline.TimedSelector)
    finally:
        loop.close()


def test_a_select_that_may_block_books_loop_idle_and_a_poll_books_nothing():
    sel = timeline.TimedSelector()
    try:
        assert sel.select(0) == [] and sel.select(-1) == []
        assert "loop.idle" not in timeline.totals()
        t0 = time.perf_counter()
        assert sel.select(0.05) == []
        waited = time.perf_counter() - t0
    finally:
        sel.close()
    idle = timeline.totals()["loop.idle"]
    assert idle["count"] == 1 and idle["cpu_s"] == 0.0
    assert 0.05 <= idle["total_s"] <= waited
    assert sel.idle_s == pytest.approx(idle["total_s"], abs=1e-5)
    assert "loop.idle" in timeline.WAITS and "loop.run" in timeline.ENCLOSING


def test_loop_run_is_wall_less_idle_with_the_loop_threads_own_cpu(every_edge):
    """Two seconds of a loop that spins 30 ms and sleeps 20 ms in turn:
    idle + run = the wall, and `loop.run`'s CPU is the loop thread's own
    clock read from outside (`pthread_getcpuclockid`). Every band is taken
    from what the run itself measured (its rounds, the CPU of its spins),
    so it holds whatever share of a core the thread was given."""
    import asyncio
    import threading

    from arroyo_tpu.config import update

    clock = time.pthread_getcpuclockid(threading.get_ident())

    async def busy():
        await asyncio.sleep(0.3)            # the pump's first tick: the mark
        edges = [(time.perf_counter(), time.clock_gettime(clock))]
        while time.perf_counter() - edges[0][0] < 2.0:
            c = time.thread_time()
            _spin(0.03)
            spun.append(time.thread_time() - c)
            await asyncio.sleep(0.02)
        # up to the next tick, so that the last stretch is booked
        n = timeline.totals()["loop.run"]["count"]
        while timeline.totals()["loop.run"]["count"] == n:
            await asyncio.sleep(0.005)
        edges.append((time.perf_counter(), time.clock_gettime(clock)))
        return edges

    spun = []                               # each round's spin, in CPU
    t0_us = time.time() * 1e6
    with update(obs={"loop_lag_interval": 0.1}):
        (w0, c0), (w1, c1) = _run_loop(busy)
    t = timeline.totals()
    run, idle = t["loop.run"], t["loop.idle"]
    # from the first tick on: the 0.3 s before it, nearly all idle, less
    lead = 0.3
    assert run["total_s"] + idle["total_s"] == pytest.approx(
        w1 - w0 + lead, abs=0.25)
    # a round sleeps 20 ms at least, however late it is woken
    assert 0.02 * len(spun) - 0.1 < idle["total_s"] - lead
    assert idle["count"] >= len(spun) > 20
    assert run["cpu_s"] <= run["total_s"] * 1.01
    assert run["self_cpu_s"] == run["cpu_s"] and run["self_s"] == run["total_s"]
    # `n` = the loop's turns: each round takes two at least (the sleep's
    # timer, then the task), and no select goes uncounted
    assert 2 * len(spun) <= run["n"] and idle["count"] <= run["n"] + 2
    # the thread's clock from outside covers a little more than the ticks
    # inside [w0, w1]: the first tick's stretch began before w0. CPU
    # seconds, so the same on a starved thread as on one with a core
    assert run["cpu_s"] == pytest.approx(c1 - c0, abs=0.03)
    # and it is the spins' CPU and the loop's own turns around them
    assert sum(spun) <= run["cpu_s"] <= sum(spun) + 0.4
    assert t0_us < time.time() * 1e6


def test_a_phase_on_a_worker_thread_stays_out_of_the_loops_sums(every_edge):
    """`asyncio.to_thread` copies the context: a phase booked there keeps
    its own CPU in `cpu_s`, none in `self_cpu_s` (identity 3 subtracts only
    the loop thread's), and gives its enclosing frame wall and no CPU."""
    import asyncio

    from arroyo_tpu.config import update

    def stored():
        with timeline.phase("storage.put"):
            _spin(0.1)

    async def flush():
        await asyncio.sleep(0.25)           # a tick names the loop's thread
        with timeline.phase("ckpt.capture", job="thr", task="3-0",
                            annotate=False):
            await asyncio.to_thread(stored)
        with timeline.phase("agg.pack", job="thr", task="3-0"):
            _spin(0.05)

    with update(obs={"loop_lag_interval": 0.1}):
        _run_loop(flush)
    t = timeline.phase_totals("thr")
    put, capture, pack = t["storage.put"], t["ckpt.capture"], t["agg.pack"]
    assert put["cpu_s"] > 0.06 and put["self_cpu_s"] == 0.0
    assert pack["self_cpu_s"] == pack["cpu_s"] > 0.03
    # the worker's CPU is no child of the loop thread's frame
    assert capture["self_s"] == pytest.approx(
        capture["total_s"] - put["total_s"], abs=1e-5)
    assert capture["self_cpu_s"] == capture["cpu_s"] < 0.03


def test_flush_resolve_is_a_wait_that_keeps_its_worker_threads_cpu(
        every_edge, monkeypatch):
    """ISSUE 39: what a capture left to the flush is resolved on the storage
    thread inside `flush.resolve`: one of `timeline.WAITS` (seconds beside
    the loop, not the loop's named work) that, unlike a wait on the loop,
    carries its thread's `cpu_s` (and, a worker's, no `self_cpu_s`). The
    three readers that sum the loop's named work (`engine_unnamed_pct` of
    BENCHMARK.json, and the two owed entries that read CPU) read the same
    with such entries in the window and without."""
    import asyncio
    import importlib
    import json

    from arroyo_tpu.config import update

    assert "flush.resolve" in timeline.WAITS

    def resolve():
        with timeline.phase("flush.resolve", task="3-0", key=7, n=1_000,
                            annotate=False):
            _spin(0.15)

    async def barrier():
        await asyncio.sleep(0.25)           # a tick names the loop's thread
        with timeline.phase("process", job="fr", task="3-0",
                            annotate=False):
            with timeline.phase("ckpt.capture", key=7, annotate=False):
                with timeline.phase("serve.seal", n=1_000, annotate=False):
                    _spin(0.01)
            flush = asyncio.ensure_future(asyncio.to_thread(resolve))
            with timeline.phase("agg.pack"):
                _spin(0.1)
            with timeline.phase("queue.wait", annotate=False):
                await flush
        await asyncio.sleep(0.25)           # the last tick books `loop.run`

    with update(obs={"loop_lag_interval": 0.1}):
        _run_loop(barrier)
    t = timeline.phase_totals()
    res = t["flush.resolve"]
    assert res["n"] == t["serve.seal"]["n"] == 1_000   # the engagement share
    assert res["cpu_s"] > 0.04 and res["self_cpu_s"] == 0.0
    assert t["queue.wait"]["cpu_s"] == 0.0             # a wait on the loop
    # the worker's seconds are no child of the loop thread's enclosure
    assert t["process"]["self_cpu_s"] < 0.05

    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    ledger_window = importlib.import_module("ledger_window")
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "owed_entries.json")) as f:
        owed = {m["name"] for m in json.load(f)["per_layer"]}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        held = {m["name"] for m in json.load(f)["per_layer"]}
    names = ["engine_unnamed_pct", "engine_unnamed_cpu_pct",
             "host_leaf_offcore_pct"]
    assert names[0] in held and set(names[1:]) <= owed
    run = types.SimpleNamespace(start={"t_ns": 0}, end={"t_ns": 2 ** 62},
                                window_s=1.0)

    def read():
        return [importlib.import_module(f"layer_metrics.{n}").read(run)
                for n in names]

    with_entries = read()
    assert "flush.resolve" in ledger_window.totals(run)
    real = ledger_window.totals
    monkeypatch.setattr(ledger_window, "totals", lambda r: {
        p: v for p, v in real(r).items() if p != "flush.resolve"})
    assert read() == with_entries and None not in with_entries


def test_a_fresh_signature_books_compile_and_a_seen_one_dispatch(every_edge):
    import jax
    import jax.numpy as jnp

    from arroyo_tpu.obs import device as obs_device

    fn = obs_device.InstrumentedJit(
        "loopclock.double", jax.jit(lambda x: x * 2))
    with timeline.phase("agg.enqueue", job="jit", task="3-0"):
        fn(jnp.arange(8))
    t = timeline.phase_totals("jit")
    assert t["compile"]["count"] == 1 and "dispatch" not in t
    assert t["agg.enqueue"]["self_s"] == pytest.approx(
        t["agg.enqueue"]["total_s"] - t["compile"]["total_s"], abs=1e-5)
    entry = next(e for e in timeline.snapshot("jit")
                 if e["phase"] == "compile")
    assert entry["key"] == "loopclock.double" and entry["task"] == "3-0"
    with timeline.phase("agg.enqueue", job="jit", task="3-0"):
        fn(jnp.arange(8))
    t = timeline.phase_totals("jit")
    assert t["compile"]["count"] == 1 and t["dispatch"]["count"] == 1
    assert 0 < t["dispatch"]["cpu_s"] <= t["dispatch"]["total_s"] * 1.01 + 1e-4
    with timeline.phase("agg.enqueue", job="jit", task="3-0"):
        fn(jnp.arange(16))                  # a new shape: a compile again
    t = timeline.phase_totals("jit")
    assert t["compile"]["count"] == 2 and t["dispatch"]["count"] == 1
    assert "compile" in timeline.DEVICE_WAITS


def test_a_disabled_ledger_turns_every_part_off_the_timed_select_too():
    import asyncio

    import jax
    import jax.numpy as jnp

    from arroyo_tpu.config import update
    from arroyo_tpu.obs import device as obs_device

    fn = obs_device.InstrumentedJit("loopclock.off", jax.jit(lambda x: x + 1))

    async def main():
        with timeline.phase("process", job="off", annotate=False) as ph:
            await asyncio.sleep(0.3)        # blocking selects, two ticks
            fn(jnp.arange(4))
            fn(jnp.arange(4))
            assert ph.elapsed() >= 0.3      # the runner's counters still read
        timeline.loop_tick()

    with update(obs={"timeline_events": 0, "loop_lag_interval": 0.1}):
        _run_loop(main)
        sel = timeline.TimedSelector()
        try:
            sel.select(0.01)
        finally:
            sel.close()
        assert sel.idle_s == 0.0
    assert timeline.totals() == {} and timeline.snapshot() == []


RUNNER_SQL = """
CREATE TABLE src (
  timestamp TIMESTAMP, a BIGINT NOT NULL
) WITH (connector = 'single_file', path = '{src}', format = 'json',
        type = 'source', event_time_field = 'timestamp');
CREATE TABLE out (a BIGINT, cnt BIGINT) WITH (
  connector = 'single_file', path = '{out}', format = 'json', type = 'sink');
INSERT INTO out
SELECT a, cnt FROM (
  SELECT a, count(*) as cnt, tumble(interval '1 second') as w
  FROM src GROUP BY 1, w);
"""


def test_every_item_a_runner_handles_is_inside_an_enclosure_of_its_task(
        tmp_path):
    """A source task's batches are under `process` with its task id and
    their rows (it has no input item: its collector opens the enclosure);
    an operator task's batch is under `process` from its counters on, the
    receiver's `audit.attest` inside; every watermark signal is under
    `watermark`, the ones that move nothing too."""
    import asyncio
    import json

    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    n = 4000
    src = tmp_path / "in.json"
    with open(src, "w") as f:
        for i in range(n):
            stamp = np.datetime64(1_677_628_800_000 + 2 * i, "ms")
            f.write(json.dumps({"a": i % 7,
                                "timestamp": str(stamp) + "Z"}) + "\n")
    signals = {}

    async def run():
        plan = plan_query(RUNNER_SQL.format(src=src, out=tmp_path / "o.json"),
                          parallelism=1)
        eng = Engine(plan.graph, job_id="encl",
                     storage_url=str(tmp_path / "ckpt")).start()
        for s in eng.program.subtasks:
            holder = s.runner.watermarks
            sound = holder.set

            def counted(i, wm, tid=s.runner.task_info.task_id, sound=sound):
                signals[tid] = signals.get(tid, 0) + 1
                return sound(i, wm)

            holder.set = counted
            signals.setdefault(s.runner.task_info.task_id, 0)
            if s.node.is_source:
                signals["source"] = s.runner.task_info.task_id
        await eng.join(120)

    asyncio.run(run())
    source = signals.pop("source")
    by_task = {tid: timeline.totals(job="encl", task=tid) for tid in signals}
    assert by_task[source]["process"]["n"] == n
    assert "watermark" not in by_task[source]
    for tid, seen in signals.items():
        if tid == source:
            continue
        t = by_task[tid]
        # every signal, moved or not: the enclosure is around `set` itself
        assert t["watermark"]["count"] == seen > 0, tid
        assert t["process"]["count"] > 0
        if "audit.attest" in t:
            # the receiver's tap is inside the batch's enclosure now
            assert t["process"]["self_s"] <= (
                t["process"]["total_s"] - t["audit.attest"]["total_s"]
                + 1e-4), tid
    assert sum(t["process"]["n"] for tid, t in by_task.items()
               if tid != source) >= n


def test_the_doctor_skips_the_recorders_waits_and_enclosures():
    """A ledger that holds `loop.idle` and `loop.run`: neither may swamp
    the host-bound score's shares (`obs/doctor.py` reads `timeline.WAITS`
    and `timeline.ENCLOSING`, no list of its own)."""
    from arroyo_tpu.obs import doctor

    base = {
        "job": "j", "window_s": 10.0, "busy_s": 8.0, "busy_ratio": 0.8,
        "device_s": 0.0, "operators": [{"task": "2-0", "busy_s": 8.0}],
        "backpressure": 0.0, "queue_depth": 0.0, "watermark_lag_s": 0.0,
        "dispatch_p50_ms": 0.0, "dispatches": 0, "padding_waste": 0.0,
        "loop_lag_ms_p99": 1.0, "neighbors": [], "neighbor_top_share": 0.0,
        "phases": {"exchange": 3.0, "emit": 1.0},
    }
    plain = doctor.diagnose(base)["ranked"]
    with_loop = doctor.diagnose(dict(base, phases={
        **base["phases"], "loop.idle": 40.0, "loop.run": 60.0,
        "loop.lag": 5.0, "queue.wait": 9.0, "process": 2.0,
        "watermark": 1.0}))["ranked"]
    assert with_loop == plain
    # and offline, from a dump that carries both
    timeline.note("loop.idle", 0.5, job="")
    timeline.note("loop.run", 0.5, job="", cpu_s=0.4)
    timeline.note("agg.pack", 0.2, job="j", task="3-0", cpu_s=0.1)
    sig = doctor.signals_from_trace(
        obs.perfetto_trace([])["traceEvents"], "j")
    assert sig["phases"] == {"agg.pack": pytest.approx(0.2)}
    assert sig["neighbors"] == []


def test_debug_timeline_shows_cpu_beside_wall(every_edge):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from arroyo_tpu.utils.admin import build_admin_app

    with timeline.phase("agg.pack", job="dbg", task="3-0"):
        _spin(0.02)
    timeline.note("loop.run", 0.5, job="", cpu_s=0.3)

    async def go():
        async with TestClient(TestServer(build_admin_app("test"))) as client:
            whole = await (await client.get("/debug/timeline")).json()
            mine = await (await client.get(
                "/debug/timeline", params={"job": "dbg", "last": "60"})).json()
            assert (await client.get(
                "/debug/timeline", params={"last": "x"})).status == 400
        return whole, mine

    whole, mine = asyncio.run(go())
    assert whole["totals"]["loop.run"]["cpu_s"] == pytest.approx(0.3)
    assert set(mine["totals"]) == {"agg.pack"}
    pack = mine["totals"]["agg.pack"]
    assert 0.01 < pack["self_cpu_s"] <= pack["total_s"] * 1.01
    assert whole["waits"] == list(timeline.WAITS)
    assert whole["enclosing"] == list(timeline.ENCLOSING)
    assert whole["device_waits"] == list(timeline.DEVICE_WAITS)


def test_an_unfused_value_operator_books_project_with_the_rows_it_took_in():
    """`project` (ISSUE 38): the source chain's projection over the raw row
    was the largest un-named part of a source task's enclosure."""
    import asyncio

    import pyarrow as pa

    from arroyo_tpu.operators.projection import BatchMapOperator

    got = []

    class Collector:
        async def collect(self, batch):
            got.append(batch)

    op = BatchMapOperator(lambda b: b.slice(0, 3), "agg_input")
    batch = pa.record_batch({"a": list(range(10))})

    async def run():
        with timeline.phase("process", job="proj", task="1-0", n=10,
                            annotate=False):
            await op.process_batch(batch, None, Collector())

    asyncio.run(run())
    t = timeline.phase_totals("proj")
    assert got[0].num_rows == 3
    assert t["project"]["count"] == 1 and t["project"]["n"] == 10
    assert t["process"]["self_s"] == pytest.approx(
        t["process"]["total_s"] - t["project"]["total_s"], abs=1e-5)
    assert [e["task"] for e in timeline.snapshot("proj")] == ["1-0", "1-0"]


def test_a_projection_that_built_a_filtered_view_books_what_it_filtered():
    """`project.filter` (ISSUE 43): a count with no duration inside
    `project`, once per call that built the predicate's view: `n` of the
    input's `padded` leaf arrays went through the filter kernel. q5's
    program over a raw NEXmark row reads `bid.auction` and `_timestamp`:
    2 of 26, not `bid`'s seven and one. A program that reads every child
    filters the struct whole, once; a predicate that keeps every row
    builds no view and books nothing."""
    import asyncio

    import pyarrow as pa
    from test_lazy_filtered_batch import RAW_LEAVES, _nexmark, _programs

    from arroyo_tpu.operators.projection import BatchMapOperator

    class Collector:
        async def collect(self, batch):
            pass

    raw = _nexmark()
    bid = raw.column(2)
    bid3 = pa.StructArray.from_arrays(
        [bid.field(n) for n in ("auction", "price", "bidder")],
        names=["auction", "price", "bidder"], mask=bid.is_null())
    narrowed = pa.RecordBatch.from_arrays(
        [bid3, raw.column(3)], names=["bid", "_timestamp"])

    def run(job, batch, texts, predicate):
        op = BatchMapOperator(_programs(batch, texts, predicate)[0],
                              "agg_input")

        async def go():
            with timeline.phase("process", job=job, task="1-0",
                                n=batch.num_rows, annotate=False):
                await op.process_batch(batch, None, Collector())

        asyncio.run(go())
        t = timeline.phase_totals(job)
        assert t["project"]["count"] == 1
        return t.get("project.filter")

    one = run("pf-q5", raw, ["bid.auction", "_timestamp"], "bid IS NOT NULL")
    assert (one["count"], one["n"], one["padded"]) == (1, 2, RAW_LEAVES)
    assert one["total_s"] == 0
    every = run("pf-q7", narrowed,
                ["bid.auction", "bid.price", "bid.bidder", "_timestamp"],
                "bid IS NOT NULL")
    assert (every["n"], every["padded"]) == (3 + 1, 4)
    some = run("pf-q7max", narrowed, ["bid.price", "_timestamp"],
               "bid IS NOT NULL")
    assert (some["n"], some["padded"]) == (2, 4)
    assert run("pf-all", raw, ["bid.auction", "_timestamp"],
               "_timestamp >= 0") is None
    assert [e["phase"] for e in timeline.snapshot("pf-q5")] == [
        "project.filter", "project", "process"]
    assert "project.filter" not in (timeline.ENCLOSING + timeline.WAITS
                                    + timeline.DEVICE_WAITS)


def test_a_breach_bundle_written_on_the_loop_is_a_leaf_of_the_ledger(tmp_path):
    """An SLO alert that fires serialises the flight recorder and the
    phase ring where it stands, on the controller's loop: `watch.bundle`,
    the job's, `key` = the rule, `n` = the spans it wrote."""
    from arroyo_tpu.config import update
    from arroyo_tpu.obs.watchtower import AlertState, RuleSpec, Watchtower

    spec = RuleSpec("loop_lag", "", lambda ctx: 1.0, "above", 0.25, 0.1,
                    0.0, 0.0, "loop")
    with update(watch={"spool_dir": str(tmp_path)}):
        tower = Watchtower()
        tower._fire("jobw", "t", None, spec, AlertState(), 1.0, 0.0)
    assert len(tower.bundles_for("jobw")) == 1
    entry, = (e for e in timeline.snapshot("jobw")
              if e["phase"] == "watch.bundle")
    assert entry["key"] == "loop_lag"
    assert entry["n"] == tower.bundles_for("jobw")[0]["spans"]
    assert "watch.bundle" not in (timeline.ENCLOSING + timeline.WAITS
                                  + timeline.DEVICE_WAITS)
