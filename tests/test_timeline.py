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
