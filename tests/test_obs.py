"""Flight recorder (ISSUE 4): histogram metric kind, span API + ring
buffer, cross-process trace propagation through a real embedded-cluster
checkpoint, and the /metrics + trace export surfaces."""

import asyncio
import json

import pytest

from arroyo_tpu import obs
from arroyo_tpu.metrics import (
    BATCHES_RECV,
    DEFAULT_BUCKETS,
    RateWindow,
    Registry,
    REGISTRY,
)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    obs.reset()
    yield
    obs.reset()


# -- histogram metric kind ---------------------------------------------------


def test_histogram_buckets_and_exposition():
    reg = Registry()
    h = reg.histogram("lat_seconds", "test latency", buckets=(0.01, 0.1, 1.0))
    hd = h.labels(op="x")
    for v in (0.005, 0.05, 0.5, 5.0):
        hd.observe(v)
    text = reg.expose()
    assert 'lat_seconds_bucket{op="x",le="0.01"} 1' in text
    assert 'lat_seconds_bucket{op="x",le="0.1"} 2' in text
    assert 'lat_seconds_bucket{op="x",le="1.0"} 3' in text
    assert 'lat_seconds_bucket{op="x",le="+Inf"} 4' in text
    assert 'lat_seconds_count{op="x"} 4' in text
    assert 'lat_seconds_sum{op="x"} 5.555' in text
    assert "# TYPE lat_seconds histogram" in text


def test_histogram_snapshot_and_handle_view():
    reg = Registry()
    h = reg.histogram("s", "", buckets=(1.0,))
    h.labels(a="1").observe(0.5)
    h.labels(a="1").observe(2.0)
    snap = reg.snapshot()["s"]
    assert snap == [({"a": "1"}, {"sum": 2.5, "count": 2,
                                  "buckets": {"1.0": 1, "+Inf": 2}})]
    assert h.labels(a="1").get_hist()["count"] == 2
    assert h.labels(a="other").get_hist() is None


def test_histogram_boundary_lands_in_its_bucket():
    # Prometheus buckets are <= le: an observation exactly on a boundary
    # counts in that bucket
    reg = Registry()
    h = reg.histogram("b", "", buckets=(0.1, 1.0))
    h.labels().observe(0.1)
    assert h.labels().get_hist()["buckets"]["0.1"] == 1


def test_default_buckets_are_sorted_and_latency_shaped():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)
    assert DEFAULT_BUCKETS[0] <= 0.001 and DEFAULT_BUCKETS[-1] >= 10


# -- Registry.reset regression (satellite) -----------------------------------


def test_reset_keeps_module_level_handles_visible():
    """Registry.reset() used to drop the _Metric objects from the
    registry while module-level families kept handles to them: increments
    after reset() silently vanished from expose()/snapshot(). reset()
    now clears values in place."""
    handle = BATCHES_RECV.labels(job="rj", task="0-0")
    handle.inc()
    REGISTRY.reset()
    assert handle.get() == 0  # cleared in place
    handle.inc(3)
    assert 'arroyo_worker_batches_recv{job="rj",task="0-0"} 3' in (
        REGISTRY.expose()
    )
    snap = REGISTRY.snapshot()["arroyo_worker_batches_recv"]
    assert ({"job": "rj", "task": "0-0"}, 3.0) in snap
    REGISTRY.reset()


def test_reset_clears_histograms_and_refreshers():
    reg = Registry()
    h = reg.histogram("hh", "")
    h.labels(x="1").observe(1.0)
    g = reg.gauge("gg", "")
    g.labels(x="1").set_refresher(lambda: 42.0)
    reg.reset()
    assert h.labels(x="1").get_hist() is None
    assert "gg 42" not in reg.expose()


# -- RateWindow (satellite) --------------------------------------------------


def test_rate_window_deque_trims_time_and_caps_samples():
    w = RateWindow()
    from collections import deque

    assert isinstance(w.samples, deque)
    w.add(0.0, now=0.0)
    w.add(100.0, now=100.0)
    w.add(400.0, now=400.0)  # pushes the t=0 sample out of the window
    assert w.samples[0][0] == 100.0
    assert w.rate() == pytest.approx(1.0)
    # hard cap regardless of window
    w2 = RateWindow()
    for i in range(RateWindow.MAX_SAMPLES + 50):
        w2.add(float(i), now=100.0 + i * 0.001)
    assert len(w2.samples) == RateWindow.MAX_SAMPLES


# -- span API + ring buffer --------------------------------------------------


def test_span_nesting_parents_and_events():
    with obs.span("root", trace="t/1", cat="a", k=1) as root:
        assert obs.current() == ("t/1", root.span_id)
        with obs.span("child", cat="b") as child:
            assert child.trace_id == "t/1"
            assert child.parent_id == root.span_id
            child.event("marker", n=2)
    spans = obs.recorder().snapshot(trace_id="t/1")
    assert [s["name"] for s in spans] == ["child", "root"]  # finish order
    assert spans[0]["events"][0]["name"] == "marker"
    assert spans[1]["parent_id"] is None


def test_span_without_context_is_null():
    sp = obs.span("floating")
    assert sp is obs.NULL_SPAN
    with sp:
        sp.event("x")
        sp.set(a=1)
    assert len(obs.recorder()) == 0


def test_span_disabled_by_config():
    from arroyo_tpu.config import update

    with update(obs={"enabled": False}):
        assert obs.span("x", trace="t/1") is obs.NULL_SPAN
        obs.event("e")
    assert len(obs.recorder()) == 0


def test_ring_buffer_overflow_drops_oldest():
    rec = obs.reset(capacity=10)
    for i in range(25):
        with obs.span(f"s{i}", trace="t/ring"):
            pass
    assert len(rec) == 10
    assert rec.dropped == 15
    names = [s["name"] for s in rec.snapshot()]
    assert names == [f"s{i}" for i in range(15, 25)]  # oldest dropped


def test_error_in_span_recorded():
    with pytest.raises(ValueError):
        with obs.span("boom", trace="t/err"):
            raise ValueError("nope")
    (sp,) = obs.recorder().snapshot(trace_id="t/err")
    assert "ValueError" in sp["attrs"]["error"]


def test_chrome_trace_export_shape():
    with obs.span("root", trace="t/x", cat="c") as sp:
        sp.event("inst")
    obs.event("lone", cat="chaos")
    doc = obs.chrome_trace(obs.recorder().snapshot())
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "M"} <= phases
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["args"]["trace_id"] == "t/x"
    assert doc["displayTimeUnit"] == "ms"


def test_attach_detach_for_async_hops():
    sp = obs.start_span("hop", trace="t/hop")
    tok = sp.attach()
    try:
        child = obs.span("inner")
        assert child.parent_id == sp.span_id
        child.finish()
    finally:
        sp.detach(tok)
        sp.finish()
    assert obs.current() is None
    assert len(obs.recorder()) == 2


# -- cross-process propagation through a real embedded cluster ---------------


CLUSTER_SQL = """
CREATE TABLE impulse WITH (
  connector = 'impulse', event_rate = '150000',
  message_count = '100000', start_time = '0', realtime = 'true'
);
CREATE TABLE out (k BIGINT UNSIGNED, cnt BIGINT) WITH (
  connector = 'single_file', path = '{out}',
  format = 'json', type = 'sink'
);
INSERT INTO out
SELECT k, cnt FROM (
  SELECT counter % 8 as k, tumble(interval '1 millisecond') as w,
         count(*) as cnt
  FROM impulse GROUP BY 1, 2
);
"""


def _connected_tree(spans):
    """(single_root, orphans): parent links resolve within the trace."""
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    orphans = [
        s for s in spans
        if s["parent_id"] is not None and s["parent_id"] not in by_id
    ]
    return len(roots) == 1, orphans


def test_checkpoint_trace_tree_spans_cluster(tmp_path):
    """The golden acceptance: a windowed-agg run on the embedded cluster
    (controller + 2 workers over real gRPC + TCP) produces, per
    checkpoint epoch, ONE connected span tree covering controller →
    worker → operator barrier → storage commit — and /metrics exposes
    the new histogram families and watermark-lag gauges."""
    from arroyo_tpu.config import update
    from arroyo_tpu.controller.controller import ControllerServer
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler
    from arroyo_tpu.controller.state_machine import JobState

    async def go():
        c = await ControllerServer(EmbeddedScheduler()).start()
        with update(pipeline={"checkpointing": {"interval": 0.1}}):
            await c.submit_job(
                "obs1", sql=CLUSTER_SQL.format(out=tmp_path / "out.json"),
                storage_url=str(tmp_path / "ck"), n_workers=2, parallelism=2,
            )
            state = await c.wait_for_state(
                "obs1", JobState.FINISHED, JobState.FAILED, timeout=60
            )
        await c.stop()
        return state

    state = asyncio.run(go())
    assert state == JobState.FINISHED

    spans = obs.recorder().snapshot(trace_prefix="obs1/")
    ck_traces = sorted({
        s["trace_id"] for s in spans if "/ck-" in s["trace_id"]
    })
    assert ck_traces, "no checkpoint trace recorded"
    checked = 0
    for tid in ck_traces:
        tr = [s for s in spans if s["trace_id"] == tid]
        cats = {s["cat"] for s in tr}
        names = {s["name"] for s in tr}
        if "storage" not in cats:
            continue  # a barely-started epoch racing job finish
        single_root, orphans = _connected_tree(tr)
        assert single_root, f"{tid}: multiple roots"
        assert not orphans, f"{tid}: orphans {[s['name'] for s in orphans]}"
        # the acceptance chain: controller → worker → runner → storage
        assert {"controller", "rpc", "worker", "runner", "storage"} <= cats
        assert "checkpoint" in names            # controller root
        assert "worker.checkpoint" in names     # worker fan-out hop
        assert "checkpoint.capture" in names    # operator barrier hop
        assert any(n.startswith("storage.") for n in names)  # state commit
        checked += 1
    assert checked >= 1

    # metric surface: >= 3 histogram families with _bucket/_sum/_count
    # plus the watermark-lag gauge, all live from this run
    text = REGISTRY.expose()
    for fam in ("arroyo_worker_batch_processing_seconds",
                "arroyo_exchange_frame_seconds",
                "arroyo_storage_op_seconds",
                "arroyo_checkpoint_phase_seconds"):
        assert f"{fam}_bucket" in text, fam
        assert f"{fam}_sum" in text, fam
        assert f"{fam}_count" in text, fam
    assert 'arroyo_worker_watermark_lag_seconds{job="obs1"' in text
    assert 'arroyo_worker_barrier_alignment_seconds{job="obs1"' in text
    assert 'phase="capture"' in text and 'phase="flush"' in text


def test_rpc_trace_header_round_trip():
    """The gRPC-analog layer forwards the __trace__ header into a server
    span that parents to the client's call span."""
    from arroyo_tpu.engine.rpc import RpcClient, RpcServer

    seen = {}

    async def go():
        server = RpcServer("127.0.0.1")

        async def method(req):
            seen["ctx"] = obs.current()
            return {"ok": 1}

        server.add_service("TestSvc", {"Do": method})
        port = await server.start()
        client = RpcClient(f"127.0.0.1:{port}")
        with obs.span("origin", trace="t/rpc") as sp:
            await client.call("TestSvc", "Do", {"x": 1})
            origin_id = sp.span_id
        await client.close()
        await server.stop()
        return origin_id

    origin_id = asyncio.run(go())
    assert seen["ctx"][0] == "t/rpc"
    spans = obs.recorder().snapshot(trace_id="t/rpc")
    names = {s["name"]: s for s in spans}
    assert "call.TestSvc.Do" in names
    assert "rpc.TestSvc.Do" in names
    assert names["call.TestSvc.Do"]["parent_id"] == origin_id
    assert names["rpc.TestSvc.Do"]["parent_id"] == (
        names["call.TestSvc.Do"]["span_id"]
    )


def test_trace_report_merge_and_stats(tmp_path):
    import sys

    sys.path.insert(0, "/root/repo/tools")
    try:
        import trace_report
    finally:
        sys.path.remove("/root/repo/tools")

    with obs.span("root", trace="t/m", cat="a"):
        with obs.span("kid", cat="b"):
            pass
    doc = obs.chrome_trace(obs.recorder().snapshot())
    p1 = tmp_path / "d1.json"
    p1.write_text(json.dumps(doc))
    p2 = tmp_path / "d2.json"
    p2.write_text(json.dumps(doc))  # duplicate dump: spans dedupe
    merged = trace_report.merge([str(p1), str(p2)])
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2  # deduped
    traces = trace_report.group_traces(merged["traceEvents"])
    st = trace_report.tree_stats(traces["t/m"])
    assert st["connected"] and st["spans"] == 2
    assert st["roots"] == ["root"]


def test_admin_debug_trace_endpoint():
    from aiohttp.test_utils import TestClient, TestServer

    from arroyo_tpu.utils.admin import build_admin_app

    with obs.span("adm", trace="t/adm"):
        pass

    async def go():
        app = build_admin_app("test")
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/debug/trace")
            doc = await resp.json()
            resp2 = await client.get("/debug/trace",
                                     params={"trace": "t/none"})
            doc2 = await resp2.json()
            return doc, doc2

    doc, doc2 = asyncio.run(go())
    assert doc["spanCount"] >= 1
    assert any(e.get("args", {}).get("trace_id") == "t/adm"
               for e in doc["traceEvents"])
    assert doc2["spanCount"] == 0


def test_rest_job_traces_endpoint(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from arroyo_tpu.api.rest import build_app

    with obs.span("ck", trace="jobx/ck-1", cat="controller"):
        pass
    with obs.span("other", trace="joby/ck-1", cat="controller"):
        pass

    async def go():
        app = build_app(db_path=str(tmp_path / "api.db"))
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/api/v1/jobs/jobx/traces")
            assert resp.status == 200
            return await resp.json()

    doc = asyncio.run(go())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["args"]["trace_id"] == "jobx/ck-1"
    assert doc["spanCount"] == 1


def test_openapi_lists_traces_route(tmp_path):
    from arroyo_tpu.api.openapi import build_spec

    spec = build_spec()
    assert "/api/v1/jobs/{job_id}/traces" in spec["paths"]
    assert "TraceDump" in spec["components"]["schemas"]


# -- fleet observatory (ISSUE 11): attribution, timeline, doctor -------------


def _valid_chrome_events(doc):
    """Chrome trace-event schema check: the document round-trips as JSON
    and every event carries the fields its phase type requires."""
    json.loads(json.dumps(doc))
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert isinstance(ev.get("name"), str) and ev["name"]
        assert ev.get("ph") in ("X", "i", "M")
        if ev["ph"] == "M":
            continue
        assert isinstance(ev.get("ts"), (int, float))
        assert "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert isinstance(ev.get("dur"), (int, float))
            assert ev["dur"] >= 0


def test_attribution_accounting_flush_and_summary():
    from arroyo_tpu.metrics import REGISTRY
    from arroyo_tpu.obs import attribution

    acct = attribution.ACCOUNTING
    with attribution.job_scope("jobA"):
        assert attribution.current_job() == "jobA"
        attribution.note(busy=0.3, nbytes=1000)
        attribution.note(device=0.05, dispatches=3)
    attribution.note(job="jobB", busy=0.1)
    attribution.note(busy=0.05)  # no ambient job -> unattributed bucket
    acct.flush()
    text = REGISTRY.expose()
    assert 'arroyo_job_attributed_busy_seconds{job="jobA"} 0.3' in text
    assert 'arroyo_job_attributed_device_seconds{job="jobA"} 0.05' in text
    assert 'arroyo_job_attributed_dispatches{job="jobA"} 3' in text
    assert 'arroyo_job_attributed_bytes{job="jobA"} 1000' in text
    assert 'arroyo_job_attributed_busy_seconds{job="jobB"} 0.1' in text
    s = acct.summary()
    assert s["jobs"]["jobA"]["busy"] == pytest.approx(0.3)
    assert s["unattributed_busy_s"] == pytest.approx(0.05)
    # coverage: attributed share of all recorded busy
    assert s["coverage"] == pytest.approx(0.4 / 0.45, abs=1e-3)


def test_attribution_gc_drops_job_state():
    from arroyo_tpu.metrics import REGISTRY
    from arroyo_tpu.obs import attribution, timeline

    attribution.note(job="gone", busy=1.0)
    timeline.note("process", 0.5, job="gone", task="1-0")
    with obs.span("x", trace="gone/ck-1"):
        pass
    attribution.ACCOUNTING.flush()
    assert 'job="gone"' in REGISTRY.expose()
    REGISTRY.drop_job("gone")
    obs.expunge_job("gone")
    assert 'job="gone"' not in REGISTRY.expose()
    assert attribution.ACCOUNTING.summary()["jobs"].get("gone") is None
    assert timeline.snapshot("gone") == []
    assert obs.recorder().snapshot(trace_prefix="gone/") == []


def test_trace_recorder_expunge_is_job_scoped():
    for j in ("keepme", "dropme"):
        for i in range(3):
            with obs.span(f"s{i}", trace=f"{j}/ck-{i}"):
                pass
    rec = obs.recorder()
    assert rec.expunge_job("dropme") == 3
    assert len(rec) == 3
    assert all(s["trace_id"].startswith("keepme/")
               for s in rec.snapshot())


def test_timeline_ring_bounded_and_phase_totals():
    from arroyo_tpu.config import update
    from arroyo_tpu.obs import timeline

    with update(obs={"timeline_events": 16}):
        timeline.clear()  # re-applies capacity from config
        for i in range(40):
            timeline.note("process", 0.001, job="ring", task="1-0")
        assert len(timeline.snapshot()) == 16
        # the totals come from the bucket store, which outlives the ring
        totals = timeline.phase_totals("ring")
        assert totals["process"]["count"] == 40
    with update(obs={"timeline_events": 0}):
        before = len(timeline.snapshot())
        timeline.note("process", 0.001, job="ring")
        assert len(timeline.snapshot()) == before  # disabled: no-op


def test_perfetto_export_schema_and_phase_tracks():
    from arroyo_tpu.obs import timeline

    with obs.span("root", trace="jp/ck-1", cat="controller") as sp:
        sp.event("inst")
    timeline.note("process", 0.002, job="jp", task="1-0")
    timeline.note("dispatch", 0.001, job="jp", task="1-0")
    timeline.note("process", 0.002, job="other", task="2-0")
    doc = obs.perfetto_trace(obs.recorder().snapshot())
    _valid_chrome_events(doc)
    assert doc["phaseCount"] == 3
    phase_events = [e for e in doc["traceEvents"]
                    if e.get("cat") == "phase"]
    assert {e["name"] for e in phase_events} == {"phase.process",
                                                "phase.dispatch"}
    # each (job, phase) pair gets its own NAMED track
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"jp · process", "jp · dispatch", "other · process"} <= names
    # job filter narrows spans AND ledger entries
    doc_jp = obs.perfetto_trace(obs.recorder().snapshot(), job="jp")
    assert doc_jp["phaseCount"] == 2
    assert all((e.get("args") or {}).get("job") != "other"
               for e in doc_jp["traceEvents"])
    # span parity with the chrome exporter: same X spans, none dropped
    chrome_x = [e for e in obs.chrome_trace(
        obs.recorder().snapshot())["traceEvents"] if e["ph"] == "X"]
    perf_x = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e.get("cat") != "phase"]
    assert len(perf_x) == len(chrome_x)


def test_doctor_verdicts_from_synthetic_signals():
    from arroyo_tpu.obs import doctor

    base = {
        "job": "j", "window_s": 10.0, "busy_s": 8.0, "busy_ratio": 0.8,
        "device_s": 0.0, "operators": [{"task": "2-0", "busy_s": 6.0},
                                       {"task": "1-0", "busy_s": 2.0}],
        "backpressure": 0.0, "queue_depth": 0.0, "watermark_lag_s": 0.0,
        "phases": {"process": 6.0, "emit": 1.0}, "dispatch_p50_ms": 0.0,
        "dispatches": 0, "padding_waste": 0.0, "loop_lag_ms_p99": 1.0,
        "neighbors": [], "neighbor_top_share": 0.0,
    }
    assert doctor.diagnose(base)["verdict"]["cause"] == "host-bound"
    assert doctor.diagnose(base)["verdict"]["operator"] == "2-0"

    dev = dict(base, device_s=7.0, dispatch_p50_ms=2.0,
               phases={"dispatch": 7.0, "process": 1.0})
    assert doctor.diagnose(dev)["verdict"]["cause"] == "device-bound"

    exch = dict(base, phases={"exchange": 6.0, "process": 2.0},
                backpressure=0.9)
    assert doctor.diagnose(exch)["verdict"]["cause"] == "exchange-bound"

    starved = dict(base, busy_s=0.2, busy_ratio=0.02, phases={})
    assert doctor.diagnose(starved)["verdict"]["cause"] == "starved"

    noisy = dict(starved, loop_lag_ms_p99=80.0, neighbor_top_share=0.9,
                 neighbors=[{"job": "hog", "busy_s": 9.0}])
    v = doctor.diagnose(noisy)["verdict"]
    assert v["cause"] == "noisy-neighbor"
    assert v["suspect"] == "hog"


def test_doctor_offline_from_perfetto_dump():
    from arroyo_tpu.obs import doctor, timeline

    # a saturated hog next to an idle victim, with visible loop lag
    for _ in range(20):
        timeline.note("process", 0.04, job="hog", task="1-0")
        timeline.note("dispatch", 0.01, job="hog", task="1-0")
    timeline.note("process", 0.001, job="victim", task="1-0")
    timeline.note("loop.lag", 0.08, job="")
    doc = obs.perfetto_trace([])
    sig = doctor.signals_from_trace(doc["traceEvents"], "victim")
    assert sig["offline"] and sig["neighbors"][0]["job"] == "hog"
    assert sig["loop_lag_ms_p99"] == pytest.approx(80.0)
    rep = doctor.diagnose(sig)
    assert rep["verdict"]["cause"] == "noisy-neighbor"
    assert rep["verdict"]["suspect"] == "hog"


def test_trace_report_job_filter_and_offline_doctor(tmp_path):
    import io
    import sys

    sys.path.insert(0, "/root/repo/tools")
    try:
        import trace_report
    finally:
        sys.path.remove("/root/repo/tools")

    from arroyo_tpu.obs import timeline

    with obs.span("ck", trace="j1/ck-1", cat="controller"):
        pass
    with obs.span("ck", trace="j2/ck-1", cat="controller"):
        pass
    for _ in range(10):
        timeline.note("process", 0.05, job="j2", task="1-0")
    timeline.note("process", 0.001, job="j1", task="1-0")
    doc = obs.perfetto_trace(obs.recorder().snapshot())
    p = tmp_path / "dump.json"
    p.write_text(json.dumps(doc))
    events = trace_report.filter_job(
        trace_report.merge([str(p)])["traceEvents"], "j1"
    )
    xs = [e for e in events if e.get("ph") == "X"
          and e.get("cat") != "phase"]
    assert len(xs) == 1
    assert all((e.get("args") or {}).get("job") != "j2"
               for e in events if e.get("ph") != "M")
    # offline doctor renders a verdict for the idle j1 (hog j2 dominates)
    buf = io.StringIO()
    rc = trace_report.doctor_summary(
        trace_report.merge([str(p)])["traceEvents"], "j1", out=buf
    )
    out = buf.getvalue()
    assert rc == 0
    assert "verdict:" in out and "neighbor j2" in out


def test_rest_doctor_endpoint_and_admin_surfaces(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from arroyo_tpu.api.rest import build_app
    from arroyo_tpu.obs import attribution
    from arroyo_tpu.utils.admin import build_admin_app

    attribution.note(job="docjob", busy=0.5)

    async def go():
        app = build_app(db_path=str(tmp_path / "api.db"))
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/api/v1/jobs/docjob/doctor")
            assert resp.status == 200
            rest_doc = await resp.json()
            resp = await client.get("/api/v1/jobs/docjob/traces",
                                    params={"fmt": "perfetto"})
            trace_doc = await resp.json()
        admin = build_admin_app("test")
        async with TestClient(TestServer(admin)) as client:
            attr = await (await client.get("/debug/attribution")).json()
            doct = await (await client.get(
                "/debug/doctor", params={"job": "docjob"})).json()
            assert (await client.get("/debug/doctor")).status == 400
            perf = await (await client.get(
                "/debug/trace", params={"fmt": "perfetto"})).json()
        return rest_doc, trace_doc, attr, doct, perf

    rest_doc, trace_doc, attr, doct, perf = asyncio.run(go())
    assert rest_doc["verdict"]["cause"] in (
        "host-bound", "device-bound", "exchange-bound", "starved",
        "noisy-neighbor",
    )
    assert "phaseCount" in trace_doc and "spanCount" in trace_doc
    assert attr["jobs"]["docjob"]["busy"] == pytest.approx(0.5)
    assert doct["job"] == "docjob"
    assert "phaseCount" in perf


def test_openapi_lists_doctor_route():
    from arroyo_tpu.api.openapi import build_spec

    spec = build_spec()
    assert "/api/v1/jobs/{job_id}/doctor" in spec["paths"]
    for schema in ("DoctorReport", "DoctorVerdict", "DoctorCause"):
        assert schema in spec["components"]["schemas"]


def test_cluster_attribution_timeline_and_doctor(tmp_path):
    """Fleet-observatory acceptance at small scale: a real embedded-
    cluster run (controller + 2 workers) attributes its busy time to the
    job (>= 95% of the per-subtask busy counters), records a phase
    ledger whose Perfetto export is schema-valid and carries one
    connected span timeline per checkpoint epoch with full span parity
    vs the chrome exporter, and the doctor names a plausible cause."""
    from arroyo_tpu.config import update
    from arroyo_tpu.controller.controller import ControllerServer
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler
    from arroyo_tpu.controller.state_machine import JobState
    from arroyo_tpu.metrics import REGISTRY
    from arroyo_tpu.obs import attribution, doctor, timeline

    async def go():
        c = await ControllerServer(EmbeddedScheduler()).start()
        with update(pipeline={"checkpointing": {"interval": 0.1}},
                    cluster={"metrics_ttl": 30.0}):
            await c.submit_job(
                "obsfleet",
                sql=CLUSTER_SQL.format(out=tmp_path / "out.json"),
                storage_url=str(tmp_path / "ck"), n_workers=2,
                parallelism=2,
            )
            state = await c.wait_for_state(
                "obsfleet", JobState.FINISHED, JobState.FAILED, timeout=60
            )
        await c.stop()
        return state

    state = asyncio.run(go())
    assert state == JobState.FINISHED

    # attribution coverage: per-job attributed busy vs the per-subtask
    # busy counters (independent instruments: contextvar vs labels)
    attribution.ACCOUNTING.flush()
    attr = attribution.ACCOUNTING.summary()["jobs"].get("obsfleet", {})
    worker_busy = sum(
        v for labels, v in REGISTRY.snapshot().get(
            "arroyo_worker_busy_seconds", [])
        if labels.get("job") == "obsfleet"
    )
    assert worker_busy > 0
    assert attr.get("busy", 0.0) >= 0.95 * worker_busy

    # the phase ledger saw the run end-to-end
    totals = timeline.phase_totals("obsfleet")
    for phase in ("decode", "process", "emit", "flush"):
        assert phase in totals, (phase, sorted(totals))

    # perfetto export: schema-valid, phases present, span parity, and
    # each complete checkpoint epoch still one connected tree
    spans = obs.recorder().snapshot(trace_prefix="obsfleet/")
    doc = obs.perfetto_trace(spans, job="obsfleet")
    _valid_chrome_events(doc)
    assert doc["phaseCount"] > 0
    perf_x = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e.get("cat") != "phase"]
    chrome_x = [e for e in obs.chrome_trace(spans)["traceEvents"]
                if e["ph"] == "X"]
    assert len(perf_x) == len(chrome_x) == len(spans) - sum(
        1 for s in spans if s.get("instant"))
    checked = 0
    for tid in sorted({s["trace_id"] for s in spans
                       if "/ck-" in s["trace_id"]}):
        tr = [s for s in spans if s["trace_id"] == tid]
        if "storage" not in {s["cat"] for s in tr}:
            continue  # a barely-started epoch racing job finish
        single_root, orphans = _connected_tree(tr)
        assert single_root and not orphans, tid
        checked += 1
    assert checked >= 1

    # the doctor produces a ranked verdict with evidence attached
    rep = doctor.report("obsfleet")
    assert rep["verdict"]["cause"] in (
        "host-bound", "device-bound", "exchange-bound", "starved",
        "noisy-neighbor",
    )
    assert len(rep["ranked"]) == 5
    assert rep["signals"]["busy_s"] > 0
