"""The cell `q11.catchup` (ISSUE 44): NEXmark query 11 "user sessions" on the
NEXmark stream at 100,000 events/s of event time, read from BENCHMARK.json
itself, with the session operator on the device tier (the rehearsal's: XLA's
CPU backend). ONE traced rehearsal with the ledger printed behind its line
(`ledger_dump.py`) serves the tests of the result line and of what the
session path books; the three readers this cell brings read the program's
ledger and are held on a hand-made one to their arithmetic. A CPU run
gives no device number. The rehearsals run 12 s, six of their 2 s
checkpoint intervals (PERF.md section 7 g); the controls of this
configuration are rehearsed in `test_bench_offgrid.py`."""

import json
import os
import time
import types

import pytest

import run as bench_run
from bench_helpers import HERE, REPO, listed, rehearse, run_cell

CELL = "q11.catchup"
CONFIG = "nexmark-q11"
# name: (unit, source)
NEW = {"sess_place_us_per_kevent": ("us/kevent", "program_span"),
       "sess_expire_us_per_close": ("us", "program_span"),
       "sess_open_kslots": ("kslots", "program_counter")}
# what ISSUE 44 lists the cell in at the least (a traced chip run reads a
# number of each); `device_trace` ones are not a CPU run's to give
SHARED = {"host_cpu_cores", "dispatches_per_mevent", "agg_update_call_us",
          "compiles_in_window.catchup", "device_idle_pct.catchup",
          "state_hbm_peak_mb", "agg_pack_us_per_kevent",
          "agg_update_rows_per_call", "agg_update_pad_pct",
          "engine_unnamed_pct", "loop_idle_pct", "engine_offcore_pct",
          "host_leaf_offcore_pct", "engine_unnamed_cpu_pct",
          "flush_resolve_pct", "ckpt_capture_ms", "project_us_per_kevent"}
NOT_ITS = {"join_close_ms", "rank_close_ms", "rank_krows_per_close",
           "mesh_route_call_us", "mesh_rows_per_dispatch",
           "mesh_busiest_shard_pct", "exchange_padding_pct",
           "dir_assign_us_per_kevent", "dir_new_slot_pct"}
S = 1_000_000_000

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(HERE, "data", "future_cells.json")) as _f:
    KEPT = json.load(_f)


def entry(bench, group, name):
    return next(e for e in bench[group] if e["name"] == name)


def reader(name):
    return bench_run.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def traced():
    """(the result line, what it said, the ledger behind it) of one traced
    rehearsal."""
    out = run_cell("--workload", CELL, "--seed", str(2**31 + 44),
                   "--seconds", "12", "--trace", "1", "--rehearsal",
                   script=os.path.join(HERE, "ledger_dump.py"))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(next(ln for ln in lines if ln.startswith('{"correct"')))
    line["said"] = [ln for ln in lines if ln.startswith("[")]
    line["tier"] = [ln for ln in out.stderr.splitlines()
                    if "window session_window" in ln]
    return line, json.loads(lines[-1].removeprefix("LEDGER "))


def test_the_cells_entries_are_the_kept_ones_word_for_word():
    assert entry(BENCH, "workloads", CELL) == entry(KEPT, "workloads", CELL)
    assert entry(BENCH, "configs", CONFIG) == entry(KEPT, "configs", CONFIG)
    cell = entry(BENCH, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "catchup-100k", 1)
    assert entry(BENCH, "configs", CONFIG)["reduced"] == ["nominal_rate"]
    assert entry(BENCH, "configs", CONFIG)["file"] == (
        f"benchmark/configs/{CONFIG}.json")
    # the traffic is the file q5's cell uses, as it stands
    assert entry(BENCH, "workloads", "q5.catchup")["traffic"] == (
        cell["traffic"])
    assert BENCH["run_seconds"] == 45


def test_five_cells_one_of_them_on_four_chips_and_this_one_behind_them():
    assert [w["name"] for w in BENCH["workloads"]][:5] == [
        "q5.catchup", "q7.catchup-25k", "q5-mesh4.catchup",
        "top5-hop60.catchup", CELL]
    assert [c["name"] for c in BENCH["configs"]][:5] == [
        "nexmark-q5", "nexmark-q7", "nexmark-q5-mesh4",
        "nexmark-top5-hop60", CONFIG]
    assert [w["chips"] for w in BENCH["workloads"]][:5] == [1, 1, 4, 1, 1]
    older = ["q5.catchup", "q7.catchup-25k", "q5-mesh4.catchup",
             "top5-hop60.catchup"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW:
            # appended behind the older cells, which stand in their order
            at = cells.index(CELL)
            assert cells[:at] == [c for c in older if c in cells], m["name"]
            assert cells.count(CELL) == 1


def test_the_three_new_metrics_are_appended_under_one_layer():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("project_us_per_kevent") + 1   # PR 43's, the last held
    assert names[at:at + 3] == list(NEW)
    for name, (unit, source) in NEW.items():
        assert entry(BENCH, "per_layer", name) == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "session state", "moves": "events_per_s",
            "workloads": [CELL]}


def test_the_cell_is_listed_where_its_path_books_and_nowhere_else():
    mine = listed(BENCH, CELL, "per_layer")
    assert SHARED | set(NEW) <= mine
    assert not NOT_ITS & mine
    assert listed(BENCH, CELL, "end_to_end") == {"setup_s", "events_per_s"}
    # every listed metric has its reader, found by name
    for name in mine:
        assert hasattr(reader(name), "read"), name


def test_the_rehearsal_is_correct_over_thousands_of_sessions(traced):
    line, _ledger = traced
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 1_000
    compared = next(s for s in line["said"] if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    booked = [s for s in line["said"] if "conservation:" in s]
    assert len(booked) == 1 and "off by 0 (limit 0)" in booked[0]
    cadence = next(s for s in line["said"] if "cadence:" in s)
    assert int(cadence.split("checkpoints_in_window=")[1].split()[0]) >= 2


def test_the_session_operator_takes_the_device_tier_and_dispatches(traced):
    """The tier every window operator takes: a rehearsal waives the
    accelerator, so the accumulator is jax's on XLA's CPU backend."""
    line, _ledger = traced
    assert len(line["tier"]) == 1 and "accumulator=jax " in line["tier"][0]
    assert line["metrics"]["dispatches_per_mevent"]["value"] > 0
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_the_traced_line_holds_the_three_new_metrics(traced):
    line, _ledger = traced
    # (the device's memory is no CPU run's to read either)
    counted = {n for n in SHARED - {"state_hbm_peak_mb"} if entry(
        BENCH, "per_layer", n)["source"] != "device_trace"}
    assert counted | set(NEW) <= set(line["metrics"]) <= listed(
        BENCH, CELL, "per_layer")
    for name, (unit, _source) in NEW.items():
        assert line["metrics"][name]["unit"] == unit
        assert line["metrics"][name]["value"] > 0
    # a view of dict layers: nothing of the seal is deferred to the flush
    assert line["metrics"]["flush_resolve_pct"]["value"] == 0.0
    # at the rehearsal's 2,000 events/s: 40 bidders a second open for the
    # ~25 s their bids span, the gap and the delay: over a thousand
    assert 0.5 < line["metrics"]["sess_open_kslots"]["value"] < 3


def test_the_session_path_books_its_phases_in_one_task(traced):
    _line, ledger = traced
    window = ledger["window"]
    for phase in ("sess.segment", "sess.place", "sess.open", "sess.expire",
                  "agg.pack", "agg.enqueue", "agg.gather", "agg.read",
                  "agg.reset", "close.build", "close.build.stage",
                  "win.cols", "ckpt.capture", "serve.seal"):
        assert phase in window, phase
    for gone in ("dir.assign", "close.take", "close.combine", "agg.host"):
        assert gone not in window, gone
    tasks = [t for t in ledger["tasks"].values() if "sess.place" in t]
    assert len(tasks) == 1
    t = tasks[0]
    for phase in ("sess.segment", "sess.open", "sess.expire", "close.build"):
        assert phase in t, phase
    # every batch's live rows are cut and placed; bids alone reach it
    assert t["sess.segment"]["count"] == t["sess.place"]["count"]
    assert t["sess.segment"]["n"] <= t["sess.segment"]["padded"]
    assert 0.85 * ledger["events"] < t["sess.segment"]["padded"] < (
        ledger["events"])
    # in the rehearsal a bidder's run is cut now and then (27 of 1,526 in
    # 40 s): a few segments go one at a time, most none
    assert t["sess.place"]["padded"] < t["sess.place"]["n"] / 20
    # a watermark's closes: one gather, one read, one reset, one batch
    assert t["sess.expire"]["count"] == t["close.build"]["count"]
    assert t["agg.reset"]["count"] == t["sess.expire"]["count"]
    assert t["sess.expire"]["n"] == t["close.build"]["n"]
    assert t["sess.expire"]["padded"] > 100 * t["sess.expire"]["count"]


def test_the_untraced_line_holds_the_end_to_end_metrics():
    line, _said = rehearse(CELL, seed=2**31 + 45, seconds=12)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "events_per_s"}
    assert line["metrics"]["events_per_s"]["value"] > 0


def a_run(**more):
    now = time.time_ns()
    return types.SimpleNamespace(
        start={"t_ns": now - S}, end={"t_ns": now + S}, window_s=45.0,
        events_in_window=2_000_000, closes=[{}] * 20, **more)


def test_the_readers_give_none_without_the_phases_and_do_not_raise(
        monkeypatch):
    """The parent commit's program books no `sess.*`: the readers return
    None, not 0, and the line leaves the metrics out. So does every reader
    on a run whose tasks are not known, on a window that holds no entry of
    theirs, and on a program with no ledger at all."""
    from arroyo_tpu import obs
    from arroyo_tpu.obs import timeline

    readers = [reader(n) for n in NEW]
    obs.reset()
    try:
        timeline.note("agg.enqueue", 0.5, job="j", task="3-0", n=9)
        for run in (a_run(flow={"3-0": (5, 5)}), a_run(flow={}), a_run()):
            assert [r.read(run) for r in readers] == [None, None, None]
        # watermarks that closed nothing booked no `sess.expire`
        timeline.note("sess.segment", 0.25, job="j", task="3-0", n=9)
        timeline.note("sess.place", 0.25, job="j", task="3-0", n=3)
        got = [r.read(a_run(flow={"3-0": (5, 5)})) for r in readers]
        assert got[0] == pytest.approx(250.0) and got[1:] == [None, None]
    finally:
        obs.reset()
    monkeypatch.delattr(timeline, "totals")
    assert [r.read(a_run(flow={"3-0": (5, 5)})) for r in readers] == [
        None, None, None]


def test_the_readers_arithmetic():
    """Segment and place by their total seconds per thousand events;
    `sess.expire`'s seconds per session closed and its open sessions per
    entry; each in the one operator that did the most, whatever a second
    session operator of the job booked."""
    from arroyo_tpu import obs
    from arroyo_tpu.obs import timeline

    obs.reset()
    try:
        for _ in range(100):
            timeline.note("sess.segment", 0.004, job="j", task="3-0",
                          n=7_500, padded=8_192)
            timeline.note("sess.place", 0.002, job="j", task="3-0", n=1_000)
            timeline.note("sess.open", 0.0, job="j", task="3-0", n=164)
            timeline.note("sess.expire", 0.002, job="j", task="3-0", n=160,
                          padded=21_000, key=1)
            timeline.note("sess.place", 0.5, job="j", task="7-0", n=2)
            timeline.note("sess.expire", 0.5, job="j", task="7-0", n=1,
                          padded=3)
        run = a_run(flow={"3-0": (0, 0), "7-0": (0, 0)})
        place, expire, kslots = (reader(n).read(run) for n in NEW)
        assert place == pytest.approx(1e6 * 0.6 / 2_000)
        assert expire == pytest.approx(12.5)     # 0.2 s over 16,000
        assert kslots == pytest.approx(21.0)
    finally:
        obs.reset()
