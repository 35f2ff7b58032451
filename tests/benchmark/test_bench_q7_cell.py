"""The cell `q7.catchup-25k` (ISSUE 26): NEXmark q7 at 25,000 events/s of
event time, read from BENCHMARK.json itself. ONE traced rehearsal on XLA's
CPU backend serves the tests of the result line; the two counts this cell
brings (`dir_new_slot_pct`, `ckpt_delta_krows_per_capture`) are counts, so
a CPU run may hold them to what must be true on any machine. A CPU run
gives no device number."""

import json
import os
import types

import pytest

import run as bench_run
from bench_helpers import HERE, REPO, listed, rehearse

CELL = "q7.catchup-25k"
NEW = {"dir_new_slot_pct": "%", "ckpt_delta_krows_per_capture": "krows"}
# what the cell shares with q5.catchup and a CPU run can give
COUNTED = {"host_cpu_cores", "dispatches_per_mevent",
           "compiles_in_window.catchup"}
# the loop's clock (ISSUE 38; entered by ISSUE 40 in all four cells): host
# times the program's ledger books, so a CPU run gives them too
LOOP = {"loop_idle_pct", "engine_offcore_pct", "host_leaf_offcore_pct",
        "engine_unnamed_cpu_pct"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def entry(group, name):
    return next(e for e in BENCH[group] if e["name"] == name)


@pytest.fixture(scope="module")
def traced():
    line, said = rehearse(CELL, seed=2**31 + 26, seconds=12, trace=1)
    line["said"] = said
    return line


def test_the_cell_is_q7_under_the_25k_traffic_on_one_chip():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark-q7", "catchup-25k", 1)
    # the configuration's entry is the one kept for it, but for its why
    with open(os.path.join(HERE, "data", "future_cells.json")) as f:
        kept = next(c for c in json.load(f)["configs"]
                    if c["name"] == "nexmark-q7")
    mine = entry("configs", "nexmark-q7")
    assert {k: v for k, v in mine.items() if k != "why"} == {
        k: v for k, v in kept.items() if k != "why"}
    assert "25k" in mine["why"] and "262,144" in mine["why"]


def test_the_traffic_is_catchup_100k_at_a_quarter_of_the_rate():
    def traffic(name):
        with open(os.path.join(
                REPO, "benchmark", "traffic", name + ".json")) as f:
            return json.load(f)

    mine, base = traffic("catchup-25k"), traffic("catchup-100k")
    assert mine["nominal_rate"] == 25_000 == base["nominal_rate"] // 4
    assert "25,000" in mine["why"] and len(mine["why"]) > 100
    for t in (mine, base):
        del t["nominal_rate"], t["why"]
    assert mine == base


def test_the_cell_reports_what_the_issue_lists_and_no_ledger_span():
    mine = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    known = {"setup_s", "events_per_s", "agg_update_call_us",
             "device_idle_pct.catchup", "state_hbm_peak_mb"} | COUNTED | set(
                 NEW) | LOOP
    assert known <= set(mine)           # a later PR may list the cell too
    # the eight older `program_span` metrics stay q5.catchup's (ISSUE 40
    # relaxed the pin and entered the loop's clock, nothing else)
    assert {n for n in known
            if mine[n]["source"] == "program_span"} == LOOP
    for name, unit in NEW.items():
        m = mine[name]
        assert (m["unit"], m["source"], m["moves"], m["workloads"]) == (
            unit, "program_counter", "events_per_s", [CELL])


def test_the_rehearsal_is_correct_over_more_than_ten_closes(traced):
    assert traced["correct"] is True and traced["rehearsal"] is True
    assert traced["failed"] == 0 and traced["attempted"] > 10
    compared = next(s for s in traced["said"] if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    booked = [s for s in traced["said"] if "conservation:" in s]
    assert len(booked) == 2 and all(
        "off by 0 (limit 0)" in s for s in booked)


def test_the_traced_line_holds_the_counted_metrics_and_the_two_new(traced):
    assert COUNTED | set(NEW) | LOOP <= set(
        traced["metrics"]) <= listed(BENCH, CELL, "per_layer")
    assert "breakdown" not in traced           # a CPU trace has no device
    for name, unit in NEW.items():
        assert traced["metrics"][name]["unit"] == unit


def test_nearly_every_row_of_the_keyed_operator_opens_a_slot(traced):
    """The count per (auction, price, bidder) sees a new key in all but a
    few rows; the window's max price, under no key, opens one slot a
    window and is not the operator the share is read from."""
    assert 80 <= traced["metrics"]["dir_new_slot_pct"]["value"] <= 100


def test_a_capture_carries_thousands_of_rows(traced):
    # 20,000 events a window at the rehearsal's 2,000 ev/s, a barrier
    # every 2 s: a delta holds the keys of more than one window
    krows = traced["metrics"]["ckpt_delta_krows_per_capture"]["value"]
    assert 1 < krows < 1000


def test_the_untraced_line_holds_the_end_to_end_metrics():
    line, _said = rehearse(CELL, seed=2**31 + 27, seconds=5)
    assert line["correct"] is True and line["failed"] == 0
    assert {"setup_s", "events_per_s"} <= set(
        line["metrics"]) == listed(BENCH, CELL, "end_to_end")
    assert line["metrics"]["events_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["drop", "dup"])
def test_a_lost_or_repeated_bid_comes_out_not_correct(fault):
    line, said = rehearse(CELL, "--control", fault, seed=26)
    assert line["correct"] is False
    assert any(s.split("] ")[1].startswith(f"control: {fault} event")
               for s in said)
    booked = [s for s in said if "conservation:" in s]
    assert booked and not any("off by 0 " in s for s in booked[:1])


def test_the_readers_give_none_without_the_ledger_and_do_not_raise(
        monkeypatch):
    """The parent commit's program books neither count: each reader
    returns None and the line leaves the metric out. So too on a run
    whose tasks are not known, and on a program with no ledger at all."""
    from arroyo_tpu.obs import timeline

    def a_run(**more):
        return types.SimpleNamespace(
            start={"t_ns": 1_000}, end={"t_ns": 2_000}, window_s=45.0,
            events_in_window=1_000_000, closes=[{}] * 20, **more)

    readers = [bench_run.load_module("layer_metrics", n) for n in NEW]
    for run in (a_run(flow={"3-0": (5, 5), "4-0": (5, 1)}), a_run(flow={}),
                a_run()):
        assert [r.read(run) for r in readers] == [None, None]
    monkeypatch.delattr(timeline, "totals")
    assert [r.read(a_run(flow={"3-0": (5, 5)})) for r in readers] == [
        None, None]


def test_the_readers_read_the_operator_with_the_most(monkeypatch):
    """Two tasks booked the counts: the share and the mean are those of
    the task with the larger count, not of the sum."""
    from arroyo_tpu import obs
    from arroyo_tpu.obs import timeline

    obs.reset()
    try:
        for task, rows, new, delta in (("3-0", 1000, 990, 5000),
                                       ("4-0", 1000, 2, 4)):
            timeline.note("dir.assign", 0.001, job="j", task=task, n=rows)
            timeline.note("dir.new", 0.0, job="j", task=task, n=new)
            for _ in range(2):
                timeline.note("ckpt.delta", 0.0, job="j", task=task, n=delta)
        import time

        now = time.time_ns()
        run = types.SimpleNamespace(
            start={"t_ns": now - 10**9}, end={"t_ns": now + 10**9},
            flow={"3-0": (0, 0), "4-0": (0, 0), "5-0": (0, 0)})
        share, krows = (bench_run.load_module("layer_metrics", n).read(run)
                        for n in NEW)
        assert share == pytest.approx(99.0) and krows == pytest.approx(5.0)
    finally:
        obs.reset()
