"""The cell `q5-mesh4.catchup` (ISSUE 30): NEXmark q5 with window state
sharded over four chips, read from BENCHMARK.json itself. ONE traced
rehearsal on four of the test environment's virtual CPU devices serves the
tests of the result line; the three counts the cell brings are counts, so a
CPU run may hold them to what must be true on any machine. A CPU run gives
no device number: `mesh_route_call_us` is tested on made-up traces."""

import json
import os
import types

import pytest

import run as bench_run
from bench_helpers import HERE, REPO, listed, rehearse

CELL = "q5-mesh4.catchup"
CONFIG = "nexmark-q5-mesh4"
# name: (unit, better, source); all of layer "mesh exchange"
NEW = {"exchange_padding_pct": ("%", "lower", "program_counter"),
       "mesh_busiest_shard_pct": ("%", "lower", "program_counter"),
       "mesh_rows_per_dispatch": ("rows", "higher", "program_counter"),
       "mesh_route_call_us": ("us", "lower", "device_trace")}
COUNTED_NEW = set(NEW) - {"mesh_route_call_us"}
# what the cell shares with the one-chip cells and a CPU run can give
COUNTED = {"host_cpu_cores", "dispatches_per_mevent",
           "compiles_in_window.catchup"}
# the loop's clock (ISSUE 38; entered by ISSUE 40 in all four cells): host
# times the program's ledger books, so a CPU run gives them too
LOOP = {"loop_idle_pct", "engine_offcore_pct", "host_leaf_offcore_pct",
        "engine_unnamed_cpu_pct"}
# the cells the benchmark had when this one was appended, in their order
OLDER = ["q5.catchup", "q7.catchup-25k"]

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def entry(group, name):
    return next(e for e in BENCH[group] if e["name"] == name)


def reader(name):
    return bench_run.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def traced():
    line, said = rehearse(CELL, seed=2**31 + 30, seconds=6, trace=1)
    line["said"] = said
    return line


def test_the_cell_is_the_kept_configuration_on_four_chips():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "catchup-100k", 4)
    # the first cell to ask for four
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4][:1] == [
        CELL]
    with open(os.path.join(HERE, "data", "future_cells.json")) as f:
        kept = next(c for c in json.load(f)["configs"]
                    if c["name"] == CONFIG)
    mine = entry("configs", CONFIG)
    assert {k: v for k, v in mine.items() if k != "why"} == {
        k: v for k, v in kept.items() if k != "why"}
    assert mine["reduced"] == ["nominal_rate", "stream_rate"]


def test_the_configuration_differs_from_q5s_by_the_mesh_alone():
    def config(name):
        with open(os.path.join(
                REPO, "benchmark", "configs", name + ".json")) as f:
            return json.load(f)

    def text(name):
        with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
            return f.read()

    mine, base = config(CONFIG), config("nexmark-q5")
    assert mine["settings"]["tpu"] == {"mesh_devices": 4}
    del mine["settings"]["tpu"]
    for key in ("settings", "setup_settings", "reference", "guarantees",
                "watermark_delay_s", "late_limit_ms"):
        assert mine[key] == base.get(key), key
    assert text(mine["sql"]) == text(base["sql"])
    assert mine["chips"] == 4


def test_the_traffic_is_the_backlog_replay_the_issue_names():
    with open(os.path.join(
            REPO, "benchmark", "traffic", "catchup-100k.json")) as f:
        t = json.load(f)
    assert (t["mode"], t["nominal_rate"], t["batch_rows"],
            t["look_ahead_batches"], t["warm_event_seconds"]) == (
        "catchup", 100_000, 8192, 32, 12)


def test_the_cell_reports_what_the_issue_lists_and_no_ledger_span():
    mine = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    known = {"setup_s", "events_per_s", "device_idle_pct.catchup",
             "state_hbm_peak_mb"} | COUNTED | set(NEW) | LOOP
    assert known <= set(mine)           # a later PR may list the cell too
    # no ledger span of its own: the loop's clock is every cell's
    assert {n for n in known
            if mine[n]["source"] == "program_span"} == LOOP
    for name, (unit, better, source) in NEW.items():
        m = mine[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            unit, better, source, "mesh exchange", "events_per_s", [CELL])
    # appended when the list held sixteen, and the older entries stand
    # where they stood, in their order: a later cell's come after them (an
    # entry put before the end reads as a change to what was there)
    assert [m["name"] for m in BENCH["per_layer"]][16:20] == list(NEW)
    for name in known:
        if name not in NEW and "workloads" in mine[name]:
            assert mine[name]["workloads"][:3] == OLDER + [CELL], name


def test_the_rehearsal_is_correct_over_more_than_ten_closes(traced):
    assert traced["correct"] is True and traced["rehearsal"] is True
    assert traced["failed"] == 0 and traced["attempted"] > 10
    assert traced["device"]["count"] >= 4
    compared = next(s for s in traced["said"] if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    booked = [s for s in traced["said"] if "conservation:" in s]
    assert len(booked) == 2 and all(
        "off by 0 (limit 0)" in s for s in booked)


def test_the_traced_line_holds_the_counted_metrics_and_the_three_new(traced):
    assert COUNTED | COUNTED_NEW | LOOP <= set(
        traced["metrics"]) <= listed(BENCH, CELL, "per_layer")
    assert "breakdown" not in traced           # a CPU trace has no device
    for name in COUNTED_NEW:
        assert traced["metrics"][name]["unit"] == NEW[name][0]


def test_the_exchange_ships_filler_and_one_shard_owns_the_most(traced):
    """Filler rides every step but never all of it; the busiest shard of
    a step owns at least its even quarter of the rows. A hot auction's
    bins all live on one shard, so a flush that spans few hot auctions (the
    rehearsal's: one holds for 1,667 events) is well above the quarter; a
    chip's 60,000-row flush spans ~39 and reads 28 % (PERF.md section 6).
    The window max spreads by position and books its even share."""
    value = {n: traced["metrics"][n]["value"] for n in COUNTED_NEW}
    assert 0 <= value["exchange_padding_pct"] < 100
    assert 25 <= value["mesh_busiest_shard_pct"] <= 100
    assert value["mesh_busiest_shard_pct"] > 30
    assert value["mesh_rows_per_dispatch"] >= 1


def test_the_untraced_line_holds_the_end_to_end_metrics():
    line, _said = rehearse(CELL, seed=2**31 + 31, seconds=4)
    assert line["correct"] is True and line["failed"] == 0
    assert {"setup_s", "events_per_s"} <= set(
        line["metrics"]) == listed(BENCH, CELL, "end_to_end")
    assert line["metrics"]["events_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["drop", "dup"])
def test_a_lost_or_repeated_bid_comes_out_not_correct(fault):
    line, said = rehearse(CELL, "--control", fault, seed=30)
    assert line["correct"] is False
    assert any(s.split("] ")[1].startswith(f"control: {fault} event")
               for s in said)
    booked = [s for s in said if "conservation:" in s]
    assert booked and not any("off by 0 " in s for s in booked[:1])


def a_run(mesh_start=None, mesh_end=None, modules=None, **more):
    trace = None if modules is None else types.SimpleNamespace(
        modules=modules)
    return types.SimpleNamespace(
        start={"t_ns": 1_000, "mesh": mesh_start},
        end={"t_ns": 2_000, "mesh": mesh_end}, trace=trace,
        events_in_window=1_000_000, **more)


def test_the_readers_give_none_without_the_counters_and_do_not_raise():
    """The parent commit's program counts no `rows_busiest` and calls
    every step `jit_step`; a one-chip run ships nothing; a run cut before
    its window has no counters at all. Each reader returns None."""
    old = {"rows_sent": 10, "rows_padded": 30, "dispatches": 2}
    idle = {**old, "rows_busiest": 0}
    assert reader("mesh_busiest_shard_pct").read(a_run(old, old)) is None
    assert reader("mesh_busiest_shard_pct").read(a_run(idle, idle)) is None
    assert reader("mesh_rows_per_dispatch").read(a_run(idle, idle)) is None
    for name in COUNTED_NEW - {"exchange_padding_pct"}:
        assert reader(name).read(a_run()) is None
        assert reader(name).read(a_run({}, {})) is None
        assert reader(name).read(types.SimpleNamespace(
            start={}, end={}, trace=None)) is None
    parent = {"jit_step": {"seconds": 3.6, "calls": 900},
              "jit_fn": {"seconds": 0.2, "calls": 300}}
    for run in (a_run(), a_run(modules={}), a_run(modules=parent),
                types.SimpleNamespace(start={}, end={})):
        assert reader("mesh_route_call_us").read(run) is None


def test_the_readers_arithmetic():
    start = {"rows_sent": 100, "rows_padded": 50, "dispatches": 1,
             "rows_busiest": 40}
    end = {"rows_sent": 1100, "rows_padded": 3050, "dispatches": 11,
           "rows_busiest": 640}
    run = a_run(start, end, modules={
        "jit_mesh_route": {"seconds": 2.0, "calls": 900},
        "jit_mesh_step_direct": {"seconds": 1.0, "calls": 100},
        "jit_mesh_stake": {"seconds": 9.0, "calls": 5}})
    assert reader("exchange_padding_pct").read(run) == pytest.approx(75.0)
    assert reader("mesh_busiest_shard_pct").read(run) == pytest.approx(60.0)
    assert reader("mesh_rows_per_dispatch").read(run) == pytest.approx(100.0)
    assert reader("mesh_route_call_us").read(run) == pytest.approx(3000.0)
