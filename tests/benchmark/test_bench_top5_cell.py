"""The cell `top5-hop60.catchup` (ISSUE 33): upstream Arroyo's first-pipeline
query (the top 5 auctions of the last minute, refreshed every 2 s) on the
NEXmark stream at 100,000 events/s of event time, read from BENCHMARK.json
itself. ONE traced rehearsal on XLA's CPU backend serves the tests of the
result line; the two counts this cell brings read the program's ledger and
may be held on a CPU run to what must be true on any machine. A third
reader, `rank_close_ms`, had no entry until ISSUE 40 relaxed the pin of
`program_span` to its eight (`test_bench_ledger_metrics.py`) and appended
it with the loop's clock. A CPU run gives no device number. Every
rehearsal runs 12 s and more, six of its 2 s checkpoint intervals: a
shorter one has failed under tier-1's six workers (PERF.md section 7)."""

import json
import os
import time
import types

import pytest

import run as bench_run
from bench_helpers import REPO, listed, rehearse

CELL = "top5-hop60.catchup"
CONFIG = "nexmark-top5-hop60"
TRAFFIC = "catchup-100k-warm62"
# name: (unit, source, layer)
NEW = {"rank_krows_per_close": (
           "krows", "program_counter", "window functions"),
       "close_kslots_per_close": (
           "kslots", "program_counter", "state + emission")}
KEPT = "rank_close_ms"      # its entry came with ISSUE 40: per_layer[26]
# the loop's clock (ISSUE 38; entered by ISSUE 40 in all four cells)
LOOP = {"loop_idle_pct", "engine_offcore_pct", "host_leaf_offcore_pct",
        "engine_unnamed_cpu_pct"}
# what the cell shares with the other one-chip cells and a CPU run can give
COUNTED = {"host_cpu_cores", "dispatches_per_mevent",
           "compiles_in_window.catchup"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def entry(group, name):
    return next(e for e in BENCH[group] if e["name"] == name)


def benchmark_file(*path):
    with open(os.path.join(REPO, "benchmark", *path)) as f:
        return f.read()


def reader(name):
    return bench_run.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def traced():
    line, said = rehearse(CELL, seed=2**31 + 33, seconds=14, trace=1)
    line["said"] = said
    return line


def test_the_cell_is_the_issues_letter_for_letter():
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    mine = entry("configs", CONFIG)
    assert mine["file"] == f"benchmark/configs/{CONFIG}.json"
    assert mine["reduced"] == ["nominal_rate"]
    assert BENCH["run_seconds"] == 45


def test_the_traffic_is_catchup_100k_with_a_warm_up_of_a_whole_window():
    mine = json.loads(benchmark_file("traffic", TRAFFIC + ".json"))
    base = json.loads(benchmark_file("traffic", "catchup-100k.json"))
    assert (mine["mode"], mine["nominal_rate"], mine["batch_rows"],
            mine["look_ahead_batches"], mine["warm_event_seconds"]) == (
        "catchup", 100_000, 8192, 32, 62)
    assert base["warm_event_seconds"] == 12
    for t in (mine, base):
        del t["warm_event_seconds"], t["why"]
    assert mine == base             # key for key, the rehearsal's too


def test_the_query_is_the_published_one_but_for_the_tie_break():
    sql = " ".join(benchmark_file("configs", CONFIG + ".sql").split())
    for published in (
            "hop(interval '2 seconds', interval '60 seconds') AS window",
            "WHERE bid is not null", "GROUP BY 2, window",
            "ROW_NUMBER() OVER ( PARTITION BY window ORDER BY count DESC",
            "WHERE row_num <= 5"):
        assert published in sql, published
    assert "ORDER BY count DESC, auction DESC) AS row_num" in sql
    cfg = json.loads(benchmark_file("configs", CONFIG + ".json"))
    q5 = json.loads(benchmark_file("configs", "nexmark-q5.json"))
    assert cfg["reference"] == "top5" and cfg["chips"] == 1
    for key in ("settings", "guarantees", "watermark_delay_s", "layout",
                "rehearsal_settings"):
        assert cfg[key] == q5[key], key
    assert "setup_settings" not in cfg
    assert list(cfg["reduced"]) == ["nominal_rate"]
    assert {"query", "tie_break", "generator"} <= set(cfg["assumed"])
    assert "from memory" in cfg["assumed"]["query"]
    assert "auction DESC" in cfg["assumed"]["tie_break"]
    assert cfg["assumed"]["generator"] == q5["assumed"]["generator"]
    import reference.top5 as top5

    assert (top5.SIZE_NS, top5.SLIDE_NS, top5.TOP, top5.COLUMNS) == (
        60 * 10**9, 2 * 10**9, 5, ("auction", "count", "row_num"))
    assert "arroyo_tpu" not in benchmark_file(
        "reference", "top5.py").replace("nothing of the\nprogram", "")


def test_the_cell_reports_the_shared_seven_and_its_own_two():
    mine = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    known = {"setup_s", "events_per_s", "agg_update_call_us",
             "device_idle_pct.catchup", "state_hbm_peak_mb"} | COUNTED | set(
                 NEW) | LOOP | {KEPT}
    assert known <= set(mine)           # a later PR may list the cell too
    for name, (unit, source, layer) in NEW.items():
        m = mine[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (
            unit, "lower", source, layer, "events_per_s", [CELL])
    # the eight older ledger spans stay q5.catchup's; this cell has the
    # loop's clock and, where ISSUE 40 appended it, its ranking's host time
    assert {n for n in known
            if mine[n]["source"] == "program_span"} == LOOP | {KEPT}
    assert BENCH["per_layer"][26] == {
        "name": KEPT, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "window functions",
        "moves": "events_per_s", "workloads": [CELL]}
    # membership, not the last place: the next cell is appended after this
    for name in known - set(NEW) - {"setup_s", KEPT}:
        assert mine[name]["workloads"].count(CELL) == 1, name
        assert "q5.catchup" in mine[name]["workloads"], name


# what the benchmark held before this cell, in its order: (name, cells)
HELD = [
    ("setup_s", None),
    ("events_per_s", "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("host_cpu_cores", "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("dispatches_per_mevent", "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("agg_update_call_us", "q5.catchup q7.catchup-25k"),
    ("compiles_in_window.catchup",
     "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("device_idle_pct.catchup", "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("state_hbm_peak_mb", "q5.catchup q7.catchup-25k q5-mesh4.catchup"),
    ("dir_assign_us_per_kevent", "q5.catchup"),
    ("agg_pack_us_per_kevent", "q5.catchup"),
    ("agg_update_rows_per_call", "q5.catchup"),
    ("agg_update_pad_pct", "q5.catchup"),
    ("close_host_ms", "q5.catchup"),
    ("close_combine_ms", "q5.catchup"),
    ("join_close_ms", "q5.catchup"),
    ("engine_unnamed_pct", "q5.catchup"),
    ("dir_new_slot_pct", "q7.catchup-25k"),
    ("ckpt_delta_krows_per_capture", "q7.catchup-25k"),
    ("exchange_padding_pct", "q5-mesh4.catchup"),
    ("mesh_busiest_shard_pct", "q5-mesh4.catchup"),
    ("mesh_rows_per_dispatch", "q5-mesh4.catchup"),
    ("mesh_route_call_us", "q5-mesh4.catchup")]


def test_the_cell_is_appended_and_what_was_held_stands_where_it_stood():
    """The driver reads an entry put first or in the middle as a change to
    what was there, so this cell's go LAST, after the mesh cell's. That
    trips `test_bench_mesh_cell.py`'s pin of the mesh cell to the last
    place (a file this PR may not edit; PERF.md section 7); this test
    holds what that pin protected: every entry the benchmark had is where
    it was, its cells in their order, and this cell's come after them."""
    # each list by itself: a later end-to-end metric is appended to its own
    lists = {"end_to_end": HELD[:2], "per_layer": HELD[2:]}
    pairs = [(m, h) for group in lists
             for m, h in zip(BENCH[group], lists[group])]
    assert len(pairs) == len(HELD)
    for m, (name, cells) in pairs:
        assert m["name"] == name
        held = cells.split() if cells else None
        if held is None:
            assert "workloads" not in m
            continue
        assert m["workloads"][:len(held)] == held, name
        assert m["workloads"][len(held):][:1] in ([], [CELL]), name
    n = len(lists["per_layer"])
    assert [m["name"] for m in BENCH["per_layer"][n:n + len(NEW)]] == list(
        NEW)
    assert [c["name"] for c in BENCH["configs"]][:4] == [
        "nexmark-q5", "nexmark-q7", "nexmark-q5-mesh4", CONFIG]
    assert [w["name"] for w in BENCH["workloads"]][:4] == [
        "q5.catchup", "q7.catchup-25k", "q5-mesh4.catchup", CELL]


def test_the_rehearsal_is_correct_over_tens_of_closes(traced):
    assert traced["correct"] is True and traced["rehearsal"] is True
    assert traced["failed"] == 0 and traced["attempted"] > 30
    compared = next(s for s in traced["said"] if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    booked = [s for s in traced["said"] if "conservation:" in s]
    assert len(booked) == 1 and "off by 0 (limit 0)" in booked[0]
    cadence = next(s for s in traced["said"] if "cadence:" in s)
    assert int(cadence.split("checkpoints_in_window=")[1].split()[0]) >= 3


def test_the_traced_line_holds_the_counted_metrics_and_the_two_new(traced):
    assert COUNTED | set(NEW) | LOOP | {KEPT} <= set(
        traced["metrics"]) <= listed(BENCH, CELL, "per_layer")
    assert "breakdown" not in traced           # a CPU trace has no device
    for name, (unit, _source, _layer) in NEW.items():
        assert traced["metrics"][name]["unit"] == unit


def test_a_close_merges_thirty_bins_and_ranks_every_auction_for_five(traced):
    """At the rehearsal's 2,000 events/s a window holds 110,400 bids on
    some thousands of auctions: the ranking orders every one of them, a
    close merges their slots over up to 30 live bins (more slots than
    auctions: one that takes bids across a slice's edge has two), and five
    rows a window reach the sink."""
    value = {n: traced["metrics"][n]["value"] for n in NEW}
    assert value["rank_krows_per_close"] > 1
    assert value["close_kslots_per_close"] >= value["rank_krows_per_close"]
    assert value["close_kslots_per_close"] < 2 * value[
        "rank_krows_per_close"]
    compared = next(s for s in traced["said"] if "compared:" in s)
    windows, rows = (int(compared.split(k + "=")[1].split()[0])
                     for k in ("windows", "rows"))
    assert rows == 5 * windows


def test_the_untraced_line_holds_the_end_to_end_metrics():
    line, _said = rehearse(CELL, seed=2**31 + 34, seconds=12)
    assert line["correct"] is True and line["failed"] == 0
    assert {"setup_s", "events_per_s"} <= set(
        line["metrics"]) == listed(BENCH, CELL, "end_to_end")
    assert line["metrics"]["events_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["drop", "dup"])
def test_a_lost_or_repeated_bid_comes_out_not_correct(fault):
    """Five rows a window answer the query: a bid lost on a cold auction
    changes none of them. The one task's row count does."""
    line, said = rehearse(CELL, "--control", fault, seed=33, seconds=12)
    assert line["correct"] is False
    assert any(s.split("] ")[1].startswith(f"control: {fault} event")
               for s in said)
    booked = [s for s in said if "conservation:" in s]
    assert booked and not any("off by 0 " in s for s in booked)


def a_run(**more):
    return types.SimpleNamespace(
        start={"t_ns": 1_000}, end={"t_ns": 2_000}, window_s=45.0,
        events_in_window=1_000_000, closes=[{}] * 20, **more)


def test_the_readers_give_none_without_the_phases_and_do_not_raise(
        monkeypatch):
    """The parent commit's program books no `rank.*`: the two readers of
    them return None and the line leaves the metrics out. So does every
    reader on a run whose tasks are not known, on a window that holds no
    entry, and on a program with no ledger at all."""
    from arroyo_tpu.obs import timeline

    readers = [reader(n) for n in (KEPT, *NEW)]
    for run in (a_run(flow={"3-0": (5, 5)}), a_run(flow={}), a_run()):
        assert [r.read(run) for r in readers] == [None, None, None]
    monkeypatch.delattr(timeline, "totals")
    assert [r.read(a_run(flow={"3-0": (5, 5)})) for r in readers] == [
        None, None, None]


def test_the_readers_arithmetic():
    """`rank.sort` and `rank.build` by their total seconds and `rank.emit`
    by its self time, `rank.buffer` not at all, per close due; rows per bin
    ranked; slots per close in the operator that merges the most."""
    from arroyo_tpu import obs
    from arroyo_tpu.obs import timeline

    obs.reset()
    try:
        for _ in range(4):
            timeline.note("rank.buffer", 0.5, job="j", task="3-0", n=9000)
            timeline.note("rank.sort", 0.030, job="j", task="3-0", n=9000)
            timeline.note("rank.build", 0.010, job="j", task="3-0", n=9000)
            with timeline.phase("rank.emit", job="j", task="3-0", n=9000):
                timeline.note("emit", 0.25)     # downstream, inside it
            timeline.note("close.take", 0.001, job="j", task="3-0", n=12000)
            timeline.note("close.take", 0.001, job="j", task="4-0", n=10)
        now = time.time_ns()
        run = types.SimpleNamespace(
            start={"t_ns": now - 10**9}, end={"t_ns": now + 10**9},
            closes=[{}] * 4, flow={"3-0": (0, 0), "4-0": (0, 0)})
        ms, krows, kslots = (reader(n).read(run) for n in (KEPT, *NEW))
        assert 40.0 <= ms < 45.0        # 30 + 10 and a sliver of self time
        assert krows == pytest.approx(9.0)
        assert kslots == pytest.approx(12.0)
    finally:
        obs.reset()
