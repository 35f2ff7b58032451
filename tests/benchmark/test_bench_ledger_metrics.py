"""The per-layer metrics that read the program's own phase ledger
(`arroyo_tpu/obs/timeline.py`; readers under `benchmark/layer_metrics/`,
`benchmark/ledger_window.py`): ONE traced rehearsal of `q5.catchup` on
XLA's CPU backend, whose result line every test here reads. A CPU run
gives host times and counts, never a device number; the tests hold them to
what must be true on any machine."""

import json
import os
import re
import types

import pytest

import run as bench_run
import trace_reduce
from bench_helpers import REPO, listed, rehearse

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
EIGHT = ["dir_assign_us_per_kevent", "agg_pack_us_per_kevent",
         "agg_update_rows_per_call", "agg_update_pad_pct", "close_host_ms",
         "close_combine_ms", "join_close_ms", "engine_unnamed_pct"]
LEDGER = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in EIGHT}
COUNTED = {"host_cpu_cores", "dispatches_per_mevent",
           "compiles_in_window.catchup"}     # what a CPU run gave before
# the loop's clock, entered by ISSUE 40 in every cell
# (test_bench_loop_clock.py rehearses it)
LOOP = {"loop_idle_pct", "engine_offcore_pct", "host_leaf_offcore_pct",
        "engine_unnamed_cpu_pct"}


@pytest.fixture(scope="module")
def traced():
    line, said = rehearse("q5.catchup", seed=2**31 + 24, trace=1)
    sizes = next(s for s in said if "events_in_window=" in s)
    line["window_s"] = float(re.search(r"window_s=([0-9.]+)", sizes).group(1))
    line["events"] = int(re.search(r"events_in_window=(\d+)", sizes).group(1))
    return line


def value(line, name):
    return line["metrics"][name]["value"]


def test_the_entries_are_the_eight_of_the_issue():
    """These eight are there, unchanged and in their order; a later
    `program_span` entry (the loop's clock, `rank_close_ms`: ISSUE 40)
    comes after them and is its own test's to hold."""
    assert list(LEDGER) == EIGHT
    for m in LEDGER.values():
        assert m["source"] == "program_span"
        assert m["moves"] == "events_per_s"
        assert m["workloads"] == ["q5.catchup"]
    spans = [m["name"] for m in BENCH["per_layer"]
             if m["source"] == "program_span"]
    assert spans[:8] == EIGHT


@pytest.mark.parametrize("name", sorted(LEDGER))
def test_each_metric_is_in_the_traced_line_with_its_unit(traced, name):
    assert traced["correct"] is True and traced["failed"] == 0
    got = traced["metrics"][name]
    assert got["unit"] == LEDGER[name]["unit"]
    assert got["value"] >= 0 and got["value"] == got["value"]   # not NaN


def test_the_line_holds_the_old_metrics_and_the_new_and_no_other(traced):
    assert COUNTED | set(LEDGER) | LOOP <= set(
        traced["metrics"]) <= listed(BENCH, "q5.catchup", "per_layer")
    assert "breakdown" not in traced           # a CPU trace has no device


def test_named_host_time_fits_inside_the_window(traced):
    closes, events = traced["attempted"], traced["events"]
    assert closes > 10 and events > 20_000
    per_close_s = (value(traced, "close_host_ms")
                   + value(traced, "join_close_ms")) / 1e3
    per_kevent_s = (value(traced, "dir_assign_us_per_kevent")
                    + value(traced, "agg_pack_us_per_kevent")) / 1e6
    named = per_close_s * closes + per_kevent_s * events / 1e3
    # the ledger answers for the buckets that start inside the window: up
    # to a quarter second more or less than the window at its edges
    from arroyo_tpu.obs import timeline

    window_s = traced["window_s"] + timeline.BUCKET_US / 1e6
    assert 0 < named <= window_s
    # the combine is one leaf of the close
    assert 0 < value(traced, "close_combine_ms") <= value(
        traced, "close_host_ms")
    # and what the ledger cannot name is the rest of the window at most
    unnamed_s = value(traced, "engine_unnamed_pct") / 100 * traced["window_s"]
    assert named + unnamed_s <= window_s


def test_percentages_lie_between_0_and_100(traced):
    for name, m in LEDGER.items():
        if m["unit"] == "%":
            assert 0 <= value(traced, name) <= 100, name


def test_rows_per_call_and_padding_agree_with_the_buckets(traced):
    """Real rows per `agg.update` call and the padding share give the
    padded rows per call, which lie on the program's shape buckets: no
    call ships fewer rows than it holds, none more than the largest
    bucket."""
    from arroyo_tpu.config import config

    rows = value(traced, "agg_update_rows_per_call")
    pad = value(traced, "agg_update_pad_pct")
    buckets = config().tpu.shape_buckets
    padded = rows / (1 - pad / 100)
    assert 0 < rows <= padded
    assert min(buckets) <= padded <= max(buckets)


def test_a_gap_mostly_inside_a_leaf_carries_its_name():
    """`trace_reduce.attribute_gaps` names an engine gap for the one host
    event that covers more than half of it: a `close.combine` annotation
    over two thirds of a 0.9 s gap."""
    s = 1_000_000_000
    merged = [(0, 0), (1 * s, int(1.1 * s)), (2 * s, int(2.1 * s)),
              (3 * s, 3 * s)]
    calls = [("close.combine", int(1.2 * s), int(1.8 * s)),
             ("close.build", int(1.8 * s), int(1.95 * s)),
             ("dir.assign", int(2.2 * s), int(2.4 * s))]
    gaps = trace_reduce.attribute_gaps(merged, [], calls)
    # a gap split among leaves none of which covers half stays `engine`
    assert sorted(gaps) == [("engine", pytest.approx(0.9)),
                            ("engine", pytest.approx(1.0)),
                            ("engine:close.combine", pytest.approx(0.9))]


def test_a_program_without_the_ledger_gives_none_and_does_not_raise(
        monkeypatch):
    """The parent commit's program has no `timeline.totals`: each reader
    returns None and the line leaves the metric out. So too where the
    ledger booked nothing in the window. (One test for the eight readers:
    no file of these tests may reach twenty.)"""
    from arroyo_tpu.obs import timeline

    def a_run():
        return types.SimpleNamespace(
            start={"t_ns": 1_000}, end={"t_ns": 2_000}, window_s=45.0,
            events_in_window=1_000_000, closes=[{}] * 60)

    readers = [bench_run.load_module("layer_metrics", name)
               for name in LEDGER]
    assert [r.read(a_run()) for r in readers] == [None] * 8   # none booked
    monkeypatch.delattr(timeline, "totals")
    assert [r.read(a_run()) for r in readers] == [None] * 8   # no ledger
