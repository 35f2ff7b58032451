"""The whole stream for a reference that asks (ISSUE 40): persons and
auctions as `gen_batch` sends them, a stub reference over them judged
through the harness, and the grid path held to the parent's lines.

`data/parent_lines.json` is the golden file: the `compared:`,
`conservation:` and `closes due in the window:` lines that the PARENT's
(269eaf2) `check.py` and `feed.py` printed in rehearsals of the four cells
at seeds 1, 2, 3, with the numbers of each run's feed. A rehearsal's window
ends where the wall clock says, so a second rehearsal never delivers the
same events; the test replays each run's numbers through this tree's
`check.judge` with an engine that is exact (the reference itself) and
holds the lines character for character."""

import importlib.util
import json
import os
import types

import numpy as np
import pyarrow as pa
import pytest

import check
import run as bench_run
from bench_helpers import HERE, REPO, rehearse
from feed import NS, Feed, Traffic
from gen import nexmark as gen
from test_bench_check import T0_NS, deliver, edges
from test_bench_check import judge as judge_delivered

STUB = os.path.join(HERE, "stub_cell.py")
STUB_CELLS = ("--workload", "persons-sellers.catchup", "--benchmark-file",
              os.path.join(HERE, "data", "stub_cells.json"))
with open(os.path.join(HERE, "data", "parent_lines.json")) as _f:
    GOLDEN = json.load(_f)
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("lo,hi,seed", [
    (0, 4096, 0), (50_000_000, 50_008_192, 3),
    (123_457, 123_457 + 777, 2**31 + 5)])
def test_events_are_the_batchs_columns_kind_for_kind(lo, hi, seed):
    ns = np.arange(lo, hi, dtype=np.int64)
    ts = gen.event_times(ns, 0, 1e5)
    batch = gen.gen_batch(ns, ts, seed)
    got = gen.events(ns, ts, seed)
    assert list(got) == ["person", "auction", "bid"]
    masks = gen.kinds(ns)
    sent = {"person": {"id": "id", "name": "name", "city": "city",
                       "state": "state", "ts": "datetime"},
            "auction": {"id": "id", "seller": "seller",
                        "category": "category", "initial_bid": "initial_bid",
                        "reserve": "reserve", "expires": "expires",
                        "ts": "datetime"},
            "bid": {"auction": "auction", "bidder": "bidder",
                    "price": "price", "ts": "datetime"}}
    for kind, fields in sent.items():
        col = batch.column(kind)
        assert np.array_equal(np.asarray(col.is_valid()), masks[kind])
        flat = col.filter(col.is_valid())
        assert set(got[kind]) == set(fields)
        for mine, theirs in fields.items():
            want = flat.field(theirs)
            if pa.types.is_timestamp(want.type):
                want = want.cast(pa.int64())
            assert got[kind][mine].tolist() == want.to_pylist(), (kind, mine)
    assert sum(int(m.sum()) for m in masks.values()) == len(ns)
    only = gen.events(ns, ts, seed, ("auction",))
    assert list(only) == ["auction"]
    is_bid, auction, bidder, price = gen.bids(ns, seed)
    assert np.array_equal(is_bid, masks["bid"])
    assert np.array_equal(got["bid"]["auction"], auction)


def make_feed(seed=11, n_events=30_000):
    t = Traffic(mode="catchup", nominal_rate=1000.0, first_event=20_000,
                warm_event_seconds=12, batch_rows=100)
    f = Feed(t, seed, seconds=10.0)
    f.n_window_start = f.n_warm
    f.n_delivered = f.n_window_end = f.n_first + n_events
    f.t_window_start, f.t_window_end = 0.0, 10.0
    return f


def test_the_wider_stream_is_regenerated_block_by_block(monkeypatch):
    feed = make_feed()
    whole = check.event_stream(feed, ("person", "auction"), 20_000, 50_000)
    monkeypatch.setattr(check, "BLOCK", 4_096)
    blocks = check.event_stream(feed, ("person", "auction"), 20_000, 50_000)
    assert list(blocks) == ["person", "auction"]
    for kind in blocks:
        assert set(blocks[kind]) == set(whole[kind])
        for name, col in blocks[kind].items():
            assert col.tolist() == whole[kind][name].tolist(), (kind, name)
    assert len(whole["person"]["ts"]) == 600
    assert len(whole["auction"]["ts"]) == 1_800
    assert (np.diff(whole["auction"]["ts"]) > 0).all()
    empty = check.event_stream(feed, ("person",), 20_000, 20_000)
    assert set(empty["person"]) == set(whole["person"])
    assert all(len(c) == 0 for c in empty["person"].values())


def stub():
    """`data/persons_sellers.py`, as `stub_cell.py` hands it to run.py."""
    spec = importlib.util.spec_from_file_location(
        "stub_persons_sellers",
        os.path.join(HERE, "data", "persons_sellers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_control_loses_an_event_of_a_kind_the_reference_reads():
    t = Traffic(mode="catchup", nominal_rate=1000.0, warm_event_seconds=12,
                batch_rows=100)
    reads = check.reads_of(stub())
    assert reads == ("person", "auction")
    picks = {seed: check.pick_fault("drop", t, seed, reads).event
             for seed in range(24)}
    assert all(n % 50 < 4 for n in picks.values())
    assert {n % 50 == 0 for n in picks.values()} == {True, False}
    # a reference that declares nothing: a bid, the draw the parent made
    for seed in range(6):
        n = check.pick_fault("drop", t, seed).event
        ns = np.arange(0, 12_000, dtype=np.int64)
        assert n == np.random.default_rng(seed).choice(
            ns[gen.bids(ns, seed)[0]])


def stub_judge(feed, reference, drop_person=None):
    """The reference itself as the engine, over a stream that one person
    may be missing from; its tasks book what they took in and gave out."""
    feed.schedule = check.schedule_of(reference, feed)
    (stream,) = check.reference_stream(
        feed, reference, feed.n_first, feed.n_delivered)
    ends = feed.schedule.due_by(feed.n_delivered)
    if drop_person is not None:
        keep = np.ones(len(stream["person"]["ts"]), dtype=bool)
        keep[drop_person] = False
        stream = {**stream, "person": {
            k: v[keep] for k, v in stream["person"].items()}}
    results = reference.compute(stream, ends)
    for end, rows in sorted(results.items()):
        ids, names = zip(*rows) if rows else ((), ())
        feed.arrived(pa.RecordBatch.from_arrays(
            [pa.array(list(ids), type=pa.int64()),
             pa.array(list(names), type=pa.string()),
             pa.array([end - 1] * len(rows), type=pa.int64()).cast(
                 pa.timestamp("ns"))], names=["id", "name", "_timestamp"]))
    flow = {f"{k}-0": (i, o) for k, (_w, i, o) in enumerate(
        reference.flows(stream, ends), 2)}
    run = types.SimpleNamespace(
        feed=feed, seconds=10.0, window_s=10.0, job_seconds=30.0,
        checkpoints=5, stated_interval_s=10.0, flow=flow, **edges(10.0))
    feed.barriers = [(1, T0_NS + NS)]
    said = []
    return check.judge(run, reference, {}, said.append), said


def test_a_reference_over_persons_and_auctions_is_judged_like_any_other():
    reference = stub()
    v, said = stub_judge(make_feed(), reference)
    assert v.correct and v.attempted > 0 and v.failed == 0
    assert "windows=2 " in said[0] and "rows=0 " not in said[0]
    # one person who sold in the window they came in, lost at the source:
    # their answer is missing and the first aggregate is a row short
    feed = make_feed()
    (stream,) = check.reference_stream(feed, reference, feed.n_first,
                                       feed.n_delivered)
    ends = check.schedule_of(reference, feed).due_by(feed.n_delivered)
    who = reference.compute(stream, ends)[ends[0]][0][0]
    lost = int(np.nonzero(stream["person"]["id"] == who)[0][0])
    v, said = stub_judge(feed, reference, drop_person=lost)
    assert not v.correct and "wrong=1 (limit 0)" in said[0]
    assert any("off by (-1, -1) (limit 0)" in s for s in said)


def test_the_stub_reference_is_correct_in_a_rehearsal():
    line, said = rehearse(*STUB_CELLS, seed=2**31 + 41, seconds=12,
                          script=STUB)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    compared = next(s for s in said if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    assert "rows=0 " not in compared
    booked = [s for s in said if "conservation:" in s]
    assert len(booked) == 2 and all(
        "off by 0 (limit 0)" in s for s in booked)


def test_the_stub_reference_with_one_person_dropped_is_not_correct():
    # seed 4's draw among the warm-up's persons and auctions is a person
    t = Traffic.from_dict({"mode": "catchup", "nominal_rate": 2000,
                           "warm_event_seconds": 12, "batch_rows": 512})
    n = check.pick_fault("drop", t, 4, ("person", "auction")).event
    assert n % 50 == 0
    line, said = rehearse(*STUB_CELLS, "--control", "drop", seed=4,
                          seconds=12, script=STUB)
    assert line["correct"] is False
    assert any(s.endswith(f"control: drop event {n}") for s in said)
    persons = next(s for s in said if "conservation: persons" in s)
    assert "off by 0 " not in persons


def replay(entry):
    """The lines this tree's `judge` prints over one golden run's numbers,
    with the reference itself as the engine."""
    cell = bench_run.Cell(entry["cell"])
    traffic = Traffic.from_dict(
        {**cell.traffic, **cell.traffic.get("rehearsal", {})})
    reference = bench_run.load_module("reference", cell.config["reference"])
    feed = Feed(traffic, entry["seed"], entry["seconds"])
    assert (feed.n_first, feed.n_warm) == (entry["n_first"], entry["n_warm"])
    feed.schedule = check.schedule_of(reference, feed)
    for name in ("n_window_start", "n_window_end", "n_delivered"):
        setattr(feed, name, entry[name])
    feed.t_window_start, feed.t_window_end = 0.0, float(entry["seconds"])
    stream = check.reference_stream(
        feed, reference, feed.n_first, feed.n_delivered)
    ends = feed.schedule.due_by(feed.n_delivered)
    # the end-of-stream flush also emits the windows still open
    flush = [ends[-1] + reference.SLIDE_NS * k for k in (1, 2)]
    deliver(feed, reference, reference.compute(*stream, ends + flush))
    verdict, said = judge_delivered(
        feed, reference, window_s=float(entry["seconds"]))
    assert verdict.correct
    return [s for s in said if s.startswith(
        ("compared:", "conservation:", "closes due in the window:"))]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]][:4])
def test_the_grid_path_prints_the_parents_lines(cell):
    runs = [e for e in GOLDEN["runs"] if e["cell"] == cell]
    assert sorted(e["seed"] for e in runs) == [1, 2, 3]
    for entry in runs:
        assert len(entry["lines"]) >= 3
        assert replay(entry) == entry["lines"], (cell, entry["seed"])
