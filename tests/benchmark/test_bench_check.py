"""`correct` fails on a single lost, duplicated or miscounted event, whether
an answer rests on it or not. The "system" here is the plain reference
itself over a stream with the fault in it: an engine that is exact on what
it was given, and whose tasks book the rows they took in and gave out."""

import types

import numpy as np
import pyarrow as pa
import pytest

import check
import schedule
from feed import NS, Feed, Traffic
from reference import q5, q7

RATE = 1000.0
FIRST = 20_000
SEED = 11
T0_NS = 1_790_000_000 * NS     # the window's start on the wall clock


def make_feed(reference=q5, n_events=30_000):
    t = Traffic(mode="catchup", nominal_rate=RATE, first_event=FIRST,
                warm_event_seconds=12, batch_rows=100)
    f = Feed(t, SEED, seconds=10.0)
    f.schedule = check.schedule_of(reference, f)
    f.n_window_start = f.n_warm
    f.n_delivered = f.n_window_end = FIRST + n_events
    f.t_window_start, f.t_window_end = 0.0, 10.0
    return f


def stream(feed, drop=None, dup=None):
    ts, auction, bidder, price = check.bid_stream(
        feed, feed.n_first, feed.n_delivered)
    keep = np.ones(len(ts), dtype=bool)
    if drop is not None:
        keep[drop] = False
    cols = [c[keep] for c in (ts, auction, bidder, price)]
    if dup is not None:
        order = np.sort(np.append(np.arange(len(cols[0])), dup))
        cols = [c[order] for c in cols]
    return cols


def deliver(feed, reference, results):
    """Hand the feed's sink one batch per window, as the engine would."""
    for end, rows in sorted(results.items()):
        if not rows:
            continue
        cols = list(zip(*rows))
        arrays = [pa.array(list(c), type=pa.int64()) for c in cols]
        arrays.append(pa.array([end - 1] * len(rows), type=pa.int64()).cast(
            pa.timestamp("ns")))
        feed.arrived(pa.RecordBatch.from_arrays(
            arrays, names=list(reference.COLUMNS) + ["_timestamp"]))


def edges(window_s):
    """The window's edges on the wall clock, as `run.py` records them."""
    return {"t_job0_ns": T0_NS - 5 * NS, "start": {"t_ns": T0_NS},
            "end": {"t_ns": T0_NS + int(window_s * 1e9)}}


def judge(feed, reference, checkpoints=5, window_s=10.0, barriers=(),
          **fault):
    # barriers: seconds into the window at which each was initiated
    feed.barriers = [(k + 1, T0_NS + int(t * 1e9))
                     for k, t in enumerate(barriers)]
    run = types.SimpleNamespace(
        feed=feed, seconds=window_s, window_s=window_s, job_seconds=30.0,
        checkpoints=checkpoints, stated_interval_s=10.0,
        flow=engine_flow(feed, reference, **fault), **edges(window_s))
    said = []
    v = check.judge(run, reference, {}, said.append)
    return v, said


def engine_results(feed, reference, **fault):
    ends = feed.schedule.due_by(feed.n_delivered)
    # the end-of-stream flush also emits the windows still open
    flush = [ends[-1] + reference.SLIDE_NS * k for k in (1, 2)]
    return reference.compute(*stream(feed, **fault), ends + flush)


def engine_flow(feed, reference, **fault):
    """{task: (rows received, rows sent)} of an engine that is exact on the
    stream it was given, with a stateless task before and after."""
    ends = feed.schedule.due_by(feed.n_delivered)
    steps = reference.flows(*stream(feed, **fault), ends)
    flow = {f"{k}-0": (rows_in, rows_out)
            for k, (_what, rows_in, rows_out) in enumerate(steps, 2)}
    flow["1-0"] = (0, steps[0][1])
    flow["9-0"] = (len(ends), 0)
    return flow


def a_bid_no_answer_rests_on(feed, reference):
    """A bid drawn from the seed whose loss or repeat changes no answer."""
    ts, auction, bidder, price = stream(feed)
    ends = feed.schedule.due_by(feed.n_delivered)
    sound = reference.compute(ts, auction, bidder, price, ends)
    rng = np.random.default_rng(SEED)
    while True:
        i = int(rng.integers(len(ts)))
        if all(reference.compute(*stream(feed, **{kind: i}), ends) == sound
               for kind in ("drop", "dup")):
            return i


def a_bid_an_answer_rests_on(feed, reference):
    ts, auction, bidder, price = stream(feed)
    ends = feed.schedule.due_by(feed.n_delivered)
    end = ends[len(ends) // 2]
    row = reference.compute(ts, auction, bidder, price, [end])[end][0]
    m = (ts >= end - reference.SIZE_NS) & (ts < end)
    for name, value in zip(reference.COLUMNS, row):
        col = {"auction": auction, "bidder": bidder, "price": price}.get(name)
        if col is not None:
            m &= col == value
    return int(np.nonzero(m)[0][0])


@pytest.mark.parametrize("reference", [q5, q7], ids=["q5", "q7"])
def test_a_sound_run_is_correct_and_prints_each_number_beside_its_limit(
        reference):
    feed = make_feed(reference)
    deliver(feed, reference, engine_results(feed, reference))
    v, said = judge(feed, reference)
    assert v.correct and v.failed == 0 and v.attempted > 0
    assert "wrong=0 (limit 0)" in said[0] and "missing=0 (limit 0)" in said[0]
    assert "checkpoints_in_window=5 (at least 1)" in said[1]
    booked = [s for s in said if s.startswith("conservation:")]
    assert len(booked) == 2 and all(
        "off by 0 (limit 0)" in s for s in booked)


@pytest.mark.parametrize("reference,fault", [
    (q5, "drop"), (q5, "dup"), (q7, "drop")])
def test_one_lost_or_repeated_bid_that_an_answer_rests_on_is_not_correct(
        reference, fault):
    feed = make_feed(reference)
    i = a_bid_an_answer_rests_on(feed, reference)
    deliver(feed, reference, engine_results(feed, reference, **{fault: i}))
    v, said = judge(feed, reference, **{fault: i})
    assert not v.correct
    assert "wrong=0 " not in said[0]


@pytest.mark.parametrize("reference,fault", [
    (q5, "drop"), (q5, "dup"), (q7, "drop"), (q7, "dup")])
def test_one_lost_or_repeated_bid_that_no_answer_rests_on_is_not_correct(
        reference, fault):
    feed = make_feed(reference)
    i = a_bid_no_answer_rests_on(feed, reference)
    deliver(feed, reference, engine_results(feed, reference, **{fault: i}))
    v, said = judge(feed, reference, **{fault: i})
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in said[0]
    assert not v.correct            # the answers agree; the counts do not
    off = [s for s in said if s.startswith("conservation:")
           and "off by 0 " not in s]
    assert off and all("(limit 0)" in s for s in off)


@pytest.mark.parametrize("reference", [q5, q7], ids=["q5", "q7"])
def test_a_step_that_lost_a_batch_of_rows_is_not_correct(reference):
    # the answers are the sound ones (the batch held no hottest auction
    # and no highest bid), only the task's own books show it
    feed = make_feed(reference)
    deliver(feed, reference, engine_results(feed, reference))
    run = types.SimpleNamespace(
        feed=feed, seconds=10.0, window_s=10.0, job_seconds=30.0,
        checkpoints=5, stated_interval_s=10.0,
        flow=engine_flow(feed, reference), **edges(10.0))
    rows_in, rows_out = run.flow["2-0"]
    run.flow["2-0"] = (rows_in - 100, rows_out)
    said = []
    assert not check.judge(run, reference, {}, said.append).correct
    assert any("off by (-100, 0) (limit 0)" in s for s in said)


def test_one_count_off_by_one_is_not_correct():
    feed = make_feed()
    results = engine_results(feed, q5)
    end = sorted(results)[3]
    auction, num = results[end][0]
    results[end][0] = (auction, num + 1)
    deliver(feed, q5, results)
    v, said = judge(feed, q5)
    assert not v.correct and "wrong=1 (limit 0)" in said[0]


def test_a_result_delivered_twice_is_not_correct():
    feed = make_feed()
    results = engine_results(feed, q5)
    deliver(feed, q5, results)
    end = sorted(results)[2]
    deliver(feed, q5, {end: results[end]})
    assert not judge(feed, q5)[0].correct


def test_a_missing_window_is_not_correct_and_counts_as_failed():
    feed = make_feed()
    results = engine_results(feed, q5)
    ends = feed.schedule.due_by(feed.n_delivered)
    del results[ends[-1]]            # a close that became due in the window
    deliver(feed, q5, results)
    v, said = judge(feed, q5)
    assert not v.correct and v.failed == 1
    assert "missing=1 (limit 0)" in said[0]


def test_the_flushs_partial_windows_are_left_out_on_both_sides():
    feed = make_feed()
    results = engine_results(feed, q5)
    last = max(results)
    results[last] = [(1, 1)]         # whatever the flush emitted early
    deliver(feed, q5, results)
    assert judge(feed, q5)[0].correct


def test_a_paced_close_later_than_the_limit_counts_as_failed():
    t = Traffic(mode="steady", nominal_rate=RATE, first_event=FIRST,
                warm_event_seconds=12)
    feed = Feed(t, SEED, seconds=10.0)
    feed.schedule = schedule.Grid(feed, 2 * NS)
    feed.n_window_start = feed.n_warm
    feed.n_delivered = feed.n_window_end = feed.n_warm + 10_000
    feed.t_window_start, feed.t_window_end = 1000.0, 1010.0
    results = engine_results(feed, q5)
    due = {e: feed.due_wall(feed.schedule.due_event(e)) for e in results}
    import time as time_mod

    real = time_mod.monotonic_ns
    try:
        for k, (end, rows) in enumerate(sorted(results.items())):
            late = 2.5 if k == 7 else 0.3     # one close 2,500 ms late
            time_mod.monotonic_ns = lambda t=due[end] + late: int(t * 1e9)
            deliver(feed, q5, {end: rows})
    finally:
        time_mod.monotonic_ns = real
    run = types.SimpleNamespace(
        feed=feed, seconds=10.0, window_s=10.0, job_seconds=30.0,
        checkpoints=3, stated_interval_s=10.0,
        flow=engine_flow(feed, q5), **edges(10.0))
    v = check.judge(run, q5, {"late_limit_ms": 2000}, lambda s: None)
    assert v.correct                  # late is not wrong
    assert v.attempted == 5
    delays = sorted(c["delay_ms"] for c in v.closes)
    assert delays[0] == pytest.approx(300.0, abs=1e-3)
    late = [c for c in v.closes if c["delay_ms"] > 2000]
    assert v.failed == len(late) <= 1
