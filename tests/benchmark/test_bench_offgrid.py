"""Results that end on no grid (ISSUE 40): the reference says which keys
are due (`schedule.Listed`), and a produced key it does not hold is wrong.
The first consumer is kept as files, not as a cell: NEXmark q11, "user
sessions" (`reference/q11.py`, `configs/nexmark-q11.json`, entries in
`data/future_cells.json`). The rehearsals run the program's numpy tier (a
session operator takes it on one device) through `--benchmark-file`."""

import os

import numpy as np
import pytest

import check
import schedule
from bench_helpers import HERE, REPO, rehearse
from feed import NS, Feed, Traffic
from reference import q11
from test_bench_check import deliver
from test_bench_check import judge as judge_delivered

FUTURE = ("--benchmark-file", os.path.join(HERE, "data", "future_cells.json"))
CELL = "q11.catchup"
S = NS


def arr(*v):
    return np.asarray(v, dtype=np.int64)


def test_q11_cuts_a_bidders_bids_where_the_gap_is_reached():
    # bidder 7: 1, 5, 14.9 s (one session: each under 10 s after the last),
    # then 24.9 s exactly the gap later: a new one; bidder 8: one bid
    ts = (np.asarray([1, 5, 6, 14.9, 24.9]) * S).astype(np.int64)
    bidder = arr(7, 7, 8, 7, 7)
    z = np.zeros_like(bidder)
    keys = sorted(q11.ends(ts, z, bidder, z).tolist())
    assert keys == [16 * S, int(24.9 * S), int(34.9 * S)]
    got = q11.compute(ts, z, bidder, z, keys[:2])
    assert got == {16 * S: [(8, 1, 6 * S)],
                   int(24.9 * S): [(7, 3, 1 * S)]}
    assert q11.flows(ts, z, bidder, z, keys[:2]) == [
        ("count per bidder and session", 5, 2)]
    assert q11.compute(ts[:0], z[:0], z[:0], z[:0], []) == {}
    with open(os.path.join(REPO, "benchmark", "reference", "q11.py")) as f:
        text = f.read()
    assert "import arroyo_tpu" not in text and "from arroyo_tpu" not in text
    assert not hasattr(q11, "SLIDE_NS") and not hasattr(q11, "READS")


def make_feed(n_events=60_000, rate=1000.0):
    t = Traffic(mode="catchup", nominal_rate=rate, warm_event_seconds=12,
                batch_rows=100)
    f = Feed(t, 11, seconds=10.0)
    f.schedule = check.schedule_of(q11, f)
    f.n_window_start = f.n_warm
    f.n_delivered = f.n_window_end = n_events
    f.t_window_start, f.t_window_end = 0.0, 10.0
    return f


def test_the_listed_schedule_reads_its_keys_from_the_stream():
    feed = make_feed()
    due = feed.schedule
    assert isinstance(due, schedule.Listed) and due.strict
    stream = check.reference_stream(feed, q11, 0, feed.n_delivered)
    every = np.sort(q11.ends(*stream))
    t_last = int(feed.event_time_ns(feed.n_delivered - 1))
    keys = due.due_by(feed.n_delivered, stream)
    assert keys == every[every + NS <= t_last].tolist() and len(keys) > 100
    # a key that is due is final: a shorter stream names the same ones
    n_mid = 40_000
    t_mid = int(feed.event_time_ns(n_mid - 1))
    assert due.due_by(n_mid) == [k for k in keys if k + NS <= t_mid]
    assert due.last_due(n_mid) == due.due_by(n_mid)[-1]
    assert due.last_due(feed.n_first) is None
    # the warm-up's 12 s at a gap of 10 s close only what ended in its
    # first second
    warm = due.due_by(feed.n_warm)
    assert due.last_due(feed.n_warm) == (warm[-1] if warm else None)
    # each key's due event is the first at or past key + delay: the feed's
    # own arithmetic (`first_event_at`), here over every key at once
    pairs = due.due_between(n_mid, feed.n_delivered)
    assert [k for k, _n in pairs] == [
        k for k in keys if k + NS > t_mid] and len(pairs) > 50
    for key, n_due in pairs:
        assert feed.event_time_ns(n_due - 1) < key + NS <= (
            feed.event_time_ns(n_due))
        assert n_mid <= n_due < feed.n_delivered
    for key, n_due in pairs[:50] + pairs[-50:]:     # and one at a time
        assert n_due == feed.first_event_at(key + NS) == due.due_event(key)
        assert isinstance(due.due_event(key), int)
    assert due.due_between(500, 500) == []


def test_a_grid_reference_gets_the_grid_and_the_four_bid_columns():
    from reference import q5

    feed = make_feed()
    grid = check.schedule_of(q5, feed)
    assert isinstance(grid, schedule.Grid) and not grid.strict
    assert grid.slide_ns == q5.SLIDE_NS
    stream = check.reference_stream(feed, q5, 0, 5_000)
    assert len(stream) == 4 and all(
        isinstance(c, np.ndarray) and c.dtype == np.int64 for c in stream)
    assert check.reads_of(q5) == ("bid",) == check.reads_of(q11)


def judge(feed, results):
    """The sink gets `results`; the tasks book what an exact engine's do."""
    deliver(feed, q11, results)
    return judge_delivered(feed, q11)


def engine_results(feed):
    """What an exact engine sends: every session due, and at the end of
    the stream the open ones, whose keys lie past the last due one."""
    stream = check.reference_stream(feed, q11, 0, feed.n_delivered)
    return q11.compute(*stream, q11.ends(*stream).tolist())


def test_a_sound_run_of_sessions_is_correct():
    feed = make_feed()
    v, said = judge(feed, engine_results(feed))
    assert v.correct and v.failed == 0 and v.attempted > 100
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in said[0]
    assert sum("off by 0 (limit 0)" in s for s in said) == 1


def test_a_session_split_in_two_is_a_key_the_reference_does_not_hold():
    """The doctored arrival list: one session of three bids and more comes
    as two, the first ending where the reference holds no key. Both are
    wrong: the true key's row is short, and the other key is not the
    reference's. On a grid that second key would be left out."""
    feed = make_feed()
    results = engine_results(feed)
    due = feed.schedule.due_by(feed.n_delivered)
    end = next(e for e in due[len(due) // 2:] if results[e][0][1] >= 3)
    (bidder, bids, start), = results[end]
    early = end - q11.GAP_NS - 12_345            # its first bid alone
    assert early not in results and due[0] < early < due[-1]
    results[early] = [(bidder, 1, start)]
    results[end] = [(bidder, bids - 1, start + 1)]
    v, said = judge(feed, results)
    assert not v.correct
    assert "wrong=2 (limit 0) missing=0 (limit 0)" in said[0]
    assert any("want no such key" in s for s in said)
    # past the last due key the flush's sessions are left out, as on a grid
    feed = make_feed()
    results = engine_results(feed)
    results[max(results) + 1] = [(1, 1, 1)]
    assert judge(feed, results)[0].correct


@pytest.fixture(scope="module")
def sound():
    line, said = rehearse(CELL, *FUTURE, seed=2**31 + 40, seconds=12)
    line["said"] = said
    return line


def test_the_kept_q11_files_rehearse_correct_over_thousands_of_sessions(
        sound):
    assert sound["correct"] is True and sound["rehearsal"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 10
    assert set(sound["metrics"]) == {"setup_s", "events_per_s"}
    compared = next(s for s in sound["said"] if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    booked = [s for s in sound["said"] if "conservation:" in s]
    assert len(booked) == 1 and "off by 0 (limit 0)" in booked[0]


@pytest.mark.parametrize("control", ["drop", "dup", "cadence"])
def test_each_control_of_q11_comes_out_not_correct(control):
    line, said = rehearse(CELL, *FUTURE, "--control", control, seed=40,
                          seconds=12)
    assert line["correct"] is False
    booked = [s for s in said if "conservation:" in s]
    compared = next(s for s in said if "compared:" in s)
    if control == "cadence":
        assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
        assert all("off by 0 " in s for s in booked)
        assert "(at most 4:" in next(s for s in said if "cadence:" in s)
    else:
        # a session's count rests on every one of its bids
        assert booked and "off by 0 " not in booked[0]
        assert "wrong=0 " not in compared


def test_the_kept_files_are_the_issues():
    import json

    with open(FUTURE[1]) as f:
        kept = json.load(f)
    cell = next(w for w in kept["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark-q11", "catchup-100k", 1)
    entry = next(c for c in kept["configs"] if c["name"] == "nexmark-q11")
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["reference"] == "q11" and cfg["source"] == entry["source"]
    assert list(cfg["reduced"]) == entry["reduced"] == ["nominal_rate"]
    assert "from memory" in cfg["assumed"]["query"]
    with open(os.path.join(REPO, "benchmark", "configs", "nexmark-q5.json"
                           )) as f:
        q5 = json.load(f)
    for key in ("state", "checkpoint_interval_s", "delivery"):
        assert cfg["guarantees"][key] == q5["guarantees"][key]
    with open(os.path.join(REPO, "benchmark", "configs", cfg["sql"])) as f:
        assert "session(interval '10 seconds')" in f.read()
