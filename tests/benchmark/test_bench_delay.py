"""The schedule and the delay arithmetic on a hand-made schedule."""

import math

import pytest

import delay
import schedule
from feed import NS, Feed, Traffic


def feed(mode="steady", rate=1000.0, first=20_000, seconds=20.0):
    t = Traffic(mode=mode, nominal_rate=rate, first_event=first,
                warm_event_seconds=12, batch_rows=100, chunk_seconds=0.02)
    f = Feed(t, seed=1, seconds=seconds)
    f.watermark_delay_ns = NS
    f.schedule = schedule.Grid(f, 2 * NS)
    return f


def test_event_time_is_the_schedule():
    f = feed()
    assert int(f.event_time_ns(0)) == 0
    assert int(f.event_time_ns(20_000)) == 20 * NS
    assert f.first_event_at(20 * NS) == 20_000
    assert f.first_event_at(20 * NS + 1) == 20_001


def test_the_warm_up_is_a_whole_number_of_window_sized_batches():
    f = feed()                       # 20 rows a batch, 12 s = 12,000 events
    assert (f.n_warm - f.n_first) % 20 == 0
    assert f.n_warm - f.n_first == 12_000
    g = feed(mode="catchup")         # 100 rows a batch
    assert g.n_warm - g.n_first == 12_000
    lo, hi = next(g._ranges())
    assert (lo, hi) == (20_000, 20_100)


def test_a_close_is_due_when_end_plus_watermark_delay_has_happened():
    f = feed()
    # window end 34 s + 1 s delay: the event at 35 s is number 35,000
    due = f.schedule
    assert due.due_event(34 * NS) == 35_000
    # events 32,000..36,999 make the closes at 32 (due 33,000) and 34 due
    assert due.due_between(32_000, 37_000) == [
        (32 * NS, 33_000), (34 * NS, 35_000)]
    # a range's upper end is exclusive
    assert due.due_between(32_000, 35_000) == [(32 * NS, 33_000)]
    assert due.due_between(35_000, 35_001) == [(34 * NS, 35_000)]
    assert due.last_due(f.n_warm) == 30 * NS


def test_result_delay_is_arrival_minus_the_due_wall_time():
    f = feed()
    f.n_window_start = 32_000
    f.t_window_start = 100.0
    # event 35,000 is the 3,001st of the window: due 3.001 s after its start
    assert f.due_wall(35_000) == pytest.approx(103.001)

    class Run:
        closes = [{"end": 34 * NS, "delay_ms": 250.0},
                  {"end": 36 * NS},                      # never arrived
                  {"end": 38 * NS, "delay_ms": 750.0}]

    assert delay.delays_ms(Run) == [250.0, 750.0]


def test_percentile_is_nearest_rank():
    s = list(range(1, 23))           # 22 closes of a 45 s run
    assert delay.percentile(s, 0.50) == 11
    assert delay.percentile(s, 0.90) == 20      # the third-highest
    assert delay.percentile([5.0], 0.90) == 5.0
    assert delay.percentile([], 0.5) is None
    assert delay.percentile(s, 1.0) == 22 and math.isfinite(
        delay.percentile(s, 0.0))


def test_unknown_traffic_keys_are_refused():
    with pytest.raises(ValueError):
        Traffic.from_dict({"mode": "steady", "nominal_rate": 1,
                           "first_event": 0, "warm_event_seconds": 1,
                           "burst": 3})
    with pytest.raises(ValueError):
        Traffic.from_dict({"mode": "bursty", "nominal_rate": 1,
                           "first_event": 0, "warm_event_seconds": 1})
