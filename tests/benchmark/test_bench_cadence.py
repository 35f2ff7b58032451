"""The rest of what decides `correct`: checkpoints at the stated cadence,
read at the window's end, and the control's event drawn from the seed. (A
file of its own: tier-1 hands files to its workers largest first, and a
benchmark file among the first would reorder the seed's own.)"""

import pytest

import check
from feed import Traffic
from reference import q5
from test_bench_check import (RATE, deliver, engine_results, judge,
                              make_feed)


@pytest.mark.parametrize("window_s,checkpoints,barriers,correct", [
    (10.0, 0, (), False),                        # barriers skipped
    (45.5, 3, (3, 13, 23, 33, 43), True),        # the stated 10 s
    # a close held a barrier for 12 s, and the next one followed it closely
    (45.5, 1, (-2, 8, 30, 38), True),
    (45.5, 1, (5, 45), False),            # a 40 s cadence under a stated 10 s
    (45.5, 1, (20,), False),              # ... with one barrier in the span
    (45.5, 0, (3, 13, 23, 33, 43), False),       # none completed
    (19.9, 1, (), True)])                 # too short a span to hold a gap
def test_barriers_slower_than_the_stated_cadence_are_not_correct(
        window_s, checkpoints, barriers, correct):
    feed = make_feed()
    deliver(feed, q5, engine_results(feed, q5))
    v, said = judge(feed, q5, checkpoints=checkpoints, window_s=window_s,
                    barriers=barriers)
    assert v.correct is correct
    assert (f"cadence: checkpoints_in_window={checkpoints} (at least 1) "
            f"barriers={len(barriers)} "
            f"(at least {2 if window_s + 5 >= 40 else 0})") in said[1]
    assert "(at most 20: twice the stated 10 s)" in said[1]


def test_the_control_event_is_drawn_from_the_seed_among_the_warm_ups_bids():
    t = Traffic(mode="catchup", nominal_rate=RATE, warm_event_seconds=12,
                batch_rows=100)
    picks = {seed: check.pick_fault("drop", t, seed).event
             for seed in range(12)}
    assert len(set(picks.values())) > 8
    for seed, n in picks.items():
        assert 0 <= n < 12_000 and n % 50 >= 4
        assert check.pick_fault("dup", t, seed).event == n
