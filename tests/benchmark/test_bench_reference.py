"""The plain references on streams small enough to work out by hand. That
each equals the engine on a seeded stream of more than 20,000 events is
what the rehearsal runs show (test_bench_run_rehearsal.py: `correct`)."""

import numpy as np

from reference import q5, q7

S = 1_000_000_000


def arr(*v):
    return np.asarray(v, dtype=np.int64)


def test_q5_counts_per_hop_window_and_keeps_the_hottest():
    ts = arr(1, 2, 3, 4, 9, 11, 12).astype(np.int64) * S
    auction = arr(7, 7, 8, 9, 8, 8, 9)
    zeros = np.zeros_like(auction)
    got = q5.compute(ts, auction, zeros, zeros, [10 * S, 12 * S, 14 * S])
    # [0, 10): 7 x2, 8 x2, 9 x1 -> both at the max
    assert got[10 * S] == [(7, 2), (8, 2)]
    # [2, 12): 7 (2s), 8 x3 (3, 9, 11), 9 x1
    assert got[12 * S] == [(8, 3)]
    # [4, 14): 8 x2 (9, 11), 9 x2 (4, 12)
    assert got[14 * S] == [(8, 2), (9, 2)]


def test_q5_window_bounds_are_half_open():
    ts = arr(0, 10).astype(np.int64) * S
    auction = arr(1, 2)
    z = np.zeros_like(auction)
    assert q5.compute(ts, auction, z, z, [10 * S]) == {10 * S: [(1, 1)]}
    assert q5.compute(ts, auction, z, z, [30 * S]) == {30 * S: []}


def test_q7_keeps_the_distinct_bids_at_the_highest_price():
    ts = arr(1, 2, 3, 4, 11, 12).astype(np.int64) * S
    auction = arr(5, 6, 6, 7, 5, 5)
    bidder = arr(1, 2, 2, 3, 1, 1)
    price = arr(10, 90, 90, 90, 20, 30)
    got = q7.compute(ts, auction, bidder, price, [10 * S, 20 * S])
    # the same (auction, price, bidder) twice is one group
    assert got[10 * S] == [(6, 90, 2), (7, 90, 3)]
    assert got[20 * S] == [(5, 30, 1)]
