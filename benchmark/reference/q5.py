"""NEXmark query 5, "hot items", as a plain batch computation.

For each hop window of 10 s sliding by 2 s: count the bids per auction,
take the largest count, and keep the auctions that reach it. No window
operator, no directory, no device, nothing of the program: the bids of a
window are a slice of the in-order stream, `np.unique` counts them.

`flows` is the conservation side of the comparison: how many rows each
stateful step of the query takes in and gives out over the closed windows,
whatever the answers are. Only the hottest auctions reach the answer, so
the answers alone would not show a bid lost on a cold auction.
"""

from __future__ import annotations

import numpy as np

SIZE_NS = 10_000_000_000
SLIDE_NS = 2_000_000_000
COLUMNS = ("auction", "num")


def compute(bid_ts, auction, bidder, price, ends):
    """{window end: sorted rows (auction, num)} for each end in `ends`.
    `bid_ts` is ascending (the stream is in order)."""
    out = {}
    for end in ends:
        lo = np.searchsorted(bid_ts, end - SIZE_NS, side="left")
        hi = np.searchsorted(bid_ts, end, side="left")
        keys, counts = np.unique(auction[lo:hi], return_counts=True)
        if len(keys) == 0:
            out[int(end)] = []
            continue
        hot = counts >= counts.max()
        out[int(end)] = sorted(
            zip(keys[hot].tolist(), counts[hot].tolist()))
    return out


def flows(bid_ts, auction, bidder, price, ends):
    """[(what, rows in, rows out)] of the query's stateful steps over the
    whole run: the count per auction and window takes every bid and gives
    one row per auction and closed window; the max per window takes those
    and gives one row per closed window."""
    groups = 0
    for end in ends:
        lo = np.searchsorted(bid_ts, end - SIZE_NS, side="left")
        hi = np.searchsorted(bid_ts, end, side="left")
        groups += len(np.unique(auction[lo:hi]))
    return [("count per auction and window", len(bid_ts), groups),
            ("max count per window", groups, len(ends))]
