"""NEXmark query 7, "highest bid", as a plain batch computation.

For each tumbling window of 10 s: the largest price, and the distinct
(auction, price, bidder) of the bids that reach it. The bids of a window
are a slice of the in-order stream; nothing of the program is used.

`flows` is the conservation side of the comparison: how many rows each
stateful step of the query takes in and gives out over the closed windows.
The answer is one row per window, so the answers alone would show a lost
or repeated bid only if it were the highest.
"""

from __future__ import annotations

import numpy as np

SIZE_NS = 10_000_000_000
SLIDE_NS = 10_000_000_000
COLUMNS = ("auction", "price", "bidder")


def compute(bid_ts, auction, bidder, price, ends):
    """{window end: sorted rows (auction, price, bidder)} for each end."""
    out = {}
    for end in ends:
        lo = np.searchsorted(bid_ts, end - SIZE_NS, side="left")
        hi = np.searchsorted(bid_ts, end, side="left")
        if hi == lo:
            out[int(end)] = []
            continue
        p = price[lo:hi]
        top = p == p.max()
        out[int(end)] = sorted(set(zip(
            auction[lo:hi][top].tolist(), p[top].tolist(),
            bidder[lo:hi][top].tolist())))
    return out


def flows(bid_ts, auction, bidder, price, ends):
    """[(what, rows in, rows out)] of the query's stateful steps over the
    whole run: the count per (auction, price, bidder) and window takes
    every bid and gives one row per distinct triple and closed window; the
    max price per window takes every bid and gives one row per closed
    window."""
    groups = 0
    for end in ends:
        lo = np.searchsorted(bid_ts, end - SIZE_NS, side="left")
        hi = np.searchsorted(bid_ts, end, side="left")
        if hi == lo:
            continue
        a, p, b = auction[lo:hi], price[lo:hi], bidder[lo:hi]
        order = np.lexsort((b, p, a))
        a, p, b = a[order], p[order], b[order]
        new = (a[1:] != a[:-1]) | (p[1:] != p[:-1]) | (b[1:] != b[:-1])
        groups += 1 + int(new.sum())
    return [("count per (auction, price, bidder) and window",
             len(bid_ts), groups),
            ("max price per window", len(bid_ts), len(ends))]
