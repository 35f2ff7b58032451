"""Upstream Arroyo's first-pipeline query, "the top 5 auctions of the last
minute, refreshed every 2 seconds", as a plain batch computation.

For each hop window of 60 s sliding by 2 s: count the bids per auction,
order the auctions by (count descending, auction descending) and keep the
first five with `row_num` 1..5 (fewer where the window holds fewer
auctions). No window operator, no directory, no device, nothing of the
program: numpy alone.

A window end is a multiple of the slide, so a window is 30 whole slices of
2 s. The bids of a slice are a slice of the in-order stream and `np.unique`
counts them once; a window then adds its slices' counts per auction (one
`np.unique` over the slices' distinct auctions, ~363k of them at 100k
events/s, in place of one over the window's 5.5M bids). An end that is not
on the slide's grid is counted from its bids directly.

`flows` is the conservation side of the comparison. In the plan this query
gets, the hop count, the ranking (`ROW_NUMBER ... OVER`) and the filter
`row_num <= 5` are stateless-to-stateful neighbours on forward edges at
parallelism one, so the optimizer chains them into ONE task behind the
shuffle from the source: that task's row counters read every bid in and the
surviving rows (at most five a closed window) out, and no counter stands
between the hop operator and the ranking. So there is one flow, not two;
what the hop count hands the ranking inside that task is `groups`, which
the program's ledger answers for (`rank.sort`'s `n`), not a row counter.
"""

from __future__ import annotations

import numpy as np

SIZE_NS = 60_000_000_000
SLIDE_NS = 2_000_000_000
TOP = 5
COLUMNS = ("auction", "count", "row_num")


def _windows(bid_ts, auction, ends):
    """(end, auctions, counts) of each window [end - SIZE_NS, end)."""
    slices = {}

    def counted(lo_ns, hi_ns):
        lo = np.searchsorted(bid_ts, lo_ns, side="left")
        hi = np.searchsorted(bid_ts, hi_ns, side="left")
        return np.unique(auction[lo:hi], return_counts=True)

    for end in ends:
        end = int(end)
        if end % SLIDE_NS:
            yield (end, *counted(end - SIZE_NS, end))
            continue
        parts = []
        for hi_ns in range(end - SIZE_NS + SLIDE_NS, end + 1, SLIDE_NS):
            if hi_ns not in slices:
                slices[hi_ns] = counted(hi_ns - SLIDE_NS, hi_ns)
            parts.append(slices[hi_ns])
        keys, inverse = np.unique(
            np.concatenate([p[0] for p in parts]), return_inverse=True)
        counts = np.zeros(len(keys), dtype=np.int64)
        np.add.at(counts, inverse, np.concatenate([p[1] for p in parts]))
        yield end, keys, counts


def compute(bid_ts, auction, bidder, price, ends):
    """{window end: sorted rows (auction, count, row_num)} for each end in
    `ends`. `bid_ts` is ascending (the stream is in order)."""
    out = {}
    for end, keys, counts in _windows(bid_ts, auction, ends):
        order = np.lexsort((-keys, -counts))[:TOP]
        out[end] = sorted(zip(
            keys[order].tolist(), counts[order].tolist(),
            range(1, len(order) + 1)))
    return out


def groups(bid_ts, auction, ends):
    """How many (auction, window) groups the closed windows hold: what the
    hop count hands the ranking inside their one task."""
    return sum(len(keys) for _e, keys, _c in _windows(bid_ts, auction, ends))


def flows(bid_ts, auction, bidder, price, ends):
    """[(what, rows in, rows out)] of the query's one stateful task over
    the whole run: it takes every bid and gives the rows that survive
    `row_num <= 5`, five a closed window or the window's auctions if they
    are fewer (see the module's docstring for why there is one)."""
    kept = sum(min(TOP, len(keys))
               for _e, keys, _c in _windows(bid_ts, auction, ends))
    return [("count per auction and window, ranked, first five kept",
             len(bid_ts), kept)]
