"""NEXmark query 11, "user sessions", as a plain batch computation.

For each bidder: the runs of its bids in which each bid comes less than
10 s after the one before (a session window with a gap of 10 s), with the
number of bids in the run and the time of its first. A session ends at its
last bid + the gap, and that end is its key: on no grid, so this reference
has no `SLIDE_NS` and says itself where its results end (`ends`). No
window operator, no session bookkeeping, nothing of the program: numpy
alone sorts the bids by (bidder, time) and cuts where the bidder changes or
the gap is reached.

A session whose end + the watermark delay lies at or before the last
delivered event time is final: the stream is in order, so every later bid
comes past its end and opens a new session. The comparison asks for those
alone (`ends` handed to `compute` and `flows`); the sessions still open at
the end of the stream are the program's flush to emit, and left out.

`flows` is the conservation side of the comparison: the one stateful step
of the query takes every bid and gives one row per closed session.
"""

from __future__ import annotations

import numpy as np

GAP_NS = 10_000_000_000
COLUMNS = ("bidder", "bid_count", "session_start")


def _sessions(bid_ts, bidder):
    """(end, bidder, bids, start) of every session of the stream, one
    entry each, in no order."""
    if len(bid_ts) == 0:
        e = np.empty(0, dtype=np.int64)
        return e, e, e, e
    order = np.lexsort((bid_ts, bidder))
    b, t = bidder[order], bid_ts[order]
    first = np.ones(len(b), dtype=bool)
    first[1:] = (b[1:] != b[:-1]) | (t[1:] - t[:-1] >= GAP_NS)
    starts = np.nonzero(first)[0]
    lasts = np.r_[starts[1:], len(b)] - 1
    return t[lasts] + GAP_NS, b[starts], lasts - starts + 1, t[starts]


def ends(bid_ts, auction, bidder, price):
    """The key of every session the stream holds: its last bid + the gap."""
    return _sessions(bid_ts, bidder)[0]


def compute(bid_ts, auction, bidder, price, ends):
    """{session end: sorted rows (bidder, bid_count, session_start)} for
    each end in `ends`. Two bidders' sessions may end in the same
    nanosecond only if their last bids share an event time."""
    end, who, bids, start = _sessions(bid_ts, bidder)
    out = {int(e): [] for e in ends}
    keep = np.isin(end, np.asarray(list(out), dtype=np.int64))
    for e, row in zip(end[keep].tolist(), zip(
            who[keep].tolist(), bids[keep].tolist(), start[keep].tolist())):
        out[e].append(row)
    for rows in out.values():
        rows.sort()
    return out


def flows(bid_ts, auction, bidder, price, ends):
    """[(what, rows in, rows out)] of the query's one stateful step over
    the whole run: the count per bidder and session takes every bid and
    gives one row per closed session."""
    end = _sessions(bid_ts, bidder)[0]
    closed = int(np.isin(end, np.asarray(list(ends), dtype=np.int64)).sum())
    return [("count per bidder and session", len(bid_ts), closed)]
