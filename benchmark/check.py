"""The comparison that decides `correct`.

What the timed path produced (every batch the sink received, warm-up and
window alike) is held against the plain reference over the same stream,
regenerated from the seed: the complete result set of every window whose
end + watermark delay lies at or before the last delivered event time, rows
keyed by the window end, exact equality as multisets (a result delivered
twice is wrong), no tolerance. The windows that the end-of-stream flush
emits early are partial on the program's side and are left out on both.

Which events a reference reads and which results are due is the
reference's to say (`benchmark/reference/<name>.py`):

- `COLUMNS`, `compute(*stream, ends)` -> {key: sorted rows} and
  `flows(*stream, ends)` -> [(what, rows in, rows out)]: every reference.
- `READS`, a tuple of "person", "auction", "bid": the reference is handed
  ONE argument, {kind: {"ts": event times, field: values}} of those kinds
  (`gen.events`). One that declares nothing is handed the four bid columns
  (event time, auction, bidder, price), as the first three were.
- `SLIDE_NS`: its results end on that grid (`schedule.Grid`); or
  `ends(*stream)` -> every key its results have over the events given: its
  results end where the stream says (`schedule.Listed`: sessions). Off a
  grid one rule is stricter: a produced key at or before the last due one
  that the reference does not hold (a session split or merged wrongly) is
  wrong. On a grid the program can produce no such key but by the flush.
Beside the answers stands a conservation comparison that does not depend
on them: the rows that each stateful step of the query took in and gave
out, by the program's own per-task row counters, against the reference's
count of the delivered bids and of the groups of every closed window
(`reference.flows`). These queries answer with the hottest auctions or the
one highest bid, so a bid lost or repeated elsewhere changes no answer; it
changes these counts. And the job's barriers must have gone out over the
run at the configured cadence, with a checkpoint completed inside the
window (`cadence`), so a run that skipped or slowed its barriers is
not correct.

`attempted` counts the window closes that became due inside the timed
window (paced: by the schedule; catch-up: by the event time delivered);
`failed` those missing, wrong or, in a paced cell, later than the
configuration's limit.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
from typing import Callable, Dict, List

import numpy as np

import schedule
from gen import nexmark as gen

BLOCK = 1 << 20
BID_READS = ("bid",)     # what a reference that declares no READS reads


@dataclasses.dataclass
class Verdict:
    correct: bool
    attempted: int
    failed: int
    closes: List[dict]


def bid_stream(feed, n_lo: int, n_hi: int):
    """The bids among events [n_lo, n_hi): (event time, auction, bidder,
    price)."""
    bid = event_stream(feed, BID_READS, n_lo, n_hi)["bid"]
    return bid["ts"], bid["auction"], bid["bidder"], bid["price"]


def event_stream(feed, reads, n_lo: int, n_hi: int) -> Dict[str, dict]:
    """{kind: {"ts": event times, field: values}} of the events of the
    kinds `reads` among events [n_lo, n_hi), regenerated from the seed in
    blocks (`gen.events`)."""
    parts: Dict[str, Dict[str, list]] = {kind: {} for kind in reads}
    # an empty range still names every field, each with no values
    for lo in range(n_lo, n_hi, BLOCK) or (n_lo,):
        ns = np.arange(lo, min(lo + BLOCK, n_hi), dtype=np.int64)
        block = gen.events(ns, feed.event_time_ns(ns), feed.seed, reads)
        for kind, fields in block.items():
            for name, col in fields.items():
                parts[kind].setdefault(name, []).append(col)
    return {kind: {name: np.concatenate(cols) for name, cols in p.items()}
            for kind, p in parts.items()}


def reads_of(reference) -> tuple:
    return tuple(getattr(reference, "READS", BID_READS))


def reference_stream(feed, reference, n_lo: int, n_hi: int) -> tuple:
    """Events [n_lo, n_hi) as the reference's functions take them, to be
    splatted in front of `ends`."""
    if not hasattr(reference, "READS"):
        return bid_stream(feed, n_lo, n_hi)
    return (event_stream(feed, reads_of(reference), n_lo, n_hi),)


def schedule_of(reference, feed):
    """The object that says which of the reference's results are due, and
    when (`schedule.py`)."""
    if hasattr(reference, "SLIDE_NS"):
        return schedule.Grid(feed, reference.SLIDE_NS)
    return schedule.Listed(
        feed, reference.ends,
        lambda n_hi: reference_stream(feed, reference, feed.n_first, n_hi))


def produced(feed, reference) -> Dict[int, dict]:
    """{window end: {"rows": sorted rows, "arrival_ns": last arrival}} from
    what the sink received. A result row carries the window's last
    nanosecond as `_timestamp`."""
    import pyarrow as pa

    out: Dict[int, dict] = {}
    for t_ns, batch in feed.arrivals:
        names = batch.schema.names
        ts = np.asarray(
            batch.column(names.index("_timestamp")).cast(pa.int64()))
        ends = ts + 1
        cols = [np.asarray(batch.column(names.index(c)))
                for c in reference.COLUMNS]
        for end in np.unique(ends).tolist():
            m = ends == end
            w = out.setdefault(end, {"rows": [], "arrival_ns": t_ns})
            w["rows"].extend(zip(*(c[m].tolist() for c in cols)))
            w["arrival_ns"] = max(w["arrival_ns"], t_ns)
    for w in out.values():
        w["rows"].sort()
    return out


def judge(run, reference, config: dict, say: Callable[[str], None]) -> Verdict:
    feed = run.feed
    late_limit_ms = config.get("late_limit_ms")
    paced = feed.traffic.mode == "steady"
    due = feed.schedule
    stream = reference_stream(feed, reference, feed.n_first, feed.n_delivered)
    ends = due.due_by(feed.n_delivered, stream)
    want = reference.compute(*stream, ends)
    got = produced(feed, reference)

    wrong = [e for e in ends if e in got and got[e]["rows"] != want[e]]
    if due.strict and ends:
        # a key the reference does not hold, where every key is due
        wrong += sorted(e for e in got if e <= ends[-1] and e not in want)
    missing = [e for e in ends if e not in got]
    rows = sum(len(want[e]) for e in ends)
    say(f"compared: windows={len(ends)} rows={rows} "
        f"wrong={len(wrong)} (limit 0) missing={len(missing)} (limit 0)")
    for e in (wrong + missing)[:3]:
        say(f"  window end {e}: got "
            f"{got.get(e, {}).get('rows', 'nothing')!s:.200} "
            f"want {want.get(e, 'no such key')!s:.200}")
    cadence_ok = cadence(run, say)
    unbooked = conservation(run.flow, reference.flows(*stream, ends), say)

    # the closes that became due inside the timed window
    n_lo = feed.n_window_start
    n_hi = (n_lo + int(feed.rate * run.seconds) if paced
            else feed.n_window_end)
    closes = []
    bad = set(wrong) | set(missing)
    for end, n_due in due.due_between(n_lo, n_hi):
        # a close due by the schedule whose events were never delivered
        # (the run fell behind) is not in `want`, and failed
        c = {"end": end, "due_event": n_due,
             "ok": end in got and end not in bad and end in want}
        if paced:
            c["due_wall"] = feed.due_wall(n_due)
            if end in got:
                c["delay_ms"] = (
                    got[end]["arrival_ns"] / 1e9 - c["due_wall"]) * 1e3
                if late_limit_ms is not None and (
                        c["delay_ms"] > late_limit_ms):
                    c["ok"] = False
        closes.append(c)
    failed = sum(1 for c in closes if not c["ok"])
    if paced:
        delays = [c["delay_ms"] for c in closes if "delay_ms" in c]
        n2 = max(int(2 / feed.traffic.chunk_seconds), 1)   # batches in 2 s
        late = feed.late_s or [float("nan")]
        say(f"closes due in the window: {len(closes)} failed={failed} "
            f"(late limit {late_limit_ms} ms) worst delay_ms="
            f"{max(delays, default=float('nan')):.1f}; generator late_ms "
            f"median of the first 2 s {1e3 * statistics.median(late[:n2]):.1f}"
            f" of the last 2 s {1e3 * statistics.median(late[-n2:]):.1f} "
            f"batches={len(feed.late_s)}")
    else:
        say(f"closes due in the window: {len(closes)} failed={failed}")
    if feed.gate_timed_out:
        say("the warm-up's last close never reached the sink")
    correct = (not wrong and not missing and len(ends) > 0
               and not unbooked
               and cadence_ok and not feed.gate_timed_out)
    return Verdict(correct, len(closes), failed, closes)


def cadence(run, say) -> bool:
    """Barriers at the configuration's stated cadence.

    How many checkpoints complete, or barriers go out, inside one window
    swings with the engine's stalls: a close of q7 holds the one event loop
    for ~20 of the 45 s, no barrier is initiated or passes meanwhile, and
    those behind it then go out and publish together. So the cadence is
    read where a stall does not reach it: from the times at which the
    program initiated the barriers that the source passed on, from the
    job's start to the window's end, the smallest distance between one and
    the next. Where the engine has room it is the stated interval (a stall
    delays one barrier and so shortens the distance to the next); at a
    quarter of the cadence every distance is four intervals. It may be at
    most two intervals; a span of four intervals or more has to hold two
    barriers at the least; and one checkpoint at the least has to have
    completed (`latest.json`) between the window's start and its end."""
    interval = run.stated_interval_s
    lo, hi = run.t_job0_ns, run.end["t_ns"]
    at = sorted(t for _epoch, t in run.feed.barriers if lo <= t <= hi)
    gaps = [(b - a) / 1e9 for a, b in zip(at, at[1:])]
    timed = (hi - lo) / 1e9 >= 4 * interval     # long enough to hold a gap
    w0 = run.start["t_ns"]
    say(f"cadence: checkpoints_in_window={run.checkpoints} (at least 1) "
        f"barriers={len(at)} (at least {2 if timed else 0}) "
        f"barrier_gap_min_s="
        f"{format(min(gaps), '.3f') if gaps else 'none'} "
        f"(at most {2 * interval:g}: twice the stated {interval:g} s); "
        f"initiated, seconds from the window's start: "
        f"{[round((t - w0) / 1e9, 1) for t in at]}")
    if run.checkpoints < 1:
        return False
    if timed and len(at) < 2:
        return False
    return not gaps or min(gaps) <= 2 * interval


def conservation(flow: Dict[str, tuple], wanted, say) -> int:
    """How many of the reference's (rows in, rows out) no task of the job
    booked exactly. `flow` is {task: (rows received, rows sent)}; each task
    answers for one step at most."""
    have = collections.Counter(flow.values())
    unbooked = 0
    for what, rows_in, rows_out in wanted:
        if have[(rows_in, rows_out)] > 0:
            have[(rows_in, rows_out)] -= 1
            off = 0
        else:
            unbooked += 1
            # the nearest task, to say by how much it is off
            near = min(flow.values(), default=(0, 0), key=lambda rs: (
                abs(rs[0] - rows_in) + abs(rs[1] - rows_out)))
            off = (near[0] - rows_in, near[1] - rows_out)
        say(f"conservation: {what}: reference rows in={rows_in} "
            f"out={rows_out}; nearest task off by {off} (limit 0)")
    if unbooked:
        say(f"  the tasks' (received, sent): {sorted(flow.values())}")
    return unbooked


def pick_fault(kind: str, traffic, seed: int, reads=BID_READS):
    """The event a control run loses or repeats at the source: drawn from
    the seed among the warm-up's events (every run delivers those) of the
    kinds the reference reads, bids unless it declares more, whatever the
    answers rest on."""
    from feed import Fault, Feed

    feed = Feed(traffic, seed, 0.0)
    ns = np.arange(feed.n_first, feed.n_warm, dtype=np.int64)
    masks = gen.kinds(ns)
    read = np.logical_or.reduce([masks[k] for k in reads])
    n = np.random.default_rng(seed).choice(ns[read])
    return Fault(kind, int(n))
