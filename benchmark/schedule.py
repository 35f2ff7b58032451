"""Which results are due, and when.

A result is keyed by its `_timestamp` + 1 (a window's or a session's end)
and is due once key + the configuration's watermark delay lies at or
before the event time of the last delivered event: a real watermark has
closed it by then, and no later event can change it. Which keys exist is
the reference's to say, and one of these objects answers for it wherever
the harness asks: the comparison (`check.judge`: the keys due by the last
delivered event, and those whose due event falls in the timed window) and
the warm-up's gate (`feed._gate`: the last key the warm-up makes due).

- `Grid`: a reference with `SLIDE_NS`. Tumbling and hopping windows end on
  the multiples of the slide, whatever the stream holds.
- `Listed`: a reference with `ends(*stream)`. Sessions end at a last event
  + the gap, on no grid: the keys are read from the stream itself.

Nothing here runs per batch: the gate asks once, the comparison after the
window.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np


class Schedule:
    """What both kinds share: a key is due with the first event at or past
    key + the watermark delay."""

    def __init__(self, feed):
        self.feed = feed

    def due_event(self, key):
        """The event whose delivery makes this key due: the first with
        event time >= key + watermark delay. An array of keys gives an
        array."""
        return self.feed.first_event_at(key + self.feed.watermark_delay_ns)


class Grid(Schedule):
    """Keys on the multiples of `slide_ns`. A produced key that is not due
    is a window the end-of-stream flush emitted early, partial on the
    program's side, and is left out on both (`strict` False): on a grid
    the program can produce no other key."""

    strict = False

    def __init__(self, feed, slide_ns: int):
        super().__init__(feed)
        self.slide_ns = int(slide_ns)

    def due_by(self, n_hi: int, stream=None) -> List[int]:
        """Every window end that a real watermark closed by event `n_hi` -
        1: end + delay <= its event time."""
        feed = self.feed
        t_first = int(feed.event_time_ns(feed.n_first))
        t_last = int(feed.event_time_ns(n_hi - 1))
        slide = self.slide_ns
        first = t_first // slide * slide + slide
        last = (t_last - feed.watermark_delay_ns) // slide * slide
        return list(range(first, last + 1, slide))

    def last_due(self, n_end: int) -> Optional[int]:
        """The last key made due by events [.., n_end)."""
        feed = self.feed
        if n_end <= feed.n_first:
            return None
        t_last = int(feed.event_time_ns(n_end - 1))
        end = ((t_last - feed.watermark_delay_ns)
               // self.slide_ns * self.slide_ns)
        t_first = int(feed.event_time_ns(feed.n_first))
        return end if end > t_first else None

    def due_between(self, n_lo: int, n_hi: int) -> List[Tuple[int, int]]:
        """(key, its due event) of the keys whose due event lies in
        [n_lo, n_hi)."""
        if n_hi <= n_lo:
            return []
        out = []
        t_lo = int(self.feed.event_time_ns(n_lo)) - (
            self.feed.watermark_delay_ns)
        end = t_lo // self.slide_ns * self.slide_ns
        while True:
            n_due = self.due_event(end)
            if n_due >= n_hi:
                return out
            if n_due >= n_lo:
                out.append((end, n_due))
            end += self.slide_ns


class Listed(Schedule):
    """Keys the reference reads from the stream: `keys_of(stream)` gives
    every key its results have over the events it is handed, and
    `stream_to(n_hi)` regenerates events [n_first, n_hi) as the reference
    takes them. A key that is due is final: every later event lies past
    key + delay, so a longer stream gives the same due keys and the
    largest one regenerated answers for every shorter one.

    Off a grid a program that splits or merges a result wrongly produces a
    key the reference does not hold, so every produced key at or before
    the last due one must be the reference's (`strict`). The warm-up's
    events are regenerated here, before the job starts, so that the gate
    knows its key without work on the engine's thread."""

    strict = True

    def __init__(self, feed, keys_of: Callable, stream_to: Callable):
        super().__init__(feed)
        self._keys_of = keys_of
        self._stream_to = stream_to
        self._n = feed.n_first          # the keys are those of [n_first, _n)
        self._all = np.empty(0, dtype=np.int64)
        self._keys(feed.n_warm)

    def _keys(self, n_hi: int, stream=None) -> np.ndarray:
        """The keys due by event `n_hi` - 1, ascending."""
        feed = self.feed
        if n_hi <= feed.n_first:
            return np.empty(0, dtype=np.int64)
        if n_hi > self._n:
            if stream is None:
                stream = self._stream_to(n_hi)
            self._all = np.unique(
                np.asarray(self._keys_of(*stream), dtype=np.int64))
            self._n = n_hi
        t_last = int(feed.event_time_ns(n_hi - 1))
        return self._all[self._all + feed.watermark_delay_ns <= t_last]

    def due_by(self, n_hi: int, stream=None) -> List[int]:
        """`stream`: events [n_first, n_hi) where the caller has them
        already, so that they are not regenerated."""
        return self._keys(n_hi, stream).tolist()

    def last_due(self, n_end: int) -> Optional[int]:
        keys = self._keys(n_end)
        return int(keys[-1]) if len(keys) else None

    def due_between(self, n_lo: int, n_hi: int) -> List[Tuple[int, int]]:
        if n_hi <= n_lo:
            return []
        # a key due at n_hi - 1 has key + delay <= that event's time
        keys = self._keys(n_hi)
        due = self.due_event(keys)
        m = (due >= n_lo) & (due < n_hi)
        return list(zip(keys[m].tolist(), due[m].tolist()))
