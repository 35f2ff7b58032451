"""The open sessions of one session-window subtask, as arrays.

A session is a ROW: its first and last event time, its accumulator slot,
its key (one array per key column, `KeyCodec`'s stored form) and the flags
the barrier reads. A key finds its session through a 64-bit CODE: the key
itself where it is one integer-like column (exact), a hash of its columns
otherwise (every hit is then held to the stored key, column by column).
Codes with ONE open session, the common case, sit in a sorted index and a
batch's segments are looked up, extended and opened together, with no
Python per segment. A code with several open sessions (a key whose rows
came out of order far enough to open a second one, a row that bridges two,
two keys under one hash) lives in a dict of row lists and its segments take
`_place_one`, one at a time: the merge loop of the old per-key lists.

What the operator hands in: `alloc(n)` -> n accumulator slots, and
`fold(dst_slot, src_slot)`, which folds one session's accumulator into
another's and frees the source slot. Everything else here is numpy.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

_I8 = np.int64


class SessionTable:
    def __init__(self, gap: int, int_like: List[bool]):
        self.gap = int(gap)
        # the code IS the key: nothing to verify on a hit
        self.exact = not int_like or (len(int_like) == 1 and int_like[0])
        cap = 1024
        self.start = np.zeros(cap, _I8)
        self.last = np.zeros(cap, _I8)
        self.slot = np.zeros(cap, _I8)
        self.code = np.zeros(cap, _I8)
        self.keys = [np.zeros(cap, _I8 if i else object) for i in int_like]
        self.live = np.zeros(cap, bool)
        # the row's code holds several open sessions: filed in `_shared`
        self.shared = np.zeros(cap, bool)
        # changed since the last checkpoint / since the last serve stage
        self.dirty = np.zeros(cap, bool)
        self.restage = np.zeros(cap, bool)
        # the row's key has an entry in the `sess` table: what a close must
        # take back at the next barrier
        self.stored = np.zeros(cap, bool)
        self.top = 0                    # rows ever used: [0, top)
        self.n_live = 0
        self._free = np.zeros(cap, _I8)
        self._n_free = 0
        # codes with one open session, sorted, and the row of each
        self._codes = np.empty(0, _I8)
        self._rows = np.empty(0, _I8)
        self._shared: Dict[int, List[int]] = {}
        # keys (stored columns) of closed sessions whose key had an entry
        # in the `sess` table, since the last barrier
        self.dead_stored: List[List[np.ndarray]] = []
        # counts of the last `place`, for the ledger
        self.opened = self.merged = self.scalar = 0
        # rows folded into another by the `place` under way: {gone: into}
        self._moved: Dict[int, int] = {}

    # -- rows ---------------------------------------------------------------

    _PER_ROW = ("start", "last", "slot", "code", "live", "shared", "dirty",
                "restage", "stored", "_free")

    def _take_rows(self, n: int) -> np.ndarray:
        k = min(n, self._n_free)
        self._n_free -= k
        rows = self._free[self._n_free:self._n_free + k].copy()
        if k < n:
            fresh = np.arange(self.top, self.top + n - k, dtype=_I8)
            self.top += n - k
            if self.top > len(self.live):
                cap = len(self.live)
                while cap < self.top:
                    cap *= 2

                def grown(a):
                    b = np.zeros(cap, a.dtype)
                    b[:len(a)] = a
                    return b

                for name in self._PER_ROW:
                    setattr(self, name, grown(getattr(self, name)))
                self.keys = [grown(a) for a in self.keys]
            rows = np.concatenate([rows, fresh])
        return rows

    def _release(self, rows: np.ndarray) -> None:
        n = len(rows)
        for flags in (self.live, self.shared, self.dirty, self.restage,
                      self.stored):
            flags[rows] = False
        self._free[self._n_free:self._n_free + n] = rows
        self._n_free += n
        self.n_live -= n

    def _fill(self, rows, code, key_cols, lo, hi, slots) -> None:
        self.start[rows] = lo
        self.last[rows] = hi
        self.slot[rows] = slots
        self.code[rows] = code
        for mine, col in zip(self.keys, key_cols):
            mine[rows] = col
        self.live[rows] = self.dirty[rows] = self.restage[rows] = True
        self.n_live += len(rows)

    def live_rows(self) -> np.ndarray:
        return np.nonzero(self.live[:self.top])[0]

    # -- codes --------------------------------------------------------------

    def codes_of(self, key_cols: List[np.ndarray], n: int) -> np.ndarray:
        if not key_cols:
            return np.zeros(n, _I8)
        if self.exact:
            return key_cols[0]
        from ..types import hash_arrays, hash_column

        return hash_arrays([hash_column(c) for c in key_cols]).view(_I8)

    def _same_key(self, rows: np.ndarray, key_cols, at: np.ndarray):
        """Whether rows' stored keys equal key_cols[at], column by column."""
        same = np.ones(len(rows), bool)
        for mine, col in zip(self.keys, key_cols):
            same &= np.asarray(mine[rows] == col[at], dtype=bool)
        return same

    def _with_key(self, group: List[int], key) -> np.ndarray:
        """The rows of `group` (one code's open sessions) that hold `key`
        (one value a column, as 1-element arrays): all of them where the
        code is the key."""
        g = np.asarray(group, _I8)
        if self.exact:
            return g
        return g[self._same_key(g, key, np.zeros(len(g), _I8))]

    def _index_add(self, codes: np.ndarray, rows: np.ndarray) -> None:
        """`codes` sorted, none of them in the index."""
        at = np.searchsorted(self._codes, codes)
        self._codes = np.insert(self._codes, at, codes)
        self._rows = np.insert(self._rows, at, rows)

    def _index_drop(self, codes: np.ndarray) -> None:
        at = np.searchsorted(self._codes, codes)
        self._codes = np.delete(self._codes, at)
        self._rows = np.delete(self._rows, at)

    def _file(self, code: int, group: List[int]) -> None:
        """Put a code's open sessions back where lookups find them."""
        if len(group) >= 2:
            self._shared[code] = group
            self.shared[group] = True
            return
        self._shared.pop(code, None)
        if group:
            self.shared[group[0]] = False
            self._index_add(np.asarray([code], _I8),
                            np.asarray(group, _I8))

    def _unfile(self, code: int) -> List[int]:
        group = self._shared.get(code)
        if group is not None:
            return group
        at = int(np.searchsorted(self._codes, code))
        if at < len(self._codes) and self._codes[at] == code:
            group = [int(self._rows[at])]
            self._index_drop(np.asarray([code], _I8))
            return group
        return []

    # -- a batch's segments -------------------------------------------------

    def place(self, code, key_cols, lo, hi, alloc: Callable,
              fold: Callable) -> np.ndarray:
        """The row of every segment (its code, its key, its first and last
        event time; sorted by code, then time). A segment that touches its
        key's one open session extends it, one whose key has none opens
        one; the rest (see the module's docstring) go one by one."""
        n = len(code)
        gap = self.gap
        self.opened = self.merged = self.scalar = 0
        at = np.minimum(np.searchsorted(self._codes, code),
                        max(len(self._codes) - 1, 0))
        hit = (self._codes[at] == code) if len(self._codes) else (
            np.zeros(n, bool))
        rows = np.where(hit, self._rows[at] if len(self._rows) else 0, -1)
        slow = np.zeros(n, bool)
        if n > 1:
            twice = code[1:] == code[:-1]
            slow[1:] |= twice
            slow[:-1] |= twice
        if self._shared:
            slow |= np.isin(code, np.fromiter(self._shared, _I8,
                                              len(self._shared)))
        h = np.nonzero(hit)[0]
        if not self.exact and len(h):
            slow[h[~self._same_key(rows[h], key_cols, h)]] = True
        touch = hit & (self.start[rows] - gap < hi) & (
            lo < self.last[rows] + gap)
        slow |= hit & ~touch
        ext = hit & ~slow
        new = ~hit & ~slow
        r = rows[ext]
        self.start[r] = np.minimum(self.start[r], lo[ext])
        self.last[r] = np.maximum(self.last[r], hi[ext])
        self.dirty[r] = self.restage[r] = True
        k = int(new.sum())
        if k:
            r = self._take_rows(k)
            self._fill(r, code[new], [c[new] for c in key_cols], lo[new],
                       hi[new], alloc(k))
            self._index_add(code[new], r)
            rows[new] = r
            self.opened = k
        todo = np.nonzero(slow)[0].tolist()
        self.scalar = len(todo)
        for g in todo:
            rows[g] = self._place_one(
                int(code[g]), [c[g:g + 1] for c in key_cols],
                int(lo[g]), int(hi[g]), alloc, fold)
        self._settle(rows, todo)
        return rows

    def _settle(self, rows: np.ndarray, todo: List[int]) -> None:
        """After the one-by-one segments: a row that a fold took away is
        freed only now (so that no later open of the same batch took it
        over), and a segment placed on it follows it to the survivor."""
        moved = self._moved
        if not moved:
            return
        for g in todo:
            while int(rows[g]) in moved:
                rows[g] = moved[int(rows[g])]
        self._release(np.fromiter(moved, _I8, len(moved)))
        self._moved = {}

    def _place_one(self, code: int, key, lo: int, hi: int, alloc,
                   fold) -> int:
        """Find, extend and merge among one key's open sessions, or open
        another: interval union with the gap, which is order-independent,
        so segment by segment gives the sessions row by row would."""
        gap = self.gap
        group = self._unfile(code)
        mine = sorted(self._with_key(group, key).tolist(),
                      key=lambda r: self.start[r])
        hit = next((r for r in mine if self.start[r] - gap < hi
                    and lo < self.last[r] + gap), None)
        if hit is None:
            hit = int(self._take_rows(1)[0])
            self._fill(np.asarray([hit]), code, key, lo, hi, alloc(1))
            group.append(hit)
            self.opened += 1
        else:
            self.start[hit] = min(self.start[hit], lo)
            self.last[hit] = max(self.last[hit], hi)
            self.dirty[hit] = self.restage[hit] = True
            # the extension may bridge its neighbours: fold while they
            # touch. The survivor is the earlier one; when the hit is the
            # one folded away, the survivor becomes the hit
            i = 0
            while i < len(mine) - 1:
                a, b = mine[i], mine[i + 1]
                if self.start[b] < self.last[a] + gap:
                    fold(int(self.slot[a]), int(self.slot[b]))
                    self.start[a] = min(self.start[a], self.start[b])
                    self.last[a] = max(self.last[a], self.last[b])
                    self.stored[a] |= self.stored[b]
                    self.dirty[a] = self.restage[a] = True
                    group.remove(b)
                    mine.pop(i + 1)
                    self._moved[b] = a
                    if b == hit:
                        hit = a
                    self.merged += 1
                else:
                    i += 1
        self._file(code, group)
        return hit

    # -- closing ------------------------------------------------------------

    def expired(self, t: int) -> np.ndarray:
        """Rows of the sessions whose end a watermark at `t` has passed."""
        n = self.top
        return np.nonzero(self.live[:n] & (self.last[:n] + self.gap <= t))[0]

    def close(self, rows: np.ndarray) -> None:
        """Take closed sessions out of the index and free their rows. A key
        whose last session closes is remembered where a barrier has to
        take its entry back (`dead_stored`); one that keeps another session
        open has its entry written and its partial staged anew."""
        sh = self.shared[rows]
        alone = rows[~sh]
        if len(alone):
            self._index_drop(self.code[alone])
        gone = alone[self.stored[alone]]
        if sh.any():
            extra = []
            for r in rows[sh].tolist():
                code = int(self.code[r])
                # (its neighbour's close may have left it alone by now)
                group = self._unfile(code)
                group.remove(r)
                rest = self._with_key(group, [k[r:r + 1] for k in self.keys])
                if len(rest):
                    self.stored[rest] |= self.stored[r]
                    self.dirty[rest] = self.restage[rest] = True
                elif self.stored[r]:
                    extra.append(r)
                self._file(code, group)
            gone = np.concatenate([gone, np.asarray(extra, _I8)])
        if len(gone):
            self.dead_stored.append([k[gone] for k in self.keys])
        self._release(rows)

    # -- restore ------------------------------------------------------------

    def load(self, code, key_cols, lo, hi, slots, stored: bool) -> None:
        """Restored sessions, as they were cut (nothing is merged), then the
        index rebuilt over every open row."""
        rows = self._take_rows(len(code))
        self._fill(rows, code, key_cols, lo, hi, slots)
        self.stored[rows] = stored
        live = self.live_rows()
        order = np.argsort(self.code[live], kind="stable")
        live = live[order]
        codes = self.code[live]
        first = np.ones(len(codes), bool)
        first[1:] = codes[1:] != codes[:-1]
        alone = first & np.r_[first[1:], True]
        self._codes, self._rows = codes[alone], live[alone]
        self.shared[live] = ~alone
        self._shared = {}
        for r in live[~alone].tolist():
            self._shared.setdefault(int(self.code[r]), []).append(r)

    def shared_keys(self) -> List[List[int]]:
        """The rows outside the sorted index, a list per key, by start: the
        keys with several open sessions, and those that share a hash."""
        out = []
        for group in self._shared.values():
            todo = list(group)
            while todo:
                r = todo[0]
                mine = self._with_key(todo, [k[r:r + 1] for k in self.keys])
                out.append(sorted(mine.tolist(),
                                  key=lambda q: self.start[q]))
                held = set(mine.tolist())
                todo = [q for q in todo if q not in held]
        return out
