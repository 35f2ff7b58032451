"""Loader + wrapper for the native (C++) slot directory.

The native path handles keys that flatten to int64 words; everything
else uses the python SlotDirectory. The extension builds lazily on first
use (native/build.py, g++). A host that deliberately runs without it
sets ARROYO_DISABLE_NATIVE=1; a build or import that FAILS is an error —
the python directory is several times slower on the host's critical
path, and taking it silently would hide that.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from typing import List, Tuple

import numpy as np

_native = None

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "native",
)


def native_build_module():
    """native/build.py as a module (it is not a package member)."""
    spec = importlib.util.spec_from_file_location(
        "_arroyo_native_build", os.path.join(_NATIVE_DIR, "build.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_native():
    global _native
    if _native is not None or os.environ.get("ARROYO_DISABLE_NATIVE"):
        return _native
    # always run the (mtime-cached) build first: importing an existing
    # .so without the check would silently use a stale binary after
    # slotdir.cpp changes
    try:
        native_build_module().build()
    except (OSError, subprocess.CalledProcessError) as e:
        raise RuntimeError(
            "building native/slotdir.cpp failed "
            f"({getattr(e, 'stderr', None) or e}); install g++ or set "
            "ARROYO_DISABLE_NATIVE=1 to run on the (slower) python slot "
            "directory"
        ) from e
    importlib.invalidate_caches()
    sys.path.insert(0, _NATIVE_DIR)
    try:
        import arroyo_native
    finally:
        # the extension stays imported; nothing else should resolve
        # through native/ (it contains a generic build.py)
        sys.path.remove(_NATIVE_DIR)
    _native = arroyo_native
    return _native


def _i64_view(c: np.ndarray) -> np.ndarray:
    c = np.asarray(c)
    if c.dtype == np.uint64:
        return c.view(np.int64)
    if c.dtype.kind == "M":
        return c.view("i8")
    return c


class NativeSlotDirectory:
    """N-int64-key directory over the C++ open-addressing table,
    API-compatible with ops.directory.SlotDirectory for the paths the
    window operators use (assign/take_bin/bin_entries/items/peek_bin).
    Keys surface as n-tuples like the python impl; `take_bin_arrays`
    and the 2-D `bin_entries` matrix are the vectorized emission paths
    (no python tuple per key)."""

    key_encoding = "words"  # ops/directory.py KeyCodec

    def __init__(self, native_mod, n_keys: int = 1):
        # n_keys 0 = unkeyed: one synthetic zero key word, empty tuples out
        self.n_keys = n_keys
        self._stride = max(1, n_keys)
        self._d = native_mod.SlotDir(self._stride)
        self.free: list = []  # parity attribute; slot reuse lives natively

    @property
    def n_live(self) -> int:
        return self._d.n_live()

    def required_capacity(self) -> int:
        return self._d.required_capacity()

    def assign(self, bins: np.ndarray, key_cols: List[np.ndarray]) -> np.ndarray:
        n = len(bins)
        if not key_cols:
            flat = np.zeros(n, dtype=np.int64)
        elif self._stride == 1:
            flat = np.ascontiguousarray(_i64_view(key_cols[0]),
                                        dtype=np.int64)
        else:
            mat = np.empty((n, self._stride), dtype=np.int64)
            for j, c in enumerate(key_cols):
                mat[:, j] = _i64_view(c)
            flat = mat.reshape(-1)
        out = self._d.assign(
            np.ascontiguousarray(bins, dtype=np.int64), flat
        )
        return np.frombuffer(out, dtype=np.int64)

    def _rows_to_tuples(self, kmat: np.ndarray) -> list:
        """Key matrix -> list of python-int tuples in C-level passes
        (a per-row genexpr over numpy scalars is ~10x slower)."""
        if self.n_keys == 0:
            return [()] * len(kmat)
        if self._stride == 1:
            return [(k,) for k in kmat[:, 0].tolist()]
        return list(zip(*(kmat[:, j].tolist()
                          for j in range(self._stride))))

    def _keys_matrix(self, keys_raw: bytes) -> np.ndarray:
        return np.frombuffer(keys_raw, dtype=np.int64).reshape(
            -1, self._stride
        )

    def take_bin(self, b: int) -> Tuple[List[tuple], np.ndarray]:
        keys_raw, slots_raw = self._d.take_bin(int(b))
        keys = self._keys_matrix(keys_raw)
        slots = np.frombuffer(slots_raw, dtype=np.int64).copy()
        return self._rows_to_tuples(keys), slots

    def take_bin_arrays(
        self, b: int
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Vectorized take_bin: key columns as int64 arrays (the synthetic
        zero column when unkeyed — callers use it only for row count)."""
        keys_raw, slots_raw = self._d.take_bin(int(b))
        keys = self._keys_matrix(keys_raw)
        slots = np.frombuffer(slots_raw, dtype=np.int64).copy()
        return [keys[:, j] for j in range(self._stride)], slots

    def bin_entries(self, b: int) -> Tuple[np.ndarray, np.ndarray]:
        """(keys int64 matrix (count, stride), slots int64) of a live bin,
        without removal."""
        keys_raw, slots_raw = self._d.get_bin(int(b))
        return (
            self._keys_matrix(keys_raw),
            np.frombuffer(slots_raw, dtype=np.int64),
        )

    def bin_entries_multi(self, bins) -> Tuple[np.ndarray, np.ndarray]:
        """Concatenated (keys matrix, slots) over SEVERAL live bins in one
        C call (the sliding merge reads width/slide bins per emission and
        only ever concatenates them; per-bin identity is not needed)."""
        keys_raw, slots_raw = self._d.get_bins(
            np.ascontiguousarray(np.asarray(bins, dtype=np.int64))
        )
        return (
            self._keys_matrix(keys_raw),
            np.frombuffer(slots_raw, dtype=np.int64),
        )

    @property
    def by_bin(self):
        # truthiness probe used by the sliding operator ("anything live?")
        return {b: True for b in self._d.live_bins()}

    def peek_bin(self, b: int):
        keys, slots = self.bin_entries(b)
        if not len(keys):
            return None
        return dict(zip(self._rows_to_tuples(keys), slots.tolist()))

    def slots_for_keys(self, b: int, keys) -> dict:
        """{key: slot} for the subset of `keys` live in bin b — point
        lookups (O(len(keys))), not a whole-bin materialization."""
        if not keys:
            return {}
        mat = self._keys_to_matrix(keys)
        present, slots_raw = self._d.lookup(
            int(b), np.ascontiguousarray(mat.reshape(-1))
        )
        slots = np.frombuffer(slots_raw, dtype=np.int64)
        return {
            key: int(slots[i])
            for i, key in enumerate(keys) if present[i]
        }

    def _keys_to_matrix(self, keys) -> np.ndarray:
        if self.n_keys == 0:
            return np.zeros((len(keys), 1), dtype=np.int64)
        return np.asarray(keys, dtype=np.int64).reshape(
            len(keys), self._stride
        )

    def remove(self, b: int, keys) -> np.ndarray:
        """Remove specific keys from a bin (TTL eviction / retracted
        keys); returns the freed slots."""
        if not keys:
            return np.empty(0, dtype=np.int64)
        mat = self._keys_to_matrix(keys)
        freed = self._d.remove(int(b), np.ascontiguousarray(mat.reshape(-1)))
        return np.frombuffer(freed, dtype=np.int64).copy()

    def keys_for_slots(self, slots: np.ndarray):
        """Resolve slots back to their live (bin, key) via the native
        reverse index — O(len(slots)), like the python directory's
        key_of map (updating-aggregate dirty tracking)."""
        arr = np.ascontiguousarray(np.asarray(slots, dtype=np.int64))
        present, bins_raw, keys_raw = self._d.keys_for_slots(arr)
        # tolist() yields plain python ints in one C pass — a per-row
        # genexpr over numpy scalars dominated the updating flush
        bins = np.frombuffer(bins_raw, dtype=np.int64).tolist()
        keys = self._rows_to_tuples(self._keys_matrix(keys_raw))
        return [
            (bins[i], keys[i]) if ok else None
            for i, ok in enumerate(present)
        ]

    def live_bins(self) -> List[int]:
        return sorted(self._d.live_bins())

    def bins_up_to(self, limit: int) -> List[int]:
        return sorted(b for b in self._d.live_bins() if b < limit)

    def entries_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """All live entries as (bins, keys matrix, slots) arrays — one C
        call, no python tuple per key (checkpoint snapshots and the mesh
        facade's per-shard items() ride this)."""
        bins_raw, keys_raw, slots_raw = self._d.entries()
        return (
            np.frombuffer(bins_raw, dtype=np.int64),
            self._keys_matrix(keys_raw),
            np.frombuffer(slots_raw, dtype=np.int64),
        )

    def items(self):
        bins, keys, slots = self.entries_arrays()
        # C-level passes end to end: tolist()/zip instead of a python
        # int()+tuple() per row (the round-5 snapshot profile's cost)
        yield from zip(
            bins.tolist(), self._rows_to_tuples(keys), slots.tolist()
        )


def _i64able(t) -> bool:
    import pyarrow as pa

    # bool keys stay on the python path: native returns python ints and
    # pa.array(ints, type=bool_) is rejected at emission
    return pa.types.is_integer(t) or pa.types.is_timestamp(t)


def flat_key_widths(key_types):
    """Per-key-column int64 word counts for the native directory, or None
    when any column can't ride it (or the native module is absent).
    Struct columns (window structs) flatten into their child words when
    every child is integer/timestamp."""
    import pyarrow as pa

    if load_native() is None:
        return None
    widths = []
    for t in key_types:
        if pa.types.is_struct(t):
            if t.num_fields == 0 or not all(
                _i64able(t.field(j).type) for j in range(t.num_fields)
            ):
                return None
            widths.append(t.num_fields)
        elif _i64able(t):
            widths.append(1)
        else:
            return None
    return widths if sum(widths) <= 16 else None
