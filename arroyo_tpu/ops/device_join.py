"""Jitted merge-join probe: the device path for bin-local equi-joins.

TPU-native replacement for the reference's in-engine join probe
(/root/reference/crates/arroyo-worker/src/arrow/instant_join.rs:1-412,
join_with_expiration.rs:1-264): instead of a host hash join, the probe
runs as XLA programs: per-row key hashing (splitmix64 over the int64
key words), a device sort of the build side, the rank of every probe
hash in it, and vectorized pair expansion into a padded output bucket.
Hash-equal candidate pairs are verified against the full key words
host-side, so the join is exact even under 64-bit hash collisions (a
collision only costs spurious candidates, never wrong results).

Dynamic output size meets XLA's static-shape rule in two phases:
phase 1 computes per-probe-row match counts and their prefix sums on
device; only the scalar total crosses to host to pick a padded output
bucket; phase 2 expands the pair indices at that bucket size. All
arrays are padded to power-of-two buckets, so the compiled program
count stays O(log sizes) per key width.

What the two programs cost is set by two shapes, and each has a rule
that reads the shape alone (times are a TPU v5e's: PERF.md section 6,
PR 37):

* Phase 1 ranks every probe hash in the sorted build side with
  `jnp.searchsorted`, a binary search by gather: one dependent round of
  gathers over the whole probe side per level of the build bucket. At
  262,144 probe rows that is 73 ms against a 1,024-row bucket, 64 ms
  against 128 and 1.0 ms against 8. So a build side of up to
  `_SMALL_BUILD` (8) rows is padded to 8, not to 1,024: one bucket more
  per probe bucket and key width, not a ladder of them (a build side
  that grows from 1 row to 1,024 compiles phase 1 twice). The
  benchmark's three cells with a join (NEXmark q5, q7, q5 on the mesh)
  all probe a window's rows with its ONE max row.
* Phase 2 finds each output position's probe row: how many of the
  candidate counts' prefix sums are at or below it. In the floor bucket
  (1,024 positions: q7, one or two pairs) that is the same binary
  search, 0.7-0.8 ms whatever the probe side. A larger bucket takes the
  histogram of the prefix sums over the bucket and sums it from the
  left, one scatter-add and one prefix sum: q5's equi-key is the window
  alone, so all ~60,000 rows of a window are candidates of its max row,
  and 65,536 positions from 65,536 rows take 2.1 ms, not 17.6.

`probe()` books what it chose in the phase ledger: `join.probe.rank`
(`padded` = the build bucket) and `join.probe.fill` (`padded` = the
output bucket, `key` "search" or "hist").
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..utils.logging import get_logger
from ._jax import get_jax as _get_jax

logger = get_logger("device_join")

_fns = None


def log_probe_tier(op) -> None:
    """The line a join operator logs once at open: which probe tier it
    takes, on what platform, and the row floor below which a join stays
    on the host arrow join."""
    from ..config import config
    from . import _jax

    logger.info(
        "join %s: probe tier=%s platform=%s (device_join_min_rows=%d)",
        op.name, "device" if _jax.device_join_active() else "host",
        _jax.platform(), config().tpu.device_join_min_rows,
    )


def _build_fns():
    """Compile-cached device functions (jit caches per input shape)."""
    global _fns
    if _fns is not None:
        return _fns
    jax = _get_jax()
    jnp = jax.numpy

    U = jnp.uint64

    def mix(x):
        x = x + U(0x9E3779B97F4A7C15)
        x = (x ^ (x >> U(30))) * U(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> U(27))) * U(0x94D049BB133111EB)
        return x ^ (x >> U(31))

    def hash_rows(mat):
        h = jnp.zeros(mat.shape[0], dtype=jnp.uint64)
        for j in range(mat.shape[1]):
            h = mix(h ^ mat[:, j].astype(jnp.uint64))
        return h

    @jax.jit
    def phase1(l_mat, r_mat, n_l, n_r):
        """Sort the build side by hash, rank the probe side's hashes in
        it. Returns (order, lo, offs): build-side sort order, first
        candidate position per probe row, inclusive prefix sums of the
        candidate counts (offs[-1] = total candidate pairs)."""
        hl = hash_rows(l_mat)
        hr = hash_rows(r_mat)
        # padded build rows sort to the end under the max sentinel; a
        # real hash equal to the sentinel only adds candidates that the
        # host-side exact-key verification drops
        hr = jnp.where(
            jnp.arange(r_mat.shape[0]) < n_r, hr, U(0xFFFFFFFFFFFFFFFF)
        )
        order = jnp.argsort(hr)
        hrs = hr[order]
        lo = jnp.searchsorted(hrs, hl, side="left")
        hi = jnp.searchsorted(hrs, hl, side="right")
        counts = jnp.where(
            jnp.arange(l_mat.shape[0]) < n_l, hi - lo, 0
        )
        offs = jnp.cumsum(counts)
        return order, lo, offs

    # phase 2 expands candidate ranges into (probe_idx, build_idx) pairs
    # over a fixed-size output grid; slots past the total are invalid.
    # The output size is a shape, so it must be static: a size-keyed
    # cache of jitted closures instead of a traced argument
    phase2_cache = {}

    def phase2_at(size, order, lo, offs, rows=None):
        fn = phase2_cache.get(size)
        if fn is None:
            fill = _fill(size)

            def impl(order, lo, offs, _size=size):
                pos = jnp.arange(_size)
                # li = the probe rows whose candidates end at or before pos
                if fill == "hist":
                    ends = jnp.zeros(_size, jnp.int32).at[offs].add(
                        1, mode="drop")
                    li = jnp.cumsum(ends)
                else:
                    li = jnp.searchsorted(offs, pos, side="right")
                li_c = jnp.clip(li, 0, offs.shape[0] - 1)
                start = jnp.where(li_c > 0, offs[li_c - 1], 0)
                rpos = lo[li_c] + (pos - start)
                ri = order[jnp.clip(rpos, 0, order.shape[0] - 1)]
                valid = pos < offs[-1]
                return li_c, ri, valid

            from ..obs import device as obs_device

            fn = obs_device.InstrumentedJit(
                "join.phase2", jax.jit(impl)
            )
            phase2_cache[size] = fn
        return fn(order, lo, offs, rung=size, rows=rows)

    from ..obs import device as obs_device

    _fns = (obs_device.InstrumentedJit("join.phase1", phase1), phase2_at)
    return _fns


def _bucket(n: int, lo: int = 1024) -> int:
    b = lo
    while b < n:
        b <<= 1
    return b


# the two shape rules of the module docstring
_SMALL_BUILD = 8


def _build_bucket(n_r: int) -> int:
    return _SMALL_BUILD if n_r <= _SMALL_BUILD else _bucket(n_r)


def _fill(size: int) -> str:
    return "hist" if size > _bucket(0) else "search"


def _pad_matrix(cols: List[np.ndarray], bucket: int) -> np.ndarray:
    mat = np.zeros((bucket, len(cols)), dtype=np.int64)
    n = len(cols[0])
    for j, c in enumerate(cols):
        mat[:n, j] = c
    return mat


def probe(
    lcols: List[np.ndarray], rcols: List[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact inner-join pair indices for int64 key columns.

    Returns (l_idx, r_idx): row indices into the probe/build sides such
    that the full key tuples are equal, in probe-side order."""
    n_l, n_r = len(lcols[0]), len(rcols[0])
    if n_l == 0 or n_r == 0:
        e = np.empty(0, dtype=np.int64)
        return e, e
    phase1, phase2_at = _build_fns()
    from ..obs import device as obs_device

    from ..obs import timeline

    lb, rb = _bucket(n_l), _build_bucket(n_r)
    obs_device.note_padding("join.phase1", rb, n_l + n_r, lb + rb)
    # sub-steps of the caller's join.probe, in the ledger only
    with timeline.phase("join.probe.count", annotate=False):
        l_mat = _pad_matrix(lcols, lb)
        r_mat = _pad_matrix(rcols, rb)
        # rows= the build side's: the side whose padding `rung` names
        order, lo, offs = phase1(
            l_mat, r_mat, np.int64(n_l), np.int64(n_r), rung=rb, rows=n_r
        )
        timeline.note("join.probe.rank", 0.0, n=n_l, padded=rb)
        # the scalar crosses to the host: waits for phase 1 on the device
        total = int(offs[-1])
    if total == 0:
        e = np.empty(0, dtype=np.int64)
        return e, e
    with timeline.phase("join.probe.expand", annotate=False):
        size = _bucket(total)
        li, ri, valid = phase2_at(size, order, lo, offs, total)
        timeline.note("join.probe.fill", 0.0, n=total, padded=size,
                      key=_fill(size))
        li = np.asarray(li)
        ri = np.asarray(ri)
        valid = np.asarray(valid)
    with timeline.phase("join.probe.verify", n=total, annotate=False):
        mask = valid & (li < n_l) & (ri < n_r)
        li = li[mask]
        ri = ri[mask]
        # exact verification of hash-equal candidates on the real key words
        keep = np.ones(len(li), dtype=bool)
        for lc, rc in zip(lcols, rcols):
            keep &= lc[li] == rc[ri]
        return li[keep], ri[keep]


def _codable(t) -> bool:
    import pyarrow as pa

    return (
        pa.types.is_integer(t)
        or pa.types.is_timestamp(t)
        or pa.types.is_boolean(t)
        or pa.types.is_string(t)
        or pa.types.is_large_string(t)
        or pa.types.is_binary(t)
    )


def prepare_join_keys(
    left, right, key_names: List[str]
) -> Optional[Tuple[List[np.ndarray], List[np.ndarray],
                    Optional[np.ndarray], Optional[np.ndarray]]]:
    """Two-sided key preparation for the device probe.

    Returns (lcols, rcols, lsel, rsel) — int64 key word columns per side
    plus the original-row indices they correspond to (None = identity),
    or None when some key type can't ride the probe.

    * String/binary keys are dictionary-encoded against a JOINT
      dictionary (both sides concatenated) so equal strings get equal
      int64 codes — the probe then stays exact, no hashing of values.
    * Nullable keys: SQL equi-joins never match on NULL, so rows with
      any null key word are pre-filtered and the selection mapping is
      returned for the caller to translate pair indices back.
    """
    import pyarrow as pa

    n_l, n_r = left.num_rows, right.num_rows
    lcols: List[np.ndarray] = []
    rcols: List[np.ndarray] = []
    l_valid = np.ones(n_l, dtype=bool)
    r_valid = np.ones(n_r, dtype=bool)
    any_null = False
    for name in key_names:
        lc = left.column(name).combine_chunks()
        rc = right.column(name).combine_chunks()
        if not (_codable(lc.type) and _codable(rc.type)):
            return None
        if lc.null_count or rc.null_count:
            any_null = True
            lm = np.asarray(lc.is_valid())
            rm = np.asarray(rc.is_valid())
            l_valid &= lm
            r_valid &= rm
        if pa.types.is_string(lc.type) or pa.types.is_large_string(
            lc.type
        ) or pa.types.is_binary(lc.type):
            # joint dictionary: codes are comparable across sides.
            # large_binary, not large_string: binary keys may hold
            # non-UTF8 bytes a string cast would reject
            both = pa.chunked_array([lc.cast(pa.large_binary()),
                                     rc.cast(pa.large_binary())])
            codes = both.combine_chunks().dictionary_encode().indices
            c = np.asarray(codes.fill_null(-1).cast(pa.int64()))
            lcols.append(c[:n_l])
            rcols.append(c[n_l:])
        else:
            lcols.append(
                np.asarray(lc.fill_null(0).cast(pa.int64(), safe=False))
            )
            rcols.append(
                np.asarray(rc.fill_null(0).cast(pa.int64(), safe=False))
            )
    if not any_null:
        return lcols, rcols, None, None
    lsel = np.nonzero(l_valid)[0]
    rsel = np.nonzero(r_valid)[0]
    return (
        [c[lsel] for c in lcols],
        [c[rsel] for c in rcols],
        lsel,
        rsel,
    )
