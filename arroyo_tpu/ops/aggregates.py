"""Keyed aggregate accumulators: the device-resident window state.

This is the TPU-native replacement for the reference's per-bin DataFusion
partial-aggregation streams (/root/reference/crates/arroyo-worker/src/arrow/
tumbling_aggregating_window.rs:66-110): instead of running a CPU physical
plan per bin, ALL (bin, key) groups share flat device arrays of accumulator
slots, updated with one jitted scatter-reduce per batch and drained with one
gather per watermark. Slot assignment (the "hash table") stays host-side in
round 1 — a python dict over unique (bin, key) pairs, O(unique) per batch —
while the O(rows) arithmetic runs on device.

Shape discipline: `slots`/value arrays are padded to bucket sizes
(config.tpu.shape_buckets) so XLA compiles O(buckets × capacities) programs,
not one per batch size. Padded rows scatter neutral elements into a
reserved scratch slot.

Supported aggregate kinds: count, sum, min, max, avg (each decomposes into
"physical" accumulators: add/min/max over a column or the constant 1).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import config
from ..obs import device as obs_device
from ..obs import timeline

# jax import deferred so host-only deployments can import the module tree
from ._jax import get_jax as _get_jax


INT_MIN = np.iinfo(np.int64).min
INT_MAX = np.iinfo(np.int64).max


# one-argument variance family: decomposes to (Σx, Σx², n) — pure
# add-reductions, so updates stay on-device AND invert under retraction
VAR_KINDS = ("var", "var_samp", "var_pop", "stddev", "stddev_samp",
             "stddev_pop")
# two-argument regression family over (y, x): (Σy, Σx, Σxy, Σy², Σx², n)
REGR_KINDS = ("covar_pop", "covar_samp", "corr", "regr_slope",
              "regr_intercept", "regr_r2", "regr_avgx", "regr_avgy",
              "regr_count", "regr_sxx", "regr_syy", "regr_sxy")
# host-buffered builtins (raw values kept per slot; finalized at emission)
BUFFER_KINDS = ("median", "approx_median", "approx_percentile_cont",
                "approx_percentile_cont_with_weight", "bit_and", "bit_or",
                "bit_xor", "array_agg")


@dataclasses.dataclass(frozen=True)
class AggSpec:
    kind: str  # count | sum | min | max | avg | count_distinct | udaf | ...
    col: Optional[int]  # input column index (None for count(*))
    name: str  # output field name
    is_float: bool = False  # input/output numeric class
    udaf: Optional[str] = None  # registered UDAF name when kind == "udaf"
    col2: Optional[int] = None  # second argument (regr family, weights)
    param: Optional[float] = None  # percentile fraction etc.
    # DISTINCT modifier on a non-count aggregate (sum/avg/min/max DISTINCT):
    # values dedupe through the multiset, finalized per kind
    distinct: bool = False
    # retraction replay (reference incremental_aggregator.rs raw-value
    # replay, :77-90): a non-invertible aggregate consuming an updating
    # input keeps value -> signed count and re-aggregates at emission, so
    # retractions erase their contribution exactly
    replay: bool = False

    def host_state(self) -> Optional[str]:
        """Host-resident per-slot state flavor, or None when the aggregate
        decomposes fully onto device phys arrays. 'buffer' = raw value
        chunks (UDAFs, median/percentile/bit/array_agg; append-only).
        'multiset' = value -> signed count (count_distinct/approx_distinct,
        DISTINCT modifiers, and retraction replay; retractable,
        mergeable)."""
        if self.kind in ("count_distinct", "approx_distinct"):
            return "multiset"
        if self.distinct or self.replay:
            return "multiset"
        if self.kind == "udaf" or self.kind in BUFFER_KINDS:
            return "buffer"
        return None

    def phys(self) -> List[Tuple[str, str, str]]:
        """[(op, dtype, source)]: op in add|min|max, dtype i8|f8, source
        col|col2|one|sq (col²)|sq2 (col2²)|prod (col·col2)."""
        if self.host_state() is not None:
            # host-state aggregates keep raw values host-side (the
            # reference hands all values to its UDAFs too, udafs.rs;
            # count_distinct is a DataFusion grouped-distinct there)
            return []
        if self.kind == "count":
            return [("add", "i8", "one")]
        if self.kind in VAR_KINDS:
            return [("add", "f8", "col"), ("add", "f8", "sq"),
                    ("add", "i8", "one")]
        if self.kind in REGR_KINDS:
            return [("add", "f8", "col"), ("add", "f8", "col2"),
                    ("add", "f8", "prod"), ("add", "f8", "sq"),
                    ("add", "f8", "sq2"), ("add", "i8", "one")]
        if self.kind == "bool_and":
            return [("min", "i8", "col")]
        if self.kind == "bool_or":
            return [("max", "i8", "col")]
        d = "f8" if self.is_float else "i8"
        if self.kind == "sum":
            return [("add", d, "col")]
        if self.kind == "min":
            return [("min", d, "col")]
        if self.kind == "max":
            return [("max", d, "col")]
        if self.kind == "avg":
            return [("add", "f8", "col"), ("add", "i8", "one")]
        raise ValueError(f"unknown aggregate {self.kind}")


def _buffer_reducer(spec: "AggSpec"):
    """Grouped-values reducer for one buffered aggregate: the registered
    user function for UDAFs, a builtin for median/percentile/bit/array."""
    kind = spec.kind
    if kind == "udaf":
        from ..udf.registry import get_udaf

        u = get_udaf(spec.udaf)
        if u is None:
            raise ValueError(f"unknown UDAF {spec.udaf!r}")
        if spec.col2 is not None:
            return lambda g: u.fn(g[:, 0], g[:, 1])
        return u.fn
    if kind in ("median", "approx_median"):
        def median_fn(g):
            v = _not_null(g)
            return float(np.median(v)) if len(v) else np.nan

        return median_fn
    if kind == "approx_percentile_cont":
        p = float(spec.param) * 100.0

        def pct_fn(g):
            v = _not_null(g)
            return float(np.percentile(v, p)) if len(v) else np.nan

        return pct_fn
    if kind == "approx_percentile_cont_with_weight":
        p = float(spec.param)

        def weighted(g):
            if not len(g):
                return np.nan
            vals = g[:, 0].astype(np.float64)
            w = g[:, 1].astype(np.float64)
            order = np.argsort(vals, kind="stable")
            vals, w = vals[order], w[order]
            cum = np.cumsum(w)
            total = cum[-1]
            if total <= 0:
                return np.nan
            return float(vals[np.searchsorted(cum, p * total, "left")])

        return weighted
    if kind in ("bit_and", "bit_or", "bit_xor"):
        op = {"bit_and": np.bitwise_and, "bit_or": np.bitwise_or,
              "bit_xor": np.bitwise_xor}[kind]

        def bit_fn(g):
            v = _not_null(g)
            return int(op.reduce(v.astype(np.int64))) if len(v) else 0

        return bit_fn
    if kind == "array_agg":
        return lambda g: list(g)
    raise ValueError(f"unknown buffered aggregate {kind}")


def _reduce_multiset(spec: "AggSpec", d: dict):
    """Finalize one slot's value->count multiset. DISTINCT modifiers
    ignore the counts (each live value contributes once); retraction
    replay (spec.replay) expands values by their signed live counts and
    re-aggregates, so a fully-retracted value contributes nothing."""
    kind = spec.kind
    if not d:
        if kind == "count":
            return 0
        return [] if kind == "array_agg" else None
    keys = list(d.keys())
    if kind == "min":
        return min(keys)
    if kind == "max":
        return max(keys)
    if kind == "bool_and":
        return all(bool(k) for k in keys)
    if kind == "bool_or":
        return any(bool(k) for k in keys)
    counts = (
        np.ones(len(d), dtype=np.int64)
        if spec.distinct
        else np.fromiter(d.values(), dtype=np.int64, count=len(d))
    )
    if kind == "count":
        return int(counts.sum())
    if kind == "sum":
        vals = np.asarray(keys)
        return (vals * counts).sum()
    if kind == "avg":
        vals = np.asarray(keys, dtype=np.float64)
        return float((vals * counts).sum() / counts.sum())
    # buffered builtins / UDAFs: expand to the raw value group and reduce
    karr = np.empty(len(keys), dtype=object)
    karr[:] = keys
    expanded = np.repeat(karr, counts)
    if spec.col2 is not None:
        rows = [list(t) for t in expanded]
        try:
            g = np.asarray(rows, dtype=np.float64)
        except (ValueError, TypeError):
            # non-numeric 2-arg groups (e.g. string UDAF args) keep
            # object dtype, matching the buffer path's column_stack
            g = np.empty((len(rows), 2), dtype=object)
            for i, r in enumerate(rows):
                g[i] = r
    else:
        g = np.asarray(expanded.tolist())
    return _buffer_reducer(spec)(g)


def _not_null(g: np.ndarray) -> np.ndarray:
    return g[_not_null_mask(g)]


def _finalize_variance(kind: str, vals: List[np.ndarray]) -> np.ndarray:
    """(Σx, Σx², n) -> variance/stddev. Sample variants return NaN below
    two rows (SQL NULL); population variants need one."""
    s, ss, n = (v.astype(np.float64) for v in vals)
    pop = kind.endswith("_pop")
    denom = n if pop else n - 1
    var = (ss - s * s / np.maximum(n, 1)) / denom
    var = np.where(denom > 0, np.maximum(var, 0.0), np.nan)
    if kind.startswith("stddev"):
        return np.sqrt(var)
    return var


def _finalize_regression(kind: str, vals: List[np.ndarray]) -> np.ndarray:
    """(Σy, Σx, Σxy, Σy², Σx², n) -> the SQL regression family over
    (y, x) argument order (regr_slope(y, x) regresses y on x)."""
    sy, sx, sxy, syy, sxx, n = (v.astype(np.float64) for v in vals)
    nz = np.maximum(n, 1)
    cxy = sxy - sx * sy / nz  # n·cov
    cxx = sxx - sx * sx / nz
    cyy = syy - sy * sy / nz
    if kind == "covar_pop":
        return np.where(n > 0, cxy / nz, np.nan)
    if kind == "covar_samp":
        return np.where(n > 1, cxy / (n - 1), np.nan)
    if kind == "corr":
        return np.where(
            (n > 0) & (cxx > 0) & (cyy > 0),
            cxy / np.sqrt(cxx * cyy), np.nan,
        )
    if kind == "regr_slope":
        return np.where((n > 0) & (cxx != 0), cxy / cxx, np.nan)
    if kind == "regr_intercept":
        slope = np.where((n > 0) & (cxx != 0), cxy / cxx, np.nan)
        return sy / nz - slope * sx / nz
    if kind == "regr_r2":
        r = np.where(
            (n > 0) & (cxx > 0) & (cyy > 0),
            cxy / np.sqrt(cxx * cyy), np.nan,
        )
        return r * r
    if kind == "regr_avgx":
        return np.where(n > 0, sx / nz, np.nan)
    if kind == "regr_avgy":
        return np.where(n > 0, sy / nz, np.nan)
    if kind == "regr_count":
        return n.astype(np.int64)
    if kind == "regr_sxx":
        return np.where(n > 0, cxx, np.nan)
    if kind == "regr_syy":
        return np.where(n > 0, cyy, np.nan)
    if kind == "regr_sxy":
        return np.where(n > 0, cxy, np.nan)
    raise ValueError(f"unknown regression kind {kind}")


def _src_values(spec: "AggSpec", src: str, cols: Dict) -> np.ndarray:
    """Row values for one physical accumulator source. Derived sources
    (sq/prod) compute in float64 so Σx² and Σxy never overflow int64."""
    if src == "col":
        return cols[spec.col]
    if src == "col2":
        return cols[spec.col2]
    if src == "sq":
        x = cols[spec.col].astype(np.float64, copy=False)
        return x * x
    if src == "sq2":
        x = cols[spec.col2].astype(np.float64, copy=False)
        return x * x
    if src == "prod":
        return (
            cols[spec.col].astype(np.float64, copy=False)
            * cols[spec.col2].astype(np.float64, copy=False)
        )
    raise ValueError(f"unknown phys source {src}")


def _not_null_mask(vals: np.ndarray) -> np.ndarray:
    """True per row where the value is non-null (None or NaN)."""
    if vals.dtype == object:
        return np.fromiter(
            (v is not None and v == v for v in vals),
            dtype=bool, count=len(vals),
        )
    if vals.dtype.kind == "f":
        return ~np.isnan(vals)
    if vals.dtype.kind == "M":
        return ~np.isnat(vals)
    return np.ones(len(vals), dtype=bool)


def _neutral(op: str, dtype: str, use32: bool = False):
    if op == "add":
        return 0
    if dtype == "f8":
        return np.inf if op == "min" else -np.inf
    if use32:
        info = np.iinfo(np.int32)
        return info.max if op == "min" else info.min
    return INT_MAX if op == "min" else INT_MIN


def _np_dtype(d: str, use32: bool = False):
    if use32:
        return np.float32 if d == "f8" else np.int32
    return np.float64 if d == "f8" else np.int64


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return int(2 ** np.ceil(np.log2(max(n, 1))))


class Accumulator:
    """Flat slot-indexed accumulator state shared by all (bin, key) groups of
    one window-operator subtask. Backend 'jax' (device) or 'numpy' (host)."""

    def __init__(self, specs: List[AggSpec], capacity: int = 4096,
                 backend: str = "jax"):
        self.specs = specs
        self.backend = backend
        # TPU v5e has no native int64/float64 (emulated, slow); the
        # opt-in 32-bit mode keeps device accumulators in int32/float32.
        # Counts/mins/maxes of bounded values are exact; large sums can
        # overflow — hence opt-in (config tpu.use_32bit_accumulators)
        self.use32 = bool(
            backend == "jax"
            and getattr(config().tpu, "use_32bit_accumulators", False)
        )
        self.capacity = capacity  # last slot is scratch for padded rows
        self.phys: List[Tuple[str, str, str, int]] = []  # op,dtype,src,spec_idx
        for si, spec in enumerate(specs):
            for op, dtype, src in spec.phys():
                self.phys.append((op, dtype, src, si))
        self._buckets = tuple(config().tpu.shape_buckets)
        # host-side per-slot state: spec idx -> slot -> chunks ('buffer',
        # UDAFs) or value->count dict ('multiset', count_distinct)
        self.host_kinds: Dict[int, str] = {
            i: s.host_state() for i, s in enumerate(specs)
            if s.host_state() is not None
        }
        self.udaf_idx = [
            i for i, k in self.host_kinds.items() if k == "buffer"
        ]
        self.multiset_idx = [
            i for i, k in self.host_kinds.items() if k == "multiset"
        ]
        self.udaf_store: Dict[int, Dict[int, list]] = {
            i: {} for i in self.udaf_idx
        }
        self.multiset_store: Dict[int, Dict[int, dict]] = {
            i: {} for i in self.multiset_idx
        }
        self._gather_slots: Optional[np.ndarray] = None
        self._segment_udaf: Optional[Dict[int, list]] = None
        self._segment_multiset: Optional[Dict[int, list]] = None
        if backend == "jax":
            jnp = _get_jax().numpy
            self.state = [
                jnp.full(capacity, self._neutral(op, dt), dtype=self._dt(dt))
                for op, dt, _, _ in self.phys
            ]
            self._update_fn = self._make_update_fn()
            self._gather_fn = self._make_gather_fn()
        else:
            self.state = [
                np.full(capacity, self._neutral(op, dt), dtype=self._dt(dt))
                for op, dt, _, _ in self.phys
            ]

    def _dt(self, d: str):
        return _np_dtype(d, self.use32)

    def _neutral(self, op: str, dt: str):
        return _neutral(op, dt, self.use32)

    # -- capacity -----------------------------------------------------------

    def grow(self, min_capacity: int):
        # 4x steps (not 2x): every growth re-specializes the jitted
        # update/gather/reset programs for the new state shape, so fewer,
        # larger jumps bound recompilation churn at high cardinality
        new_cap = self.capacity
        while new_cap < min_capacity:
            new_cap *= 4
        if new_cap == self.capacity:
            return
        # the old scratch slot (capacity-1) absorbed padded-row scatters;
        # it becomes an allocatable slot after growth and must restart
        # from neutral
        if self.backend == "jax":
            jnp = _get_jax().numpy
            self.state = [
                jnp.concatenate(
                    [s, jnp.full(new_cap - self.capacity,
                                 self._neutral(op, dt),
                                 dtype=self._dt(dt))]
                ).at[self.capacity - 1].set(self._neutral(op, dt))
                for s, (op, dt, _, _) in zip(self.state, self.phys)
            ]
        else:
            self.state = [
                np.concatenate(
                    [s, np.full(new_cap - self.capacity,
                                self._neutral(op, dt),
                                dtype=self._dt(dt))]
                )
                for s, (op, dt, _, _) in zip(self.state, self.phys)
            ]
            for (op, dt, _, _), s in zip(self.phys, self.state):
                s[self.capacity - 1] = self._neutral(op, dt)
        self.capacity = new_cap

    # -- update (hot path) --------------------------------------------------

    def update(self, slots: np.ndarray, cols: Dict[int, np.ndarray],
               signs: Optional[np.ndarray] = None):
        """Scatter-reduce a batch. slots[i] = accumulator slot of row i
        (must be < capacity-1; capacity-1 is scratch). cols maps input column
        index -> numpy array of row values. `signs` (+1 append / -1 retract
        per row) makes the update invertible for retraction-consuming
        aggregates: add-reductions (count/sum/avg/variance/regression)
        apply the sign arithmetically, multisets (count_distinct, DISTINCT
        modifiers, replay specs) track signed value counts. Non-add device
        reductions (min/max phys) cannot invert — the planner must mark
        those specs `replay` first."""
        n = len(slots)
        if n == 0:
            return
        self._check_signed(signs)
        if self.backend == "numpy" or not self.phys:
            # the host tier: the whole update is host work
            with timeline.phase("agg.host", n=n):
                self._update_host(slots, cols, signs)
                if self.phys:
                    self._np_update(slots, cols, signs)
            return
        with timeline.phase("agg.pack", n=n):
            self._update_host(slots, cols, signs)
            jnp = _get_jax().numpy
            padded = _bucket(n, self._buckets)
            slots_p = np.full(padded, self.capacity - 1, dtype=np.int64)
            slots_p[:n] = slots
            valid = np.zeros(padded, dtype=np.int64)
            valid[:n] = 1 if signs is None else signs
            inputs = []
            for op, dt, src, si in self.phys:
                spec = self.specs[si]
                if src == "one":
                    vals = valid
                else:
                    vals = np.zeros(padded, dtype=self._dt(dt))
                    base = _src_values(spec, src, cols)
                    vals[:n] = base if signs is None else base * signs
                    if op != "add":
                        vals[n:] = self._neutral(op, dt)
                inputs.append(jnp.asarray(vals))
            slots_d = jnp.asarray(slots_p)
        obs_device.note_padding("agg.update", padded, n, padded)
        # the host side of the call alone: the device runs it later
        with timeline.phase("agg.enqueue"):
            self.state = self._update_fn(
                self.state, slots_d, *inputs, rung=padded, rows=n
            )

    def _check_signed(self, signs: Optional[np.ndarray]):
        if signs is not None and (
            self.udaf_idx or any(op != "add" for op, _, _, _ in self.phys)
        ):
            raise ValueError(
                "signed (retractable) update reached a non-invertible "
                "accumulator (min/max phys or append-only buffer); the "
                "planner should have marked these specs replay=True"
            )

    def _update_host(self, slots: np.ndarray, cols: Dict[int, np.ndarray],
                     signs: Optional[np.ndarray] = None):
        """Fold a batch into the host-side per-slot states: value chunks
        for 'buffer' specs, signed value counts for 'multiset' specs."""
        if not self.host_kinds:
            return
        n = len(slots)
        order = np.argsort(slots, kind="stable")
        s_sorted = slots[order]
        bounds = np.nonzero(np.diff(s_sorted))[0] + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [n]])
        sg_sorted = signs[order] if signs is not None else None
        for si in self.udaf_idx:
            vals = self._host_vals(si, cols)[order]
            spec = self.specs[si]
            if spec.col2 is not None:
                # two-argument buffers (weighted percentile, 2-arg UDAFs)
                # stack to one (rows, 2) chunk so chunks concatenate
                second = cols[("raw", spec.col2)] if (
                    "raw", spec.col2) in cols else cols[spec.col2]
                vals = np.column_stack([vals, second[order]])
            store = self.udaf_store[si]
            for lo, hi in zip(starts, ends):
                store.setdefault(int(s_sorted[lo]), []).append(vals[lo:hi])
        for si in self.multiset_idx:
            # SQL aggregates exclude NULLs; raw columns carry them as None
            # (object dtype) or NaN (float)
            vals = self._host_vals(si, cols)[order]
            valid = _not_null_mask(vals)
            spec = self.specs[si]
            if spec.col2 is not None:
                # two-argument multisets (weighted percentile / 2-arg UDAF
                # replay): the multiset key is the (v1, v2) pair. col2
                # nulls/NaNs must be masked too — None breaks np.unique's
                # sort and a NaN-bearing pair key never equals itself, so
                # a retraction could never cancel its insert
                second = cols[("raw", spec.col2)] if (
                    "raw", spec.col2) in cols else cols[spec.col2]
                second = second[order]
                valid = valid & _not_null_mask(second)
                pairs = np.empty(len(vals), dtype=object)
                pairs[:] = list(zip(vals.tolist(), second.tolist()))
                vals = pairs
            store = self.multiset_store[si]
            for lo, hi in zip(starts, ends):
                d = store.setdefault(int(s_sorted[lo]), {})
                gv = valid[lo:hi]
                group = vals[lo:hi][gv]
                if sg_sorted is None:
                    uniq, counts = np.unique(group, return_counts=True)
                    for v, c in zip(uniq.tolist(), counts.tolist()):
                        d[v] = d.get(v, 0) + c
                else:
                    for v, sg in zip(group.tolist(),
                                     sg_sorted[lo:hi][gv].tolist()):
                        nc = d.get(v, 0) + int(sg)
                        if nc <= 0:
                            d.pop(v, None)
                        else:
                            d[v] = nc

    def _host_vals(self, si: int, cols: Dict) -> np.ndarray:
        """Host-state specs read the raw (uncast) representation when the
        operator provided one under ('raw', col) — a column shared with a
        float-cast numeric spec would otherwise lose integer precision
        above 2^53 in the multiset keys."""
        c = self.specs[si].col
        return cols[("raw", c)] if ("raw", c) in cols else cols[c]

    def _make_update_fn(self):
        jax = _get_jax()
        phys = list(self.phys)

        @partial(jax.jit, donate_argnums=(0,))
        def update(state, slots, *vals):
            out = []
            for (op, dt, src, si), s, v in zip(phys, state, vals):
                if op == "add":
                    out.append(s.at[slots].add(v))
                elif op == "min":
                    out.append(s.at[slots].min(v))
                else:
                    out.append(s.at[slots].max(v))
            return out

        return obs_device.InstrumentedJit("agg.update", update)

    def _np_update(self, slots, cols, signs=None):
        for (op, dt, src, si), s in zip(self.phys, self.state):
            spec = self.specs[si]
            if src == "one":
                vals = (
                    np.ones(len(slots), dtype=np.int64)
                    if signs is None else signs.astype(np.int64)
                )
            else:
                vals = _src_values(spec, src, cols).astype(
                    self._dt(dt), copy=False
                )
                if signs is not None:
                    vals = vals * signs
            if op == "add":
                np.add.at(s, slots, vals)
            elif op == "min":
                np.minimum.at(s, slots, vals)
            else:
                np.maximum.at(s, slots, vals)

    # -- drain --------------------------------------------------------------

    def gather(self, slots: np.ndarray,
               materialize: bool = True) -> List[np.ndarray]:
        """Read accumulator values for `slots` (emission); returns one numpy
        array per physical accumulator. The slots are remembered so
        finalize() can resolve UDAF value buffers for the same emission.
        With materialize=False the jax device->host copy is only
        *dispatched*: the returned arrays are device arrays whose
        np.asarray completes later (async snapshot overlap), PADDED to
        the gather's bucket: the caller takes `np.asarray(v)[:len(slots)]`
        behind the copy. A slice here would be an eager device program
        over a length that is new at every barrier: a compile each time,
        on the engine's thread."""
        self._gather_slots = np.asarray(slots)
        self._segment_udaf = None
        self._segment_multiset = None
        if len(slots) == 0:
            return [np.empty(0, dtype=s.dtype) for s in
                    (self.state if self.backend == "numpy" else self.state)]
        if self.backend == "numpy":
            return [s[slots] for s in self.state]
        jnp = _get_jax().numpy
        padded = _bucket(len(slots), self._buckets)
        obs_device.note_padding("agg.gather", padded, len(slots), padded)
        # sub-steps of the caller's close.combine, in the ledger only
        with timeline.phase("agg.gather", annotate=False):
            slots_p = np.full(padded, self.capacity - 1, dtype=np.int64)
            slots_p[: len(slots)] = slots
            outs = self._gather_fn(
                self.state, jnp.asarray(slots_p), rung=padded,
                rows=len(slots)
            )
        if not materialize:
            return list(outs)
        # the device-to-host read: waits for every program queued before it
        with timeline.phase("agg.read", n=len(slots), annotate=False):
            return [np.asarray(o)[: len(slots)] for o in outs]

    def _make_gather_fn(self):
        jax = _get_jax()

        @jax.jit
        def gather(state, slots):
            return [s[slots] for s in state]

        return obs_device.InstrumentedJit("agg.gather", gather)

    def drop_host_state(self, slots: np.ndarray):
        """Forget host-side per-slot state (UDAF buffers / multisets) for
        freed slots — the host half of reset_slots, for callers that
        fused the device half into the gather (gather_and_reset)."""
        self._drop_udaf_slots(slots)

    def _drop_udaf_slots(self, slots: np.ndarray):
        for si in self.udaf_idx:
            store = self.udaf_store[si]
            for s in slots:
                store.pop(int(s), None)
        for si in self.multiset_idx:
            store = self.multiset_store[si]
            for s in slots:
                store.pop(int(s), None)

    def reset_slots(self, slots: np.ndarray):
        """Return emitted slots to neutral so they can be reused."""
        self._drop_udaf_slots(slots)
        if len(slots) == 0 or not self.phys:
            return
        if self.backend == "numpy":
            for (op, dt, _, _), s in zip(self.phys, self.state):
                s[slots] = self._neutral(op, dt)
            return
        with timeline.phase("agg.reset", annotate=False):
            self._reset_device(slots)

    def _reset_device(self, slots: np.ndarray):
        jnp = _get_jax().numpy
        padded = _bucket(len(slots), self._buckets)
        slots_p = np.full(padded, self.capacity - 1, dtype=np.int64)
        slots_p[: len(slots)] = slots
        if not hasattr(self, "_reset_fn"):
            jax = _get_jax()
            neutrals = [
                self._neutral(op, dt) for op, dt, _, _ in self.phys
            ]

            @partial(jax.jit, donate_argnums=(0,))
            def reset(state, s_idx):
                return [
                    s.at[s_idx].set(nv) for s, nv in zip(state, neutrals)
                ]

            self._reset_fn = obs_device.InstrumentedJit("agg.reset", reset)
        self.state = self._reset_fn(
            self.state, jnp.asarray(slots_p), rung=padded, rows=len(slots)
        )

    # -- finalize -----------------------------------------------------------

    def finalize(self, gathered: List[np.ndarray]) -> List[np.ndarray]:
        """Physical accumulator values -> one output column per spec.
        Host-state specs resolve from the per-slot stores of the slots from
        the preceding gather()/combine_for_segments()."""
        out = []
        pi = 0
        for si, spec in enumerate(self.specs):
            hs = spec.host_state()
            if hs == "buffer":
                out.append(self._finalize_udaf(si))
                continue
            if hs == "multiset":
                out.append(self._finalize_multiset(si))
                continue
            n_phys = len(spec.phys())
            vals = gathered[pi: pi + n_phys]
            pi += n_phys
            with np.errstate(invalid="ignore", divide="ignore"):
                if spec.kind == "avg":
                    out.append(vals[0] / np.maximum(vals[1], 1))
                elif spec.kind in VAR_KINDS:
                    out.append(_finalize_variance(spec.kind, vals))
                elif spec.kind in REGR_KINDS:
                    out.append(_finalize_regression(spec.kind, vals))
                elif spec.kind in ("bool_and", "bool_or"):
                    out.append(vals[0] != 0)
                else:
                    out.append(vals[0])
        return out

    def _finalize_multiset(self, si: int) -> np.ndarray:
        spec = self.specs[si]
        if self._segment_multiset is not None:
            dicts = self._segment_multiset.get(si, [])
        else:
            store = self.multiset_store[si]
            dicts = [store.get(int(s), {}) for s in self._gather_slots]
        if spec.kind in ("count_distinct", "approx_distinct"):
            return np.asarray([len(d) for d in dicts], dtype=np.int64)
        out = [_reduce_multiset(spec, d) for d in dicts]
        if spec.kind == "array_agg":
            arr = np.empty(len(out), dtype=object)
            arr[:] = out
            return arr
        return np.asarray(out)

    def _finalize_udaf(self, si: int) -> np.ndarray:
        """Evaluate a buffered aggregate (registered UDAF or builtin
        median/percentile/bit/array_agg reducer) per emitted slot."""
        spec = self.specs[si]
        if self._segment_udaf is not None:
            groups = self._segment_udaf.get(si, [])
        else:
            store = self.udaf_store[si]
            empty = (
                np.empty((0, 2)) if spec.col2 is not None else np.empty(0)
            )
            groups = [
                np.concatenate(store.get(int(s), [empty]))
                for s in self._gather_slots
            ]
        fn = _buffer_reducer(spec)
        out = [fn(g) for g in groups]
        if spec.kind == "array_agg":
            arr = np.empty(len(out), dtype=object)
            arr[:] = out
            return arr
        return np.asarray(out)

    def combine_for_segments(
        self, slots: np.ndarray, seg_ids: np.ndarray, n_segments: int
    ) -> List[np.ndarray]:
        """Merge per-slot accumulators into per-segment values (sliding
        window emission): device phys arrays segment-reduce on host; UDAF
        buffers concatenate per segment for the subsequent finalize()."""
        return self._combine_gathered(
            self.gather(slots), slots, seg_ids, n_segments
        )

    def combine_for_segments_and_free(
        self, slots: np.ndarray, seg_ids: np.ndarray, n_segments: int,
        free_n: int = 0,
    ) -> List[np.ndarray]:
        """combine_for_segments, additionally freeing the device state of
        the FIRST free_n slots — the sliding merge frees the bin exiting
        the window in the same wave it last reads it, so the union is
        ordered freed-bin-first. The mesh accumulator overrides this with
        ONE fused gather+reset dispatch; here the reset is a second pass."""
        combined = self.combine_for_segments(slots, seg_ids, n_segments)
        if free_n:
            self.reset_slots(np.asarray(slots)[:free_n])
        return combined

    def _combine_gathered(
        self, gathered: List[np.ndarray], slots: np.ndarray,
        seg_ids: np.ndarray, n_segments: int,
    ) -> List[np.ndarray]:
        combined = []
        for (op, dt, _, _), vals in zip(self.phys, gathered):
            outv = np.full(n_segments, self._neutral(op, dt), dtype=self._dt(dt))
            if op == "add":
                np.add.at(outv, seg_ids, vals)
            elif op == "min":
                np.minimum.at(outv, seg_ids, vals)
            else:
                np.maximum.at(outv, seg_ids, vals)
            combined.append(outv)
        if self.udaf_idx:
            seg_map: Dict[int, list] = {}
            for si in self.udaf_idx:
                store = self.udaf_store[si]
                empty = (
                    np.empty((0, 2))
                    if self.specs[si].col2 is not None else np.empty(0)
                )
                groups = [[] for _ in range(n_segments)]
                for s, seg in zip(slots, seg_ids):
                    groups[int(seg)].extend(store.get(int(s), []))
                seg_map[si] = [
                    np.concatenate(g) if g else empty for g in groups
                ]
            self._segment_udaf = seg_map
        if self.multiset_idx:
            mseg: Dict[int, list] = {}
            for si in self.multiset_idx:
                store = self.multiset_store[si]
                dicts: List[dict] = [{} for _ in range(n_segments)]
                for s, seg in zip(slots, seg_ids):
                    d = dicts[int(seg)]
                    for v, c in store.get(int(s), {}).items():
                        d[v] = d.get(v, 0) + c
                mseg[si] = dicts
            self._segment_multiset = mseg
        return combined

    def merge_slot_into(self, dst: int, src: int):
        """Fold slot src into dst (session merges): device phys via
        gather/restore is handled by the caller; host state moves here."""
        for si in self.udaf_idx:
            store = self.udaf_store[si]
            if src in store:
                store.setdefault(dst, []).extend(store.pop(src))
        for si in self.multiset_idx:
            store = self.multiset_store[si]
            if src in store:
                d = store.setdefault(dst, {})
                for v, c in store.pop(src).items():
                    d[v] = d.get(v, 0) + c

    # -- checkpoint ---------------------------------------------------------

    def snapshot(self, slots: np.ndarray,
                 materialize: bool = True) -> List[np.ndarray]:
        """Device->host copy of live slots for checkpointing; host state
        rides along as one list-valued column per host-state spec (value
        chunks for buffers, [value, count] pairs for multisets), ordered
        buffers-then-multisets by spec index."""
        out = self.gather(slots, materialize=materialize)
        for si in self.udaf_idx:
            store = self.udaf_store[si]
            out.append(np.asarray(
                [np.concatenate(store.get(int(s), [np.empty(0)])).tolist()
                 for s in slots],
                dtype=object,
            ))
        for si in self.multiset_idx:
            store = self.multiset_store[si]
            out.append(np.asarray(
                [[[v, c] for v, c in store.get(int(s), {}).items()]
                 for s in slots],
                dtype=object,
            ))
        return out

    def _restore_udaf_cols(
        self, slots: np.ndarray, values: List[np.ndarray]
    ) -> List[np.ndarray]:
        """Consume trailing host-state columns; returns the physical
        accumulator columns."""
        if not self.host_kinds:
            return values
        n_phys = len(self.phys)
        host_cols = values[n_phys:]
        values = values[:n_phys]
        n_buf = len(self.udaf_idx)
        for si, col in zip(self.udaf_idx, host_cols[:n_buf]):
            store = self.udaf_store[si]
            for s, vals in zip(slots, col):
                arr = np.asarray(list(vals))
                if len(arr):
                    store.setdefault(int(s), []).append(arr)
        for si, col in zip(self.multiset_idx, host_cols[n_buf:]):
            store = self.multiset_store[si]
            for s, pairs in zip(slots, col):
                if len(pairs):
                    d = store.setdefault(int(s), {})
                    for v, c in pairs:
                        # msgpack round-trips tuple keys (two-argument
                        # multisets) as lists; re-hash as tuples
                        k = tuple(v) if isinstance(v, list) else v
                        d[k] = d.get(k, 0) + int(c)
        return values

    def restore(self, slots: np.ndarray, values: List[np.ndarray]):
        """Write physical accumulator values back into `slots` (the tail
        columns are host-state buffers when such specs exist)."""
        values = self._restore_udaf_cols(slots, values)
        if len(slots) == 0 or not self.phys:
            return
        if self.backend == "numpy":
            for s, v in zip(self.state, values):
                s[slots] = v
            return
        jnp = _get_jax().numpy
        self.state = [
            s.at[jnp.asarray(slots)].set(jnp.asarray(v))
            for s, v in zip(self.state, values)
        ]

    def block_until_ready(self):
        if self.backend != "numpy":
            for s in self.state:
                s.block_until_ready()


def float_state_stays_on_host(specs: List[AggSpec]) -> bool:
    """True when `specs` keep float64 physical accumulators (float
    sum/min/max, avg, the variance and regression families) and the
    device tier's float64 is not the host's (a TPU): their results must
    equal the host tier's, so the whole accumulator runs on numpy
    there. Integer aggregates — counts, integer sums, min/max — are
    exact on the device and stay on it."""
    from . import _jax

    return (
        any(dt == "f8" for s in specs for _, dt, _ in s.phys())
        and _jax.device_tier_active() and not _jax.float64_is_ieee()
    )


def make_accumulator(specs: List[AggSpec], capacity: Optional[int] = None,
                     backend: Optional[str] = None) -> Accumulator:
    if backend is None:
        from ._jax import device_tier_active

        backend = "jax" if (
            device_tier_active() and not float_state_stays_on_host(specs)
        ) else "numpy"
    if capacity is None:
        capacity = int(config().tpu.initial_capacity)
    return Accumulator(specs, capacity, backend)
