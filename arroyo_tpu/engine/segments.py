"""Fused segment runtime: whole-chain compilation + device pipelining.

ROADMAP item 1's dispatch-floor attack (GSPMD's lesson — hand the
compiler BIGGER programs; Weld/HyPer's lesson — one compiled kernel per
stateless chain, not one dispatch per operator):

* **Plan-time segment fusion** (`SegmentFusionPass`, applied right after
  the ChainingOptimizer): maximal contiguous runs of >= 2 stateless
  value operators inside a chained node (filter -> project ->
  expression-eval, the ARROW_VALUE/PROJECTION/ARROW_KEY ops the planner
  emits) are replaced by ONE `FUSED_SEGMENT` chained op carrying the
  member configs. The runner then makes one dispatch per segment per
  batch instead of one per operator. With `engine.segment_fusion` off
  the pass instead annotates the members (`segment_member` /
  `segment_lead`) so the unfused A/B run counts the dispatches it pays
  into the same `arroyo_segment_*` families.

* **One composed program, three execution tiers**
  (`FusedSegmentOperator` + `build_program`): the whole chain's output
  expressions compose into ONE function over the segment's input
  leaves (numeric columns + host-evaluated struct/string reads, via
  the `BoundExpr.jax` mirrors in sql/expressions.py). On plain hosts
  it runs as the numpy *vector* tier — leaves viewed ZERO-COPY out of
  the arrow buffers (no per-stage wide-struct filter), the combined
  row mask applied once to the narrow outputs, output nulls
  reconstructed from leaf validity for strictly null-propagating
  subtrees — engaged only when bit-exact vs the arrow kernels
  (`JaxExpr.exact`). The lazy-*view* tier (composition through
  `_ProjectedView`/`_LazyFilteredBatch`, kernel-for-kernel identical
  to the unfused plan) runs opaque `py_fn` members and any batch the
  composer rejects. Under `ops._jax.device_tier_active` the SAME
  composed function is jitted into one XLA program per shape
  signature: leaves padded on a shared pow-2 `_StickyRung` ladder (a
  rung change recompiles the segment once, not N times), dispatched
  through `InstrumentedJit` (compile/dispatch telemetry +
  `arroyo_segment_dispatch_seconds`), with buffer donation on the
  steady-state program (`engine.segment_donation`). Chaos drills
  pin fused-vs-unfused byte identity across all tiers.

* **Async double-buffered pipelining**: jax-tier dispatches stage
  UN-materialized in a bounded FIFO (up to `engine.pipeline_depth - 1`
  deep), so the host Arrow decode/pack of batch k+1 overlaps the
  in-flight device dispatch of batch k; host-tier results emit eagerly
  (there is nothing in flight to overlap, and forced staging measured
  ~2% pure overhead on the 1-core bench host). Emission is strictly
  ordered; watermarks arriving while batches are staged are queued IN
  the FIFO (held, then re-injected after the batches they followed —
  the async_udf held-watermark pattern); checkpoint barriers drain the
  pipeline before capture (`SubtaskRunner._drain_pipeline`, span
  `runner.pipeline_drain`), so outputs and checkpoint state are
  byte-identical at any depth.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..config import config
from ..graph.logical import ChainedOp, LogicalGraph, OperatorName
from ..metrics import (
    SEGMENT_BATCHES,
    SEGMENT_DISPATCH_SECONDS,
    SEGMENT_DISPATCHES,
    SEGMENT_FUSED_OPS,
)
from ..utils.logging import get_logger
from .construct import register_operator
from ..operators.base import Operator

logger = get_logger("segments")

# operator kinds whose registered implementations are stateless value
# transforms (lint JAX004 `segment-purity` keeps the registered classes
# honest: no state, no checkpoint hooks — so fusing them can never skip
# a barrier's state capture)
FUSABLE_OPS = (
    OperatorName.ARROW_VALUE,
    OperatorName.PROJECTION,
    OperatorName.ARROW_KEY,
)


def fusable(op: ChainedOp) -> bool:
    return op.operator in FUSABLE_OPS


def plan_runs(chain: List[ChainedOp]) -> List[Tuple[int, int]]:
    """Maximal contiguous [start, end) runs of >= 2 fusable ops."""
    runs: List[Tuple[int, int]] = []
    i = 0
    while i < len(chain):
        if not fusable(chain[i]):
            i += 1
            continue
        j = i
        while j < len(chain) and fusable(chain[j]):
            j += 1
        if j - i >= 2:
            runs.append((i, j))
        i = j
    return runs


class SegmentFusionPass:
    """Rewrite each node's chain: fuse runs (segment_fusion on) or
    annotate them for A/B dispatch accounting (segment_fusion off)."""

    def __init__(self, fuse: Optional[bool] = None):
        self.fuse = (
            bool(config().engine.segment_fusion) if fuse is None else fuse
        )

    def optimize(self, graph: LogicalGraph) -> LogicalGraph:
        for node in graph.nodes.values():
            runs = plan_runs(node.chain)
            if not runs:
                continue
            if not self.fuse:
                for start, end in runs:
                    for k in range(start, end):
                        node.chain[k].config["segment_member"] = True
                    node.chain[start].config["segment_lead"] = True
                continue
            # rewrite back-to-front so earlier run indices stay valid
            for start, end in reversed(runs):
                members = node.chain[start:end]
                descs = [m.description or m.operator.value for m in members]
                seg = ChainedOp(
                    OperatorName.FUSED_SEGMENT,
                    {
                        "ops": [
                            {
                                "operator": m.operator.value,
                                "config": m.config,
                                "description": m.description,
                            }
                            for m in members
                        ],
                        # segment output schema = last member's
                        "schema": members[-1].config.get("schema"),
                    },
                    "segment[" + " -> ".join(descs) + "]",
                )
                node.chain[start:end] = [seg]
        return graph


# ---------------------------------------------------------------------------
# Host-tier composition: lazy views over the member projections
# ---------------------------------------------------------------------------


class _ProjectedView:
    """Duck-typed RecordBatch whose columns are a projection's output
    expressions over a base relation, computed (and cast to the output
    field type, mirroring CompiledProjection.__call__) on first access."""

    __slots__ = ("_exprs", "_base", "_cols", "num_rows", "schema")

    def __init__(self, proj, base):
        self._exprs = proj.exprs
        self._base = base
        self._cols: Dict[int, Any] = {}
        self.num_rows = base.num_rows
        self.schema = proj.out_schema

    def column(self, i: int):
        c = self._cols.get(i)
        if c is None:
            from ..sql.expressions import _cast

            c = self._exprs[i].eval(self._base)
            f = self.schema.field(i)
            if not c.type.equals(f.type):
                c = _cast(c, f.type)
            self._cols[i] = c
        return c

    def __getattr__(self, name):
        raise AttributeError(
            f"_ProjectedView (the fused-segment lazy projection view) "
            f"exposes only column()/num_rows/schema, not {name!r}; "
            f"materialize the stage in FusedSegmentOperator instead"
        )


def _materialize(cur) -> pa.RecordBatch:
    if isinstance(cur, pa.RecordBatch):
        return cur
    return pa.RecordBatch.from_arrays(
        [cur.column(i) for i in range(len(cur.schema))], schema=cur.schema
    )


@dataclasses.dataclass
class _Stage:
    kind: str  # "proj" | "opaque" | "identity"
    proj: Any = None            # CompiledProjection
    fn: Optional[Callable] = None  # opaque py_fn
    name: str = ""


def _build_stage(member: dict) -> _Stage:
    from ..sql.expressions import CompiledProjection

    cfg = member.get("config", {})
    name = member.get("description") or member.get("operator", "")
    py_fn = cfg.get("py_fn")
    if isinstance(py_fn, CompiledProjection):
        return _Stage("proj", proj=py_fn, name=name)
    if py_fn is None and "program" in cfg:
        return _Stage("proj", proj=CompiledProjection.from_config(
            cfg["program"]), name=name)
    if py_fn is not None:
        return _Stage("opaque", fn=py_fn, name=name)
    # identity key op (routing handled by edge schema key indices)
    return _Stage("identity", name=name)


# ---------------------------------------------------------------------------
# JAX tier: the whole chain as ONE jitted program
# ---------------------------------------------------------------------------


class _StageEnv:
    """Env for stage k > 0 expressions: col(j) resolves the PREVIOUS
    stage's output expression j (memoized per program invocation, so a
    shared subexpression traces once)."""

    __slots__ = ("_col_fns", "_parent", "_memo")

    def __init__(self, col_fns, parent):
        self._col_fns = col_fns
        self._parent = parent
        self._memo: Dict[int, Any] = {}

    def col(self, j):
        v = self._memo.get(j)
        if v is None:
            v = self._memo[j] = self._col_fns[j](self._parent)
        return v

    def host(self, key):
        return self._parent.host(key)


class _BaseEnv:
    __slots__ = ("_cols", "_hosts")

    def __init__(self, cols: Dict[int, Any], hosts: Dict[int, Any]):
        self._cols = cols
        self._hosts = hosts

    def col(self, j):
        return self._cols[j]

    def host(self, key):
        return self._hosts[key]


@dataclasses.dataclass
class _SegmentProgram:
    """The composed whole-segment program + its input plan. `raw_fn` is
    tier-polymorphic: handed numpy leaf arrays it IS the host vector
    tier (filter-late: leaves read unfiltered/zero-copy, one mask
    application on the narrow outputs); handed jax arrays under jit it
    is the device tier's traced body."""

    raw_fn: Callable              # prog(*leaf_arrays) -> (mask|None, outs)
    spec: List[tuple]             # ordered leaves: ("col", j) | ("host", key, BoundExpr)
    out_fields: List[pa.Field]    # output schema fields
    out_schema: pa.Schema
    out_deps: List[frozenset]     # per output: leaf keys it depends on
    mask_deps: Optional[frozenset]  # leaf keys the row mask depends on
    strict: List[bool]            # per output: strict null propagation
    mask_strict: bool
    exact: bool                   # bit-exact vs host kernels (vector tier gate)
    # device-tier state, built lazily on first jax dispatch
    jit: Any = None               # InstrumentedJit over jax.jit(raw_fn)
    rung: Any = None              # shared _StickyRung
    n_rows_cap: int = 1 << 30
    float64_checked: bool = False  # see _float64_off_device


_FIXED_NP = {
    pa.lib.Type_INT8: "int8", pa.lib.Type_INT16: "int16",
    pa.lib.Type_INT32: "int32", pa.lib.Type_INT64: "int64",
    pa.lib.Type_UINT8: "uint8", pa.lib.Type_UINT16: "uint16",
    pa.lib.Type_UINT32: "uint32", pa.lib.Type_UINT64: "uint64",
    pa.lib.Type_FLOAT: "float32", pa.lib.Type_DOUBLE: "float64",
    pa.lib.Type_TIMESTAMP: "int64", pa.lib.Type_DURATION: "int64",
}


def _leaf_np(arr: pa.Array) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Arrow column -> (dense numpy values, validity-or-None), ZERO-copy
    for fixed-width types: the values buffer is viewed directly (null
    slots carry whatever bytes arrow left there — the validity mask is
    what gives them meaning downstream, exactly like arrow kernels
    treat them). Bit-packed bools fall back to an unpacking copy."""
    valid = None
    if arr.null_count:
        valid = arr.is_valid().to_numpy(zero_copy_only=False)
    np_dtype = _FIXED_NP.get(arr.type.id)
    if np_dtype is not None:
        buf = arr.buffers()[1]
        np_arr = np.frombuffer(buf, dtype=np_dtype,
                               count=arr.offset + len(arr))[arr.offset:]
        return np_arr, valid
    if arr.null_count:
        arr = pc.fill_null(arr, False if pa.types.is_boolean(arr.type)
                           else 0)
    np_arr = arr.to_numpy(zero_copy_only=False)
    if np_arr.dtype.kind in ("M", "m"):  # datetime64/timedelta64 -> int64
        np_arr = np_arr.view("int64")
    return np.ascontiguousarray(np_arr), valid


def _pad(arr: np.ndarray, rung: int) -> np.ndarray:
    if len(arr) == rung:
        return arr
    out = np.zeros(rung, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def build_program(stages: List[_Stage], program_name: str):
    """Compose every stage's output expressions into ONE function over
    the segment's input leaves; None when any stage blocks composition
    (opaque py_fn, non-lowerable output, host leaf past stage 0, or a
    chain with no actual compute)."""
    from ..sql.expressions import jax_lowerable_type

    projs = [s for s in stages if s.kind != "identity"]
    if any(s.kind != "proj" for s in projs):
        return None
    col_leaves: set = set()
    host_leaves: Dict[int, Any] = {}  # id(BoundExpr) -> BoundExpr
    masks: List[Tuple[Callable, frozenset, bool, bool]] = []
    prev_cols: Optional[List[Callable]] = None
    prev_deps: Optional[List[frozenset]] = None
    prev_strict: Optional[List[bool]] = None
    any_compute = False

    def compose(e, k):
        """-> (fn(env0), leaf-dep keys, strict, exact, is_leaf) or None."""
        nonlocal any_compute
        jx = getattr(e, "jax", None)
        if jx is None:
            return None
        if k == 0:
            for h in jx.hosts:
                host_leaves.setdefault(id(h), h)
            col_leaves.update(jx.cols)
            deps = frozenset(
                [("col", j) for j in jx.cols]
                + [("host", id(h)) for h in jx.hosts]
            )
            if not jx.leaf:
                any_compute = True
            return jx.fn, deps, jx.strict, jx.exact, jx.leaf
        if jx.hosts:
            return None  # host leaf past stage 0: needs materialization
        deps = frozenset()
        strict = jx.strict
        for j in jx.cols:
            deps |= prev_deps[j]
            strict = strict and prev_strict[j]
        if not jx.leaf:
            any_compute = True
        pcols = prev_cols
        return (
            lambda env, f=jx.fn, _pc=pcols: f(_StageEnv(_pc, env)),
            deps, strict, jx.exact, jx.leaf,
        )

    k = 0
    last_proj = None
    exact = True
    for st in projs:
        proj = st.proj
        if proj.predicate is not None:
            m = compose(proj.predicate, k)
            if m is None:
                return None
            masks.append((m[0], m[1], m[2], m[3]))
            exact = exact and m[3]
        new_cols, new_deps, new_strict = [], [], []
        for e, f in zip(proj.exprs, proj.out_schema):
            if not jax_lowerable_type(f.type):
                return None
            c = compose(e, k)
            if c is None:
                return None
            fn, deps, strict, e_exact, _leaf = c
            exact = exact and e_exact
            # mirror the host cast-to-out-field-type step
            if not e.dtype.equals(f.type):
                from ..sql.expressions import JaxExpr, _jx_cast

                fn = _jx_cast(JaxExpr(fn), f.type).fn
            new_cols.append(fn)
            new_deps.append(deps)
            new_strict.append(strict)
        prev_cols, prev_deps, prev_strict = new_cols, new_deps, new_strict
        last_proj = proj
        k += 1
    if last_proj is None or not any_compute:
        return None

    spec: List[tuple] = [("col", j) for j in sorted(col_leaves)] + [
        ("host", key, be) for key, be in host_leaves.items()
    ]
    leaf_keys = [s[:2] for s in spec]
    outputs = prev_cols

    def prog(*arrays):
        env = _BaseEnv(
            {key[1]: a for key, a in zip(leaf_keys, arrays)
             if key[0] == "col"},
            {key[1]: a for key, a in zip(leaf_keys, arrays)
             if key[0] == "host"},
        )
        mask = None
        for mfn, _deps, _strict, _exact in masks:
            m = mfn(env)
            mask = m if mask is None else mask & m
        outs = tuple(fn(env) for fn in outputs)
        return mask, outs

    mask_deps = None
    mask_strict = True
    if masks:
        mask_deps = frozenset().union(*(m[1] for m in masks))
        mask_strict = all(m[2] for m in masks)
    return _SegmentProgram(
        raw_fn=prog,
        spec=spec,
        out_fields=list(last_proj.out_schema),
        out_schema=pa.schema(list(last_proj.out_schema)),
        out_deps=prev_deps,
        mask_deps=mask_deps,
        strict=prev_strict,
        mask_strict=mask_strict,
        exact=exact,
    )


def attach_device_program(prog: _SegmentProgram, program_name: str) -> None:
    """Build the jitted device form of a composed segment program: jax
    jit with input donation per engine.segment_donation, an
    InstrumentedJit wrapper feeding the compile/dispatch + segment
    telemetry, and the shared sticky padding rung."""
    from ..obs import device as obs_device
    from ..ops._jax import accelerator_present, get_jax
    from ..parallel.sharded_state import _StickyRung

    jax = get_jax()
    donate_cfg = str(config().engine.segment_donation).lower()
    donate: tuple = ()
    if donate_cfg == "on" or (donate_cfg == "auto" and accelerator_present()):
        donate = tuple(range(len(prog.spec)))
    jfn = jax.jit(prog.raw_fn, donate_argnums=donate)
    # power-of-two ladder up to the coarse shape_buckets ceiling: engine
    # batches are pow2-sized (pipeline.source_batch_size), so the sticky
    # rung locks exactly onto the steady batch size instead of fighting
    # the 4x aggregate ladder's decay at half-rung
    cap = int(max(config().tpu.shape_buckets))
    ladder = tuple(
        1 << p for p in range(8, cap.bit_length())
        if (1 << p) <= cap
    ) or (cap,)
    prog.jit = obs_device.InstrumentedJit(program_name, jfn, segment=True)
    prog.rung = _StickyRung(ladder)
    prog.n_rows_cap = ladder[-1]


# ---------------------------------------------------------------------------
# Staged (pipelined) results
# ---------------------------------------------------------------------------


class _StagedBatch:
    """A host-tier result: already materialized, emission just deferred."""

    __slots__ = ("batch",)

    def __init__(self, batch: Optional[pa.RecordBatch]):
        self.batch = batch

    def materialize(self) -> Optional[pa.RecordBatch]:
        return self.batch


def _valid_of(validities: Dict[tuple, np.ndarray],
              deps: Optional[frozenset]) -> Optional[np.ndarray]:
    """AND of the validity masks of the leaves in `deps` (strict null
    propagation: an output row is null iff any contributing leaf was)."""
    if not deps or not validities:
        return None
    vs = [v for key, v in validities.items() if key in deps]
    if not vs:
        return None
    out = vs[0]
    for v in vs[1:]:
        out = out & v
    return out


def _as_rows(vals, n: int) -> np.ndarray:
    """Program outputs may be 0-d (a literal column): broadcast to n."""
    arr = np.asarray(vals)
    if arr.ndim == 0:
        arr = np.full(n, arr[()])
    return arr[:n]


def _materialize_result(prog: _SegmentProgram, n: int, mask_vals,
                        out_vals,
                        validities: Dict[tuple, np.ndarray],
                        ) -> Optional[pa.RecordBatch]:
    """numpy mask/outputs (+ leaf validities) -> the output RecordBatch,
    applying the row filter ONCE to the narrow output columns and
    reconstructing output nulls from strict leaf validity. Shared by the
    vector (host numpy) and jax (device) tiers."""
    keep = None
    if mask_vals is not None:
        keep = _as_rows(mask_vals, n)
        mv = _valid_of(validities, prog.mask_deps)
        if mv is not None:
            keep = keep & mv
        if not keep.any():
            return None
        if keep.all():
            keep = None
    arrays = []
    for i, (vals, field) in enumerate(zip(out_vals, prog.out_fields)):
        vals = _as_rows(vals, n)
        valid = _valid_of(validities, prog.out_deps[i])
        if keep is not None:
            vals = vals[keep]
            valid = valid[keep] if valid is not None else None
        arrays.append(_wrap_out(vals, valid, field.type))
    return pa.RecordBatch.from_arrays(arrays, schema=prog.out_schema)


def _wrap_out(vals: np.ndarray, valid: Optional[np.ndarray],
              t: pa.DataType) -> pa.Array:
    """numpy output column -> arrow array; zero-copy for all-valid
    fixed-width columns (the common case — pa.array() would copy)."""
    np_dtype = _FIXED_NP.get(t.id)
    if valid is None and np_dtype is not None \
            and vals.dtype == np.dtype(np_dtype) \
            and vals.flags["C_CONTIGUOUS"]:
        return pa.Array.from_buffers(
            t, len(vals), [None, pa.py_buffer(vals)]
        )
    if pa.types.is_timestamp(t):
        vals = vals.astype("int64", copy=False).view("datetime64[ns]")
    elif pa.types.is_duration(t):
        vals = vals.astype("int64", copy=False).view("timedelta64[ns]")
    arr = pa.array(vals, mask=None if valid is None else ~valid)
    if not arr.type.equals(t):
        arr = arr.cast(t)
    return arr


class _StagedDispatch:
    """A jax-tier result: the dispatch is in flight on the device; the
    host materializes (sync + arrow rebuild) only at emission time —
    which is how batch k's device time overlaps batch k+1's host pack."""

    __slots__ = ("prog", "rows", "mask_dev", "outs_dev", "validities")

    def __init__(self, prog: _SegmentProgram, rows: int, mask_dev, outs_dev,
                 validities: Dict[tuple, np.ndarray]):
        self.prog = prog
        self.rows = rows
        self.mask_dev = mask_dev
        self.outs_dev = outs_dev
        self.validities = validities

    def materialize(self) -> Optional[pa.RecordBatch]:
        mask = (
            np.asarray(self.mask_dev) if self.mask_dev is not None else None
        )
        outs = [np.asarray(o) for o in self.outs_dev]
        return _materialize_result(self.prog, self.rows, mask, outs,
                                   self.validities)


class _HeldWatermark:
    __slots__ = ("wm",)

    def __init__(self, wm):
        self.wm = wm


# ---------------------------------------------------------------------------
# The runtime operator
# ---------------------------------------------------------------------------


class FusedSegmentOperator(Operator):
    """One dispatch per batch for a whole stateless run, plus the
    double-buffered staging queue. Stateless by construction: no tables,
    no checkpoint capture — its only barrier obligation is draining the
    staged FIFO, which the runner does before capture."""

    is_fused_segment = True

    def __init__(self, members: List[dict], out_schema=None, name: str = ""):
        super().__init__(name or "segment")
        self.members = members
        self.out_schema = out_schema
        self._stages = [_build_stage(m) for m in members]
        short = "+".join(
            (s.name or s.kind)[:16] for s in self._stages
        ) or "identity"
        self.program_name = f"segment.{len(self._stages)}x.{short}"
        self._staged: deque = deque()
        self._depth = max(1, int(config().engine.pipeline_depth))
        self._prog: Any = False   # False = not yet built; None = view tier
        self._use_jax = False
        self._host_drops: set = set()
        self._vector_broken = False
        self._host_h = SEGMENT_DISPATCH_SECONDS.labels(
            program=self.program_name, tier="host")
        SEGMENT_FUSED_OPS.labels(program=self.program_name).set(
            len(self._stages))
        self._counters = None

    # -- accounting --------------------------------------------------------

    def _count(self, ctx):
        c = self._counters
        if c is None:
            ti = ctx.task_info
            c = self._counters = (
                SEGMENT_BATCHES.labels(job=ti.job_id, task=ti.task_id),
                SEGMENT_DISPATCHES.labels(job=ti.job_id, task=ti.task_id,
                                          fused="1"),
            )
        c[0].inc()
        c[1].inc()

    # -- program selection -------------------------------------------------

    def _program(self) -> Optional[_SegmentProgram]:
        """The composed whole-chain program, built once: the numpy
        VECTOR tier runs it directly (filter-late, one mask pass on the
        narrow outputs); when the device tier is active it is jitted
        into ONE XLA program. None = not composable (opaque py_fn member
        etc.) -> the lazy-view host path. A composition or lowering
        error propagates: dropping a tier silently would hide the device
        from whoever reads the result."""
        if self._prog is False:
            self._prog = build_program(self._stages, self.program_name)
            from ..ops import _jax

            self._use_jax = (self._prog is not None
                             and _jax.device_tier_active())
            if self._use_jax:
                attach_device_program(self._prog, self.program_name)
            logger.info(
                "segment %s: tier=%s platform=%s (%d ops%s)",
                self.program_name,
                "jax" if self._use_jax else
                "vector" if self._prog is not None and self._prog.exact
                else "view",
                _jax.platform(), len(self._stages),
                "" if self._prog is not None else ", not composable",
            )
        return self._prog

    # -- execution ---------------------------------------------------------

    def _float64_off_device(self, prog: _SegmentProgram, arrays) -> bool:
        """Before the first dispatch: a program that reads, computes or
        produces float64 leaves the jax tier for good where the device's
        float64 is not the host's (ops/_jax.float64_is_ieee). Decided
        from an abstract trace of the program over the first batch's
        leaf dtypes; nothing runs."""
        from ..ops import _jax

        prog.float64_checked = True
        if _jax.float64_is_ieee():
            return False
        jax = _jax.get_jax()
        traced = jax.make_jaxpr(prog.raw_fn)(
            *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays])
        if "f64[" not in str(traced):
            return False
        self._use_jax = False
        logger.warning(
            "segment %s: tier=%s, not jax: the program uses float64 and "
            "platform=%s has no IEEE float64", self.program_name,
            "vector" if prog.exact else "view", _jax.platform(),
        )
        return True

    def _note_host_drop(self, reason: str) -> None:
        """A batch the jax tier hands to the host tiers. Each one is
        counted in arroyo_segment_dispatch_seconds{tier="host"} (so the
        host share of a jax-tier segment is readable beside its jax
        dispatches); the reason is logged once."""
        if reason not in self._host_drops:
            self._host_drops.add(reason)
            logger.warning(
                "segment %s: jax tier hands batches to the host tier: %s",
                self.program_name, reason,
            )

    def _run_host(self, batch: pa.RecordBatch) -> Optional[pa.RecordBatch]:
        views = []  # the stages' filtered views, booked once they are read

        def materialize(cur) -> pa.RecordBatch:
            out = _materialize(cur)
            for v in views:
                v.book()
            views.clear()
            return out

        cur = batch
        for st in self._stages:
            if st.kind == "identity":
                continue
            if st.kind == "opaque":
                cur = st.fn(materialize(cur))
                if cur is None or cur.num_rows == 0:
                    return None
                continue
            rows = st.proj.filtered(cur)
            if rows is None:
                return None
            if rows is not cur:
                views.append(rows)
            cur = _ProjectedView(st.proj, rows)
        out = materialize(cur)
        return out if out.num_rows else None

    def _pack_leaves(self, batch: pa.RecordBatch, prog: _SegmentProgram):
        """Host decode/pack: evaluate + densify the program's input
        leaves. Returns (arrays, validities) or None when a leaf null
        would reach a non-strict subtree (kleene and/or) — those nulls
        cannot be reconstructed from leaf validity, so the batch takes
        the lazy-view path instead."""
        arrays: List[np.ndarray] = []
        validities: Dict[tuple, np.ndarray] = {}
        for leaf in prog.spec:
            if leaf[0] == "col":
                col = batch.column(leaf[1])
            else:
                col = leaf[2].eval(batch)
            vals, valid = _leaf_np(col)
            if valid is not None:
                key = leaf[:2]
                if not prog.mask_strict and prog.mask_deps \
                        and key in prog.mask_deps:
                    return None
                if any(
                    key in deps and not strict
                    for deps, strict in zip(prog.out_deps, prog.strict)
                ):
                    return None
                validities[key] = valid
            arrays.append(vals)
        return arrays, validities

    def _dispatch_jax(self, batch: pa.RecordBatch, prog: _SegmentProgram):
        """Pack leaves, pad to the shared sticky rung, dispatch the
        jitted program. Returns a _StagedDispatch (un-materialized: the
        device crunches while the host packs the next batch), or None to
        fall back (nulls in a non-strict subtree, oversized batch)."""
        n = batch.num_rows
        if n > prog.n_rows_cap:
            self._note_host_drop(
                f"batch of {n} rows exceeds the {prog.n_rows_cap}-row rung")
            return None
        packed = self._pack_leaves(batch, prog)
        if packed is None:
            self._note_host_drop("nulls reach a non-strict subtree")
            return None
        arrays, validities = packed
        if not prog.float64_checked and self._float64_off_device(
                prog, arrays):
            return None
        rung = prog.rung.fit(n)
        if rung < n:  # a just-decayed rung can undershoot; re-climb
            rung = prog.rung.fit(n)
        padded = [_pad(a, rung) for a in arrays]
        # validities stay host-side (numpy, unpadded): they only gate
        # output nulls/filtering at materialization time
        mask_dev, outs_dev = prog.jit(*padded, rung=rung)
        return _StagedDispatch(prog, n, mask_dev, outs_dev, validities)

    def _run_vector(self, batch: pa.RecordBatch, prog: _SegmentProgram):
        """Host vector tier: the composed program over numpy leaf
        arrays. Filter-late beats the per-stage lazy filter because the
        leaves are read zero-copy UNfiltered (no wide-struct filter
        kernel) and the single mask application touches only the narrow
        output columns. Returns the output batch, None (all filtered),
        or the batch itself as a fallback sentinel."""
        packed = self._pack_leaves(batch, prog)
        if packed is None:
            return batch  # sentinel: caller takes the view path
        arrays, validities = packed
        mask_vals, out_vals = prog.raw_fn(*arrays)
        return _materialize_result(prog, batch.num_rows, mask_vals,
                                   out_vals, validities)

    def _execute(self, batch: pa.RecordBatch):
        from .. import obs

        t0 = time.perf_counter()
        c0 = obs.timeline.thread_cpu(t0)
        prog = self._program()
        staged = None
        if prog is not None and self._use_jax:
            staged = self._dispatch_jax(batch, prog)
        if staged is None:
            out = batch  # fallback sentinel
            if prog is not None and prog.exact and not self._vector_broken:
                try:
                    out = self._run_vector(batch, prog)
                except Exception:
                    # never fatal: the lazy-view path computes the same
                    # values through the arrow kernels
                    logger.exception(
                        "segment %s: vector tier failed; view tier",
                        self.program_name,
                    )
                    self._vector_broken = True
                    out = batch
            if out is batch:
                out = self._run_host(batch)
            staged = _StagedBatch(out) if out is not None else None
            self._host_h.observe(time.perf_counter() - t0)
        t1 = time.perf_counter()
        obs.timeline.note("segment", t1 - t0,
                          cpu_s=obs.timeline.thread_cpu(t1) - c0)
        return staged

    # -- staging / pipelining ----------------------------------------------

    @property
    def staged_depth(self) -> int:
        return sum(
            1 for e in self._staged if not isinstance(e, _HeldWatermark)
        )

    async def _emit_head(self, ctx, collector):
        entry = self._staged.popleft()
        if isinstance(entry, _HeldWatermark):
            await self._release_watermark(ctx, entry.wm)
            return
        out = entry.materialize()
        if out is not None and out.num_rows:
            await collector.collect(out)

    async def _release_watermark(self, ctx, wm):
        runner = getattr(ctx, "_runner", None)
        if runner is None:
            return
        idx = runner.ops.index(self)
        await runner._chain_watermark(idx + 1, wm)

    async def _flush_to_depth(self, ctx, collector):
        # hold at most depth-1 batches; watermarks at the head flush
        # eagerly so downstream sees the exact unfused interleaving
        while self.staged_depth > self._depth - 1:
            await self._emit_head(ctx, collector)
        while self._staged and isinstance(self._staged[0], _HeldWatermark):
            await self._emit_head(ctx, collector)

    async def drain(self, ctx, collector):
        """Emit every staged entry in order (barriers, stops, close)."""
        while self._staged:
            await self._emit_head(ctx, collector)

    # -- operator hooks ----------------------------------------------------

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        self._count(ctx)
        staged = self._execute(batch)
        if staged is None:
            return
        if isinstance(staged, _StagedBatch) and not self._staged:
            # host-tier result: already materialized, nothing in flight
            # to overlap — emit straight through (the staging queue only
            # earns its latency where a device dispatch is actually
            # asynchronous)
            out = staged.batch
            if out is not None and out.num_rows:
                await collector.collect(out)
            return
        self._staged.append(staged)
        await self._flush_to_depth(ctx, collector)

    async def handle_watermark(self, watermark, ctx, collector):
        if not self._staged:
            return watermark
        # batches are in flight: queue the watermark behind them (strict
        # order), release it from the FIFO
        self._staged.append(_HeldWatermark(watermark))
        while self._staged and isinstance(self._staged[0], _HeldWatermark):
            await self._emit_head(ctx, collector)
        return None

    async def handle_checkpoint(self, barrier, ctx, collector):
        # normally a no-op: the runner drains the pipeline (with the
        # runner.pipeline_drain span) before capture; kept as a safety
        # net for direct chain invocations
        await self.drain(ctx, collector)

    async def on_close(self, ctx, collector, is_eod: bool):
        await self.drain(ctx, collector)
        return None


@register_operator(OperatorName.FUSED_SEGMENT)
def _make_segment(cfg: dict) -> Operator:
    return FusedSegmentOperator(
        cfg["ops"], cfg.get("schema"), cfg.get("name", "")
    )
