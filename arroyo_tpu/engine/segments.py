"""Fused segment runtime: one operator per stateless run.

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

* **One composed view per segment** (`FusedSegmentOperator`): each
  member projection becomes a lazy view over the one before it
  (`_ProjectedView` over `CompiledProjection.filtered`'s
  `_LazyFilteredBatch`), so a column nobody reads is never filtered or
  computed and the last stage's columns are materialized once,
  kernel for kernel the unfused plan's. An opaque `py_fn` member
  materializes its input and runs as it would alone. The operator holds
  nothing between batches: each batch is computed and collected in
  `process_batch`. Chaos drills pin fused-vs-unfused byte identity.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import pyarrow as pa

from ..config import config
from ..graph.logical import ChainedOp, LogicalGraph, OperatorName
from ..metrics import (
    SEGMENT_BATCHES,
    SEGMENT_DISPATCH_SECONDS,
    SEGMENT_DISPATCHES,
    SEGMENT_FUSED_OPS,
)
from ..obs import timeline
from .construct import register_operator
from ..operators.base import Operator

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
# Composition: lazy views over the member projections
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
# The runtime operator
# ---------------------------------------------------------------------------


class FusedSegmentOperator(Operator):
    """One dispatch per batch for a whole stateless run. Stateless by
    construction: no tables, no checkpoint capture, and nothing held
    between batches, so a watermark, a barrier and a close pass it by."""

    def __init__(self, members: List[dict], out_schema=None, name: str = ""):
        super().__init__(name or "segment")
        self.members = members
        self.out_schema = out_schema
        self._stages = [_build_stage(m) for m in members]
        short = "+".join(
            (s.name or s.kind)[:16] for s in self._stages
        ) or "identity"
        self.program_name = f"segment.{len(self._stages)}x.{short}"
        self._dispatch_h = SEGMENT_DISPATCH_SECONDS.labels(
            program=self.program_name)
        SEGMENT_FUSED_OPS.labels(program=self.program_name).set(
            len(self._stages))
        self._counters = None

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

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        self._count(ctx)
        t0 = time.perf_counter()
        c0 = timeline.thread_cpu(t0)
        out = self._run_host(batch)
        t1 = time.perf_counter()
        self._dispatch_h.observe(t1 - t0)
        timeline.note("segment", t1 - t0,
                      cpu_s=timeline.thread_cpu(t1) - c0)
        if out is not None:
            await collector.collect(out)


@register_operator(OperatorName.FUSED_SEGMENT)
def _make_segment(cfg: dict) -> Operator:
    return FusedSegmentOperator(
        cfg["ops"], cfg.get("schema"), cfg.get("name", "")
    )
