"""Stateless value operators: map / filter / key-calculation.

Capability parity with the reference's ValueExecutionOperator /
KeyExecutionOperator / ProjectionOperator
(/root/reference/crates/arroyo-worker/src/arrow/mod.rs:245-347), which run a
compiled physical sub-plan batch-at-a-time. Here the compiled form is an
expression program from arroyo_tpu.sql.expressions (vectorized pyarrow/
numpy, or a jitted JAX path for numeric-heavy projections); `py_fn` configs
allow raw python callables for hand-built graphs and tests.
"""

from __future__ import annotations

from typing import Callable, Optional

import pyarrow as pa

from ..graph.logical import OperatorName
from ..engine.construct import register_operator
from ..obs import timeline
from .base import Operator


class BatchMapOperator(Operator):
    """Applies fn(RecordBatch) -> RecordBatch."""

    # stateless value transform: registered fusable into segment runs
    # (engine/segments.py). Lint JAX004 `segment-purity` enforces that a
    # fusable operator never touches state tables or checkpoint hooks —
    # a fused run executes with ONE dispatch and relies on having no
    # per-operator capture to skip.
    fusable = True
    # set by the value factories when engine.segment_fusion is OFF and
    # the planner marked this op as part of a would-be segment run: the
    # op then counts its per-batch dispatch (and, for the run's lead op,
    # the batch itself) into the arroyo_segment_* families, so the
    # fused/unfused A/B reads dispatches_per_batch from the same place
    segment_member = False
    segment_lead = False

    def __init__(self, fn: Callable[[pa.RecordBatch], Optional[pa.RecordBatch]],
                 name: str = "map", out_schema=None):
        super().__init__(name)
        self.fn = fn
        self.out_schema = out_schema
        self._seg_counters = None

    def _count_unfused(self, ctx):
        c = self._seg_counters
        if c is None:
            from ..metrics import SEGMENT_BATCHES, SEGMENT_DISPATCHES

            ti = ctx.task_info
            c = self._seg_counters = (
                SEGMENT_DISPATCHES.labels(job=ti.job_id, task=ti.task_id,
                                          fused="0"),
                SEGMENT_BATCHES.labels(job=ti.job_id, task=ti.task_id)
                if self.segment_lead else None,
            )
        c[0].inc()
        if c[1] is not None:
            c[1].inc()

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        if self.segment_member:
            self._count_unfused(ctx)
        # an unfused value operator's whole work (a fused run books
        # `segment`): in a source's chain the projection over the raw row
        with timeline.phase("project", n=batch.num_rows):
            out = self.fn(batch)
        if out is not None and out.num_rows:
            await collector.collect(out)


def _apply_segment_flags(op: BatchMapOperator, config: dict) -> BatchMapOperator:
    if config.get("segment_member"):
        op.segment_member = True
        op.segment_lead = bool(config.get("segment_lead"))
    return op


def _declare_flow(op: BatchMapOperator, prog) -> BatchMapOperator:
    """Conservation ledger: a compiled projection's selectivity is known
    statically — row-wise without a predicate (out == in), filtering with
    one (out <= in). py_fn operators stay "any" (arbitrary callables)."""
    op.flow_class = (
        "contracting" if getattr(prog, "predicate", None) is not None
        else "exact"
    )
    return op


@register_operator(OperatorName.ARROW_VALUE)
@register_operator(OperatorName.PROJECTION)
def _make_value(config: dict) -> Operator:
    if "py_fn" in config:
        return _apply_segment_flags(
            BatchMapOperator(config["py_fn"], config.get("name", "map"),
                             config.get("schema")), config)
    if "program" in config:
        from ..sql.expressions import CompiledProjection

        prog = CompiledProjection.from_config(config["program"])
        return _apply_segment_flags(
            _declare_flow(
                BatchMapOperator(prog, config.get("name", "project"),
                                 config.get("schema")), prog), config)
    raise ValueError("value operator config needs py_fn or program")


@register_operator(OperatorName.ARROW_KEY)
def _make_key(config: dict) -> Operator:
    """Key calculation: in this engine keys are column *indices* on the edge
    schema (no separate key column materialization needed) — an ArrowKey node
    may still compute key expressions into columns before the shuffle."""
    if "py_fn" in config:
        return _apply_segment_flags(
            BatchMapOperator(config["py_fn"], "key", config.get("schema")),
            config)
    if "program" in config:
        from ..sql.expressions import CompiledProjection

        prog = CompiledProjection.from_config(config["program"])
        return _apply_segment_flags(
            _declare_flow(
                BatchMapOperator(prog, "key", config.get("schema")), prog),
            config)
    # identity: routing handled by edge schema key indices
    op = BatchMapOperator(lambda b: b, "key", config.get("schema"))
    op.flow_class = "exact"  # identity pass-through
    return _apply_segment_flags(op, config)
