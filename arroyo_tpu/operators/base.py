"""Operator traits.

Capability parity with the reference's ArrowOperator / SourceOperator traits
(/root/reference/crates/arroyo-operator/src/operator.rs:1144-1257, :320-377):
lifecycle hooks, batch processing, watermark handling (return None to hold),
checkpoint state-snapshot hook, 2PC commit hook, periodic tick, and the
state-table declaration. Sources run their own loop and poll the control
queue between emissions (checkpoint barriers are injected at clean points).
"""

from __future__ import annotations

import enum
from typing import Any, Dict, Optional

import pyarrow as pa

from ..types import CheckpointBarrier, Watermark
from .context import OperatorContext, SourceContext


class SourceFinishType(enum.Enum):
    GRACEFUL = "graceful"  # stop requested: propagate Stop, no final watermark
    IMMEDIATE = "immediate"  # tear down without draining
    FINAL = "final"  # source exhausted: final watermark + EndOfData


class Operator:
    """Base class for dataflow operators. Subclasses override the hooks they
    need; `process_batch` is the hot path."""

    # StateServe: keyed operators get a ServeView attached at task start
    # (serve.register_op); None everywhere else keeps the emission-path
    # check a single attribute load
    _serve_view = None

    # conservation ledger (obs/audit.py): declared selectivity class,
    # checked per epoch by the reconciler against the runner's in/out row
    # counts. "exact" = out == in (pure row-wise transforms), "contracting"
    # = out <= in (filters), "buffering"/"any" = unchecked (windows,
    # joins, and anything that holds rows across barriers)
    flow_class = "any"

    def __init__(self, name: str = ""):
        self.name = name or type(self).__name__

    # -- lifecycle ----------------------------------------------------------

    async def on_start(self, ctx: OperatorContext):
        pass

    async def process_batch(
        self,
        batch: pa.RecordBatch,
        ctx: OperatorContext,
        collector: "ChainCollector",
        input_index: int = 0,
    ):
        raise NotImplementedError

    async def handle_watermark(
        self, watermark: Watermark, ctx: OperatorContext, collector
    ) -> Optional[Watermark]:
        """Called when the combined input watermark advances. Return the
        watermark to propagate (possibly modified) or None to hold it."""
        return watermark

    async def handle_checkpoint(
        self, barrier: CheckpointBarrier, ctx: OperatorContext, collector
    ):
        """Snapshot in-memory state into ctx state tables; called after
        barrier alignment, before the table flush. May return the number
        of rows it serialised: the caller's `ckpt.capture` carries it."""

    async def handle_commit(
        self, epoch: int, commit_data: Dict[int, list], ctx: OperatorContext
    ):
        """Second phase of 2PC for transactional sinks."""

    async def handle_tick(self, tick: int, ctx: OperatorContext, collector):
        pass

    def tick_interval(self) -> Optional[float]:
        return None

    def future_to_poll(self):
        """Operator-owned async work (reference operator.rs future_to_poll):
        return an awaitable the runner selects on alongside the inputs, or
        None when idle. When it resolves, the runner calls
        handle_future_result and re-queries."""
        return None

    async def handle_future_result(self, ctx: OperatorContext, collector):
        """Called when the awaitable from future_to_poll resolved."""

    async def on_close(
        self, ctx: OperatorContext, collector, is_eod: bool
    ) -> Optional[Watermark]:
        """Called when all inputs finished. May emit final data via the
        collector; a returned watermark is run through the rest of the chain
        and broadcast (the watermark generator returns the end-of-time
        watermark here so windows flush)."""
        return None

    def tables(self) -> Dict[str, Any]:
        """State tables this operator needs: name -> TableConfig."""
        return {}

    def display(self) -> str:
        return self.name


class SourceOperator(Operator):
    """Sources drive their own loop. Implementations must call
    `await ctx.check_control(collector)` regularly (between batches) and
    return when it yields a finish type."""

    async def run(self, ctx: SourceContext, collector) -> SourceFinishType:
        raise NotImplementedError

    def drain_status(self):
        """For bounded sources: (drained, detail) after a FINAL finish —
        whether the source actually emitted its whole assigned range.
        None = unbounded/unknown. The runner attaches this to
        TaskFinishedResp; the controller refuses to FINISH a job whose
        source claims completion undrained (truncated-output guard)."""
        return None

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        raise RuntimeError("sources do not process input batches")

    async def flush_buffer(self, ctx: SourceContext, collector):
        # fleet observatory: take_buffer is the arrow pack moment (row
        # dicts -> RecordBatch) — the host decode/pack cost ROADMAP item
        # 1 wants overlapped with in-flight dispatch
        from .. import obs
        import time as _time

        t0 = _time.perf_counter()
        c0 = obs.timeline.thread_cpu(t0)
        batch = ctx.take_buffer()
        if batch is not None:
            t1 = _time.perf_counter()
            obs.timeline.note("decode", t1 - t0,
                              task=ctx.task_info.task_id,
                              cpu_s=obs.timeline.thread_cpu(t1) - c0)
            await collector.collect(batch)
        # latency markers stamp at flush cadence (throttled by
        # obs.latency_marker_interval): they leave through the subtask's
        # tail so they traverse real edges, not the in-chain fast path
        marker = ctx.next_latency_marker()
        if marker is not None and ctx._runner is not None:
            from ..types import SignalMessage

            await ctx._runner.tail.forward_marker(
                SignalMessage.marker_of(marker)
            )

    async def poll_async_iter(
        self, ait, ctx, collector, on_message, idle: float = 0.05
    ) -> Optional[SourceFinishType]:
        """Shared client-poll loop for push-style sources (MQTT, RabbitMQ,
        NATS): keeps ONE in-flight `__anext__` across idle ticks — an idle
        subject must not starve control handling (checkpoint barriers,
        stops), and cancelling `__anext__` per tick (as wait_for would)
        orphans many clients' internal queue getters, which then steal
        and drop messages. `on_message(msg)` is awaited per message;
        returns a finish type from control, or None at end-of-stream."""
        import asyncio

        pending = None
        while True:
            finish = await ctx.check_control(collector)
            if finish is not None:
                if pending is not None:
                    pending.cancel()
                return finish
            if pending is None:
                pending = asyncio.ensure_future(ait.__anext__())
            done, _ = await asyncio.wait({pending}, timeout=idle)
            if not done:
                await self.flush_buffer(ctx, collector)
                continue
            task, pending = pending, None
            try:
                msg = task.result()
            except StopAsyncIteration:
                return None
            await on_message(msg)
            if ctx.should_flush():
                await self.flush_buffer(ctx, collector)
