"""The subtask event loop — the engine's hot loop.

Capability parity with the reference's operator_run_behavior
(/root/reference/crates/arroyo-operator/src/operator.rs:932-1065):
a select over (a) the control queue, (b) all input queues, (c) a periodic
tick — with Chandy-Lamport checkpoint-barrier alignment (barriered inputs
are blocked until every live input delivered the epoch's barrier, then the
chain snapshots state, reports to the job controller, and re-broadcasts the
barrier downstream), per-input watermark min-merge, and operator chaining
(a fused chain executes in one task with direct calls, reference
operator.rs:406-530 ChainedCollector).

asyncio-native redesign: each subtask is one asyncio task; input queue reads
are armed as sub-tasks and re-armed selectively (a blocked input is simply
not re-armed — no polling).
"""

from __future__ import annotations

import asyncio
import time
import traceback
import weakref
from typing import Dict, List, Optional

import pyarrow as pa

from .. import chaos, obs
from ..analysis.model.effects import protocol_effect
from ..analysis.races import shared_state
from ..analysis.races.sanitizer import set_task_root
from ..config import config
from ..metrics import (
    BARRIER_ALIGNMENT_SECONDS,
    BATCH_PROCESSING_SECONDS,
    BATCHES_RECV,
    BUSY_SECONDS,
    BYTES_RECV,
    CHECKPOINT_PHASE_SECONDS,
    E2E_LATENCY_SECONDS,
    LATENCY_MARKER_SECONDS,
    MESSAGES_RECV,
    WATERMARK_LAG_SECONDS,
)
from ..types import (
    SignalKind,
    SignalMessage,
    StopMode,
    Watermark,
    WatermarkKind,
)
from ..utils.logging import get_logger
from .base import Operator, SourceFinishType, SourceOperator
from .collector import Collector
from .context import OperatorContext, SourceContext
from .control import (
    CheckpointCompletedResp,
    CheckpointEventResp,
    CheckpointMsg,
    CommitMsg,
    LoadCompactedMsg,
    StopMsg,
    TaskFailedResp,
    TaskFinishedResp,
)
from .queues import BatchQueue, InputQueue, QueueClosed, batch_bytes

logger = get_logger("runner")


class ChainCollector:
    """Collector seen by chain op `i`: routes collected batches directly into
    op i+1 (same task, no queue) or to the tail edge collector."""

    def __init__(self, runner: "SubtaskRunner", op_idx: int):
        self.runner = runner
        self.op_idx = op_idx

    async def collect(self, batch: pa.RecordBatch):
        if batch.num_rows == 0:
            return
        nxt = self.op_idx + 1
        r = self.runner
        if r._audit_on:
            # conservation ledger: per-epoch selectivity counts — rows
            # leaving op i are rows entering op i+1 (direct call, no queue)
            r._op_counts[self.op_idx][1] += batch.num_rows
            if nxt < len(r.ops):
                r._op_counts[nxt][0] += batch.num_rows
        if nxt < len(r.ops):
            await r.ops[nxt].process_batch(batch, r.ctxs[nxt], r.collectors[nxt], 0)
        else:
            await r.tail.collect(batch)


class SourceCollector(ChainCollector):
    """What a source operator collects into: a source task has no input
    item, so a batch it hands on is the item its task handles, inside the
    same `process` enclosure as an operator task's batch (`n` = rows): the
    chained operators' un-named work, the tail's and the runner's belong
    to that task in the ledger."""

    async def collect(self, batch: pa.RecordBatch):
        with obs.timeline.phase(
                "process", task=self.runner.task_info.task_id,
                n=batch.num_rows, annotate=False):
            await super().collect(batch)


# runner state is shared between the main select loop, the pipelined
# flush tasks it spawns (which set _flush_failed), and stop/commit
# control arrivals; the pipelined-flush bookkeeping is the hottest
# read-modify-write-across-await surface in the tree (ROADMAP item 4)
@shared_state(
    "_await_commit_epoch", "_inflight_flushes", "_flush_failed",
    "_flush_hwm", "_stopping", "_current_barrier", "_barrier_inputs",
    "_finish_kinds", "_last_flush",
    multi_writer=("_flush_failed", "_stopping"),
)
class SubtaskRunner:
    """Executes one subtask: a chain of operators with shared inputs/outputs."""

    def __init__(
        self,
        ops: List[Operator],
        ctxs: List[OperatorContext],
        inputs: List[InputQueue],
        tail: Collector,
        control_rx: asyncio.Queue,
        control_tx: asyncio.Queue,
    ):
        assert len(ops) == len(ctxs) and ops
        self.ops = ops
        self.ctxs = ctxs
        self.inputs = inputs
        self.tail = tail
        self.control_rx = control_rx
        self.control_tx = control_tx
        self.collectors = [ChainCollector(self, i) for i in range(len(ops))]
        if self.is_source:
            self.collectors[0] = SourceCollector(self, 0)
        for ctx in ctxs:
            ctx._runner = self  # back-ref for in-chain watermark injection
        self.task_info = ctxs[0].task_info
        self.watermarks = ctxs[0].watermarks
        # generation-overlap rescale: a staged incarnation's sources park
        # on this gate after on_start/restore until promotion releases
        # them (None everywhere else — zero cost on the normal path)
        self.source_gate: Optional[asyncio.Event] = None
        # hot-standby failover (ISSUE 17): a standby incarnation restores
        # its tables at arm time but parks HERE before any operator's
        # on_start — on_start derives in-memory state from the tables
        # non-idempotently (joins append, sources read offsets once), so
        # it must run exactly once, on the final promoted/tailed state
        self.standby_gate: Optional[asyncio.Event] = None
        self._finish_kinds: Dict[int, SignalKind] = {}
        self._barrier_inputs: set[int] = set()
        self._current_barrier = None
        self._stopping = False
        # committing state (reference states/committing): set to the epoch
        # of the latest checkpoint that reported commit data; the runner
        # must not tear down until the phase-2 CommitMsg for it arrives,
        # or the sealed sink transaction would be stranded uncommitted
        self._await_commit_epoch: Optional[int] = None
        tid = self.task_info.task_id
        jid = self.task_info.job_id
        self._batches_recv = BATCHES_RECV.labels(job=jid, task=tid)
        self._msgs_recv = MESSAGES_RECV.labels(job=jid, task=tid)
        self._bytes_recv = BYTES_RECV.labels(job=jid, task=tid)
        # flight recorder: per-subtask latency/lag instruments
        self._batch_seconds = BATCH_PROCESSING_SECONDS.labels(
            job=jid, task=tid)
        # DS2 true-rate denominator: seconds of useful work (vs idle on
        # queue reads / blocked on backpressure) — see metrics.BUSY_SECONDS
        self._busy_secs = BUSY_SECONDS.labels(job=jid, task=tid)
        self._align_gauge = BARRIER_ALIGNMENT_SECONDS.labels(
            job=jid, task=tid)
        self._phase_obs = {
            p: CHECKPOINT_PHASE_SECONDS.labels(job=jid, task=tid, phase=p)
            for p in ("align", "capture", "flush")
        }
        self._wm_lag = None  # registered lazily on the first watermark
        self._align_span = obs.NULL_SPAN
        self._align_started: Optional[float] = None
        # off-barrier checkpoint flush queue (ROADMAP item 4): up to
        # state.max_inflight_flushes epochs' flushes run concurrently
        # with later epochs' processing, strictly epoch-ordered per
        # subtask (each flush awaits its predecessor before doing I/O)
        self._inflight_flushes: List[asyncio.Task] = []
        self._last_flush: Optional[asyncio.Task] = None
        self._flush_failed = False
        self._max_inflight = max(1, int(config().state.max_inflight_flushes))
        self._flush_hwm = 0  # high-water mark of concurrent flushes (tests)
        # device-tier observatory: latency-marker transit up to this
        # subtask (and end-to-end when terminal), plus the trace id that
        # batch/watermark-triggered jax.compile spans anchor under
        self._marker_secs = LATENCY_MARKER_SECONDS.labels(job=jid, task=tid)
        self._e2e_secs = E2E_LATENCY_SECONDS.labels(job=jid, task=tid)
        self._compile_trace = obs.new_trace(jid, f"batch-{tid}")
        # conservation ledger (obs/audit.py): receiver-side attestation
        # taps (one per input whose queue the wiring stamped with its
        # edge key) + per-operator in/out selectivity counts. All state
        # here is select-loop-confined: _collect_audit snapshots it by
        # value before handing the payload to the pipelined flush task.
        self._audit_on = obs.audit.enabled()
        if self._audit_on:
            self._rx_taps: List[Optional[obs.audit.EdgeTap]] = [
                obs.audit.EdgeTap(e)
                if (e := getattr(iq.queue, "audit_edge", None)) else None
                for iq in inputs
            ]
        else:
            self._rx_taps = [None] * len(inputs)
        self._op_counts = [[0, 0] for _ in ops]

    @property
    def is_source(self) -> bool:
        return isinstance(self.ops[0], SourceOperator)

    # ------------------------------------------------------------------ run

    async def run(self):
        # bind the job-id attribution context for this runner task's whole
        # dynamic extent: every await-descendant (checkpoint flush tasks,
        # to_thread storage work, device dispatches) inherits it, so cost
        # on a multiplexed worker rolls up to the right tenant
        obs.attribution.set_job(self.task_info.job_id)
        set_task_root(f"runner:{self.task_info.task_id}")
        try:
            if self.standby_gate is not None:
                # hot-standby arm (ISSUE 17): pay the storage restore NOW,
                # while the primary generation is still running — the
                # controller tails later epochs' delta chains onto these
                # open tables until promotion releases the gate
                with obs.span("task.standby_arm", cat="runner",
                              task=self.task_info.task_id):
                    from ..serve import serve_mirror_tables

                    for op, ctx in zip(self.ops, self.ctxs):
                        if ctx.table_manager is not None:
                            await ctx.table_manager.open({
                                **op.tables(),
                                **serve_mirror_tables(op, self.task_info),
                            })
                await self.standby_gate.wait()
            # under the job.schedule trace (context inherited at task
            # spawn): table restore + operator on_start become visible
            # stages of a (re)start in the flight recording
            with obs.span("task.start", cat="runner",
                          task=self.task_info.task_id) as sp:
                from ..serve import register_op as serve_register
                from ..serve import serve_mirror_tables

                for idx, (op, ctx) in enumerate(zip(self.ops, self.ctxs)):
                    if (ctx.table_manager is not None
                            and self.standby_gate is None):
                        # viewed operators additionally open the
                        # `__serve__` mirror table followers tail
                        await ctx.table_manager.open({
                            **op.tables(),
                            **serve_mirror_tables(op, self.task_info),
                        })
                    sp.event("on_start", op=type(op).__name__, op_idx=idx)
                    await op.on_start(ctx)
                    # StateServe: keyed operators expose an epoch-
                    # consistent read view (seeded from restored state,
                    # so a recovered job serves immediately)
                    serve_register(op, ctx)
            drained: Optional[bool] = None
            detail = ""
            if self.is_source:
                finish = await self._run_source()
                if finish == SourceFinishType.FINAL:
                    status = self.ops[0].drain_status()
                    if status is not None:
                        drained, detail = bool(status[0]), str(status[1])
            else:
                await self._run_operator_loop()
            self.control_tx.put_nowait(
                TaskFinishedResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                    source_drained=drained,
                    source_drain_detail=detail,
                )
            )
        except Exception:
            logger.exception("task %s failed", self.task_info.task_id)
            self.control_tx.put_nowait(
                TaskFailedResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                    traceback.format_exc(),
                )
            )

    async def run_prefinished(self):
        """Restored-as-finished (the restore manifest's `finished_tasks`):
        every row this task ever produced is already reflected in the
        restored downstream state, so re-running would duplicate it. Just
        close the output streams and report finished."""
        try:
            await self.tail.broadcast(SignalMessage.end_of_data())
            self.control_tx.put_nowait(
                TaskFinishedResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                )
            )
        except Exception:
            logger.exception(
                "prefinished task %s failed", self.task_info.task_id
            )
            self.control_tx.put_nowait(
                TaskFailedResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                    traceback.format_exc(),
                )
            )

    # --------------------------------------------------------------- source

    async def _run_source(self):
        src: SourceOperator = self.ops[0]  # type: ignore[assignment]
        ctx: SourceContext = self.ctxs[0]  # type: ignore[assignment]
        ctx._runner = self  # check_control delegates here
        if self.source_gate is not None:
            # staged incarnation: state is restored (on_start already
            # ran), now hold emission until the controller promotes this
            # generation — the old one is still draining its final epoch
            await self.source_gate.wait()
        finish = await src.run(ctx, self.collectors[0])
        await src.flush_buffer(ctx, self.collectors[0])
        if finish == SourceFinishType.FINAL:
            await self._close_chain(is_eod=True)
            await self.tail.broadcast(SignalMessage.end_of_data())
        elif finish == SourceFinishType.GRACEFUL:
            await self._close_chain(is_eod=False)
            await self.tail.broadcast(SignalMessage.stop())
        # IMMEDIATE: tear down silently
        return finish

    async def source_handle_control(self, collector) -> Optional[SourceFinishType]:
        """Called by sources between emissions (via ctx.check_control):
        drain pending control messages; returns a finish type when the source
        should stop."""
        src: SourceOperator = self.ops[0]  # type: ignore[assignment]
        ctx: SourceContext = self.ctxs[0]  # type: ignore[assignment]
        while True:
            try:
                msg = self.control_rx.get_nowait()
            except asyncio.QueueEmpty:
                return None
            if isinstance(msg, CheckpointMsg):
                # rows buffered before the barrier belong to this epoch
                await src.flush_buffer(ctx, collector)
                await self._checkpoint_chain(msg.barrier)
                if msg.barrier.then_stop:
                    return SourceFinishType.GRACEFUL
            elif isinstance(msg, StopMsg):
                if msg.mode == StopMode.IMMEDIATE:
                    return SourceFinishType.IMMEDIATE
                await src.flush_buffer(ctx, collector)
                return SourceFinishType.GRACEFUL
            elif isinstance(msg, CommitMsg):
                await self._handle_commit(msg)
            elif isinstance(msg, LoadCompactedMsg):
                await self._load_compacted(msg)

    # ------------------------------------------------------------ operators

    async def _run_operator_loop(self):
        pending: Dict[asyncio.Task, object] = {}

        def arm_input(i: int):
            iq = self.inputs[i]
            t = asyncio.ensure_future(iq.queue.recv())
            pending[t] = i

        def arm_control():
            t = asyncio.ensure_future(self.control_rx.get())
            pending[t] = "control"

        tick_interval = min(
            (op.tick_interval() for op in self.ops if op.tick_interval()),
            default=None,
        )
        tick_count = 0

        def arm_tick():
            if tick_interval:
                t = asyncio.ensure_future(asyncio.sleep(tick_interval))
                pending[t] = "tick"

        # operator-owned futures (async UDF completions etc., reference
        # operator.rs future_to_poll): re-queried whenever un-armed, since
        # processing a batch may create new pollable work
        op_futs: Dict[int, asyncio.Task] = {}

        def arm_op_futures():
            for idx, op in enumerate(self.ops):
                if idx not in op_futs:
                    f = op.future_to_poll()
                    if f is not None:
                        t = asyncio.ensure_future(f)
                        op_futs[idx] = t
                        pending[t] = ("opfut", idx)

        for i in range(len(self.inputs)):
            arm_input(i)
        arm_control()
        arm_tick()
        arm_op_futures()

        while not self._all_inputs_finished() and not self._stopping:
            done, _ = await asyncio.wait(
                pending.keys(), return_when=asyncio.FIRST_COMPLETED
            )
            for t in done:
                tag = pending.pop(t)
                if tag == "control":
                    await self._handle_control(t.result())
                    arm_control()
                elif tag == "tick":
                    tick_count += 1
                    t0 = time.perf_counter()
                    for op, ctx, coll in zip(self.ops, self.ctxs, self.collectors):
                        if op.tick_interval():
                            await op.handle_tick(tick_count, ctx, coll)
                    dt = time.perf_counter() - t0
                    self._busy_secs.inc(dt)
                    obs.attribution.note(busy=dt)
                    arm_tick()
                elif isinstance(tag, tuple) and tag[0] == "opfut":
                    idx = tag[1]
                    op_futs.pop(idx, None)
                    await self.ops[idx].handle_future_result(
                        self.ctxs[idx], self.collectors[idx]
                    )
                else:
                    i: int = tag  # input index
                    try:
                        item = t.result()
                    except QueueClosed:
                        self._finish_kinds[i] = SignalKind.STOP
                        self.inputs[i].finished = True
                        # a closed input can no longer hold back alignment
                        if self._current_barrier is not None:
                            await self._maybe_complete_alignment()
                        continue
                    rearm = await self._handle_input_item(i, item)
                    if rearm and not self.inputs[i].finished and not self.inputs[i].blocked:
                        arm_input(i)
                    # alignment complete may unblock other inputs
                    if self._current_barrier is None:
                        for j, iq in enumerate(self.inputs):
                            if iq.blocked:
                                iq.blocked = False
                                if not iq.finished:
                                    arm_input(j)
            arm_op_futures()
        # keep the armed control-queue getter: it may already hold a
        # retrieved message (e.g. the phase-2 CommitMsg) that cancelling
        # would silently drop
        control_task = next(
            (t for t, tag in pending.items() if tag == "control"), None
        )
        for t in pending:
            if t is not control_task:
                t.cancel()
        control_task = await self._await_commit(control_task)
        if control_task is not None:
            control_task.cancel()
        # end-of-data only when every input actually delivered EOS — an
        # IMMEDIATE stop (crash-like teardown) leaves _finish_kinds empty
        # and must NOT finalize uncommitted sink output (exactly-once:
        # visibility belongs to the 2PC commit, not teardown)
        is_eod = (
            not self._stopping
            and len(self._finish_kinds) == len(self.inputs)
            and all(
                k == SignalKind.END_OF_DATA
                for k in self._finish_kinds.values()
            )
        )
        await self._close_chain(is_eod=is_eod)
        await self.tail.broadcast(
            SignalMessage.end_of_data() if is_eod else SignalMessage.stop()
        )

    @protocol_effect("worker.await_commit")
    async def _await_commit(self, control_task, timeout: float = 10.0):
        """Committing state (reference states/committing.rs): the inputs
        closed, but the last checkpoint reported commit data whose phase-2
        CommitMsg hasn't arrived yet — closing now would strand a sealed
        sink transaction. Keep consuming control messages (bounded) until
        the commit lands. Skipped on IMMEDIATE stop: crash-like teardown
        must not finalize anything (recovery replays the epoch)."""
        import time

        if self._await_commit_epoch is None or self._stopping:
            return control_task
        deadline = time.monotonic() + timeout
        while self._await_commit_epoch is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                logger.warning(
                    "%s: no commit received for epoch %s within %.0fs; "
                    "closing with the transaction sealed but uncommitted",
                    self.task_info.task_id, self._await_commit_epoch,
                    timeout,
                )
                break
            if control_task is None:
                control_task = asyncio.ensure_future(self.control_rx.get())
            try:
                msg = await asyncio.wait_for(
                    asyncio.shield(control_task), remaining
                )
            except asyncio.TimeoutError:
                continue  # deadline check above breaks the loop
            control_task = None
            await self._handle_control(msg)
        return control_task

    def _all_inputs_finished(self) -> bool:
        return all(iq.finished for iq in self.inputs)

    async def _handle_input_item(self, i: int, item) -> bool:
        """Process one message from input i. Returns whether to re-arm."""
        spec = chaos.fire("runner.stall", job=self.task_info.job_id,
                          task=self.task_info.task_id)
        if spec is not None:
            # a wedged operator: the input loop holds (async — only THIS
            # subtask stalls; co-resident tenants keep their turns on the
            # shared loop) while upstream queues back up and the
            # watermark falls behind — the freshness-SLO drill's seam
            if spec.param("block", False):
                # params.block: a CPU-bound/blocking UDF that never yields
                # — starves the WHOLE event loop (heartbeats, co-tenants),
                # the starvation drill's attack on squeezed deadlines
                time.sleep(float(spec.param("delay", 0.5)))  # arroyolint: disable=ASY002
            else:
                await asyncio.sleep(float(spec.param("delay", 0.5)))
        iq = self.inputs[i]
        if isinstance(item, SignalMessage):
            if item.kind == SignalKind.WATERMARK:
                # the enclosing phase of a watermark signal, first line to
                # last: a close's leaves are booked where the work
                # happens, its self time is what they leave unnamed, the
                # holder's arithmetic for a signal that moves nothing too
                with obs.timeline.phase(
                        "watermark", task=self.task_info.task_id,
                        annotate=False) as ph:
                    changed = self.watermarks.set(i, item.watermark)
                    if changed is not None:
                        self._track_watermark_lag(changed)
                        anchor = obs.device.anchor(
                            self._compile_trace, "watermark.advance",
                            task=self.task_info.task_id,
                        )
                        try:
                            await self._chain_watermark(0, changed)
                        finally:
                            anchor.close()
                        # window emission happens here: count it as busy
                        # time or watermark-driven operators look idle to
                        # the autoscaler no matter how hard they work
                        dt = ph.elapsed()
                        self._busy_secs.inc(dt)
                        # per-job attributed busy (the ambient job context
                        # is set by run())
                        obs.attribution.note(busy=dt)
                return True
            if item.kind == SignalKind.LATENCY_MARKER:
                await self._handle_marker(item)
                return True
            if item.kind == SignalKind.BARRIER:
                return await self._handle_barrier(i, item.barrier)
            if item.kind in (SignalKind.END_OF_DATA, SignalKind.STOP):
                self._finish_kinds[i] = item.kind
                iq.finished = True
                # a finished input can no longer hold back alignment
                if self._current_barrier is not None:
                    await self._maybe_complete_alignment()
                return False
            return True
        # data batch: the enclosing phase of its whole handling, the
        # counters and the audit tap ahead of the operators included
        with obs.timeline.phase("process", task=self.task_info.task_id,
                                n=item.num_rows, annotate=False) as ph:
            self._batches_recv.inc()
            self._msgs_recv.inc(item.num_rows)
            nbytes = batch_bytes(item)
            self._bytes_recv.inc(nbytes)
            obs.attribution.note(nbytes=nbytes)
            if self._audit_on:
                tap = self._rx_taps[i]
                if tap is not None:
                    with obs.timeline.phase("audit.attest", n=item.num_rows):
                        tap.observe(item)
                self._op_counts[0][0] += item.num_rows
            anchor = obs.device.anchor(
                self._compile_trace, "batch.process",
                task=self.task_info.task_id,
            )
            try:
                await self.ops[0].process_batch(
                    item, self.ctxs[0], self.collectors[0], iq.logical_input
                )
            finally:
                anchor.close()
            dt = ph.elapsed()
            self._batch_seconds.observe(dt)
            self._busy_secs.inc(dt)
            obs.attribution.note(busy=dt)
        return True

    async def _handle_marker(self, item: SignalMessage):
        """Latency marker (types.LatencyMarker): record transit since the
        source stamp, then forward to one destination per out edge — or,
        at a terminal subtask (sink), record end-to-end latency. Markers
        never block alignment and never touch event time; a marker that
        queued behind a blocked input simply carries the alignment delay
        in its transit, which is exactly the latency a record would see."""
        transit = max(0.0, (time.time_ns() - item.marker.stamp_ns) / 1e9)
        self._marker_secs.observe(transit)
        if self.tail.is_terminal:
            self._e2e_secs.observe(transit)
        else:
            await self.tail.forward_marker(item)

    def _track_watermark_lag(self, wm: Watermark):
        """Per-subtask watermark-lag gauge: wall clock minus the effective
        watermark, refreshed at scrape time so a quiesced stream shows its
        lag GROWING instead of pinning the last computed value."""
        if wm.kind != WatermarkKind.EVENT_TIME or wm.timestamp is None:
            return
        if self._wm_lag is None:
            self._wm_lag = WATERMARK_LAG_SECONDS.labels(
                job=self.task_info.job_id, task=self.task_info.task_id
            )
            holder_ref = weakref.ref(self.watermarks)

            def _lag_now():
                holder = holder_ref()
                if holder is None:
                    return None  # runner gone: unregister
                ts = holder.current_nanos()
                if ts is None:
                    return 0.0
                return max(0.0, (time.time_ns() - ts) / 1e9)

            self._wm_lag.set_refresher(_lag_now)
        self._wm_lag.set(max(0.0, (time.time_ns() - wm.timestamp) / 1e9))

    # ------------------------------------------------------------ watermark

    async def _chain_watermark(self, start_idx: int, wm: Watermark):
        """Run a watermark through chain ops [start_idx..); broadcast if it
        survives (reference operator.rs:733-790)."""
        cur: Optional[Watermark] = wm
        for idx in range(start_idx, len(self.ops)):
            cur = await self.ops[idx].handle_watermark(
                cur, self.ctxs[idx], self.collectors[idx]
            )
            if cur is None:
                return
        await self.tail.broadcast(SignalMessage.watermark_of(cur))

    # ------------------------------------------------------------- barriers

    def _barrier_span(self, name: str, barrier, parent: Optional[str] = None):
        """A span anchored to the barrier's epoch trace (NULL when the
        barrier is untraced, so nothing anchors to unrelated contexts)."""
        if not barrier.trace_id:
            return obs.NULL_SPAN
        return obs.start_span(
            name, trace=barrier.trace_id,
            parent=parent or (barrier.span_id or None), cat="runner",
            task=self.task_info.task_id, epoch=barrier.epoch,
        )

    async def _handle_barrier(self, i: int, barrier) -> bool:
        """Align: block input i until all live inputs delivered the barrier
        (reference operator.rs:673-708, 1036-1046)."""
        if self._audit_on:
            # receiver-side epoch cut: aligned inputs deliver no further
            # rows for this epoch once their barrier arrives, so input
            # i's attestation is complete right here
            tap = self._rx_taps[i]
            if tap is not None:
                tap.seal(barrier.epoch)
        if self._current_barrier is None:
            self._current_barrier = barrier
            self._align_started = time.perf_counter()
            self._align_span = self._barrier_span("barrier.align", barrier)
            self.control_tx.put_nowait(
                CheckpointEventResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                    barrier.epoch,
                    "started_alignment",
                )
            )
        self._barrier_inputs.add(i)
        self.inputs[i].blocked = True
        await self._maybe_complete_alignment()
        return self._current_barrier is None  # re-arm only if aligned+done

    async def _maybe_complete_alignment(self):
        live = {
            j for j, iq in enumerate(self.inputs) if not iq.finished
        }
        if not live.issubset(self._barrier_inputs):
            return
        barrier = self._current_barrier
        if self._align_started is not None:
            align_secs = time.perf_counter() - self._align_started
            self._align_started = None
            self._align_gauge.set(align_secs)
            self._phase_obs["align"].observe(align_secs)
        self._align_span.set(inputs=len(self.inputs))
        self._align_span.finish()
        self._align_span = obs.NULL_SPAN
        await self._checkpoint_chain(barrier)
        # clear only the barrier we just processed: alignment state is
        # select-loop-confined today, and the guard keeps that true even
        # if a future path re-arms a new epoch under the chain's awaits
        if self._current_barrier is barrier:
            self._current_barrier = None
            self._barrier_inputs.clear()
        # unblocking + re-arming happens in the main loop

    @protocol_effect("worker.capture")
    async def _checkpoint_chain(self, barrier):
        """Capture every chain op's state at the barrier, re-broadcast the
        barrier downstream immediately, then flush (device->host
        materialization + file I/O) in a background task that overlaps
        later epochs' processing. The completed-report is sent when the
        flush lands. Up to state.max_inflight_flushes epochs' flushes may
        be in flight; they run strictly epoch-ordered per subtask (each
        awaits its predecessor), so file-list bookkeeping and completion
        reports stay ordered while barrier cadence is fully decoupled
        from upload time. `then_stop` and commit paths drain completely."""
        await self._admit_flush()
        self.control_tx.put_nowait(
            CheckpointEventResp(
                self.task_info.task_id,
                self.task_info.node_id,
                self.task_info.task_index,
                barrier.epoch,
                "started_checkpointing",
            )
        )
        t0 = time.perf_counter()
        cap_span = self._barrier_span("checkpoint.capture", barrier)
        with cap_span, obs.timeline.phase(
                "ckpt.capture", task=self.task_info.task_id,
                key=barrier.epoch) as capture:
            from ..serve import seal_op

            captured = []
            commit_data = None
            for idx, (op, ctx) in enumerate(zip(self.ops, self.ctxs)):
                capture.n += await op.handle_checkpoint(
                    barrier, ctx, self.collectors[idx]) or 0
                # StateServe: seal the view's staged rows under this
                # epoch at the same synchronization point the state
                # capture stamps dirty entries — reads at published
                # epoch P then see exactly P's durable view
                seal_op(op, barrier.epoch, ctx.table_manager)
                if ctx.table_manager is not None:
                    captured.append(
                        (
                            idx,
                            ctx.table_manager.capture(
                                barrier.epoch, self.watermarks.current_nanos()
                            ),
                        )
                    )
                if ctx.commit_data is not None:
                    commit_data = ctx.commit_data
                    ctx.commit_data = None
            if commit_data is not None:
                self._await_commit_epoch = barrier.epoch
            # downstream barriers parent to THIS hop's capture span, so the
            # epoch trace follows the operator graph across the data plane
            out_barrier = (
                barrier.with_span(cap_span.span_id)
                if cap_span.recording else barrier
            )
            await self.tail.broadcast(SignalMessage.barrier_of(out_barrier))
        # the broadcast sealed every sender-side tap at this epoch; the
        # receiver taps sealed at alignment — snapshot both (plus the
        # selectivity counts) by value NOW, before the select loop can
        # process post-barrier rows, and let the attestation ride the
        # pipelined completion report
        audit = self._collect_audit(barrier.epoch)
        self._phase_obs["capture"].observe(time.perf_counter() - t0)
        flush_span = self._barrier_span(
            "checkpoint.flush", barrier,
            parent=cap_span.span_id or None,
        )
        flush = asyncio.ensure_future(
            self._flush_and_report(barrier, captured, commit_data,
                                   self.watermarks.current_nanos(),
                                   flush_span, prev=self._last_flush,
                                   audit=audit)
        )
        self._last_flush = flush
        self._inflight_flushes.append(flush)
        self._flush_hwm = max(
            self._flush_hwm,
            sum(1 for t in self._inflight_flushes if not t.done()),
        )
        if barrier.then_stop:
            await self._await_pending_flush()

    def _collect_audit(self, epoch: int) -> Optional[dict]:
        """Assemble this subtask's conservation attestation for one epoch:
        sealed sender (tx) and receiver (rx) edge attestations plus the
        per-operator selectivity ledger, reset for the next epoch. Runs
        synchronously inside the barrier path, so the counts cut exactly
        at the epoch boundary."""
        if not self._audit_on:
            return None
        tx: Dict[str, list] = {}
        for edge in self.tail.edges:
            edge.drain_audit(epoch, tx)
        rx: Dict[str, list] = {}
        for tap in self._rx_taps:
            if tap is not None:
                v = tap.drain(epoch)
                if v is not None:
                    rx[tap.edge] = [v[0], v[1]]
        ops: Dict[str, list] = {}
        flow: Dict[str, str] = {}
        for idx, op in enumerate(self.ops):
            cnt = self._op_counts[idx]
            name = f"{idx}:{op.name}"
            ops[name] = [cnt[0], cnt[1]]
            flow[name] = getattr(op, "flow_class", "any")
            cnt[0] = 0
            cnt[1] = 0
        return {"tx": tx, "rx": rx, "ops": ops, "flow": flow}

    @protocol_effect("worker.admit_flush")
    async def _admit_flush(self):
        """Block until a flush slot is free (bounds capture-ahead: the
        barrier path stalls only once max_inflight epochs are uploading)."""
        self._inflight_flushes = [
            t for t in self._inflight_flushes if not t.done()
        ]
        while len(self._inflight_flushes) >= self._max_inflight:
            await self._inflight_flushes[0]
            self._inflight_flushes = [
                t for t in self._inflight_flushes if not t.done()
            ]

    @protocol_effect("worker.drain_flushes")
    async def _await_pending_flush(self):
        """Drain EVERY in-flight flush (stop/commit/close paths stay
        strictly drained — teardown must never strand an upload)."""
        flushes, self._inflight_flushes = self._inflight_flushes, []
        for flush in flushes:
            await flush
        self._last_flush = None

    @protocol_effect("worker.flush")
    async def _flush_and_report(self, barrier, captured, commit_data,
                                watermark, flush_span=obs.NULL_SPAN,
                                prev: Optional[asyncio.Task] = None,
                                audit: Optional[dict] = None):
        set_task_root(f"flush:{self.task_info.task_id}")
        if prev is not None and not prev.done():
            await asyncio.wait({prev})
        if self._flush_failed:
            # an earlier epoch's flush already failed the task: reporting
            # (or flushing) later epochs would publish state past a hole
            flush_span.set(skipped="predecessor_failed")
            flush_span.finish()
            return
        t0 = time.perf_counter()
        tok = flush_span.attach() if flush_span.recording else None
        try:
            metadata: Dict[str, dict] = {}
            for idx, staged in captured:
                tm = self.ctxs[idx].table_manager
                # the storage-commit leg of the epoch tree: to_thread
                # copies the attached context, so storage.put spans nest
                metadata[f"op{idx}"] = await asyncio.to_thread(
                    tm.flush_captured, barrier.epoch, staged
                )
        except Exception:
            # surface immediately: the controller sees the failure rather
            # than a checkpoint-wait timeout, and nothing is silently lost
            logger.exception(
                "checkpoint flush failed for %s epoch %s",
                self.task_info.task_id, barrier.epoch,
            )
            # monotonic latch: True is the only post-init value, so a
            # concurrent setter is idempotent and the stale entry guard
            # only ever skips work already doomed
            self._flush_failed = True  # arroyolint: disable=RACE002
            flush_span.set(error=traceback.format_exc(limit=3)[:300])
            self.control_tx.put_nowait(
                TaskFailedResp(
                    self.task_info.task_id,
                    self.task_info.node_id,
                    self.task_info.task_index,
                    traceback.format_exc(),
                )
            )
            return
        finally:
            if tok is not None:
                flush_span.detach(tok)
            flush_span.finish()
            flush_dt = time.perf_counter() - t0
            self._phase_obs["flush"].observe(flush_dt)
            # checkpoint flushes overlap later batches (off-barrier
            # uploads): the timeline shows them as their own swimlane
            obs.timeline.note("flush", flush_dt,
                              task=self.task_info.task_id)
        self.control_tx.put_nowait(
            CheckpointCompletedResp(
                self.task_info.task_id,
                self.task_info.node_id,
                self.task_info.task_index,
                barrier.epoch,
                subtask_metadata=metadata,
                watermark=watermark,
                has_commit_data=commit_data is not None,
                commit_data=commit_data,
                audit=audit,
            )
        )

    # -------------------------------------------------------------- control

    async def _handle_control(self, msg):
        if isinstance(msg, CommitMsg):
            await self._handle_commit(msg)
        elif isinstance(msg, StopMsg) and msg.mode == StopMode.IMMEDIATE:
            self._stopping = True
        elif isinstance(msg, LoadCompactedMsg):
            await self._load_compacted(msg)
        elif isinstance(msg, CheckpointMsg) and not self.is_source:
            # checkpoints reach non-sources via in-band barriers; a direct
            # message is a protocol error — ignore but log.
            logger.warning(
                "non-source %s got direct CheckpointMsg", self.task_info.task_id
            )

    @protocol_effect("worker.commit")
    async def _handle_commit(self, msg: CommitMsg):
        span = obs.NULL_SPAN
        if msg.trace_id:
            span = obs.start_span(
                "commit.apply", trace=msg.trace_id,
                parent=msg.span_id or None, cat="runner",
                task=self.task_info.task_id, epoch=msg.epoch,
            )
        with span:
            node_data = msg.committing_data.get(self.task_info.node_id, {})
            for op, ctx in zip(self.ops, self.ctxs):
                await op.handle_commit(msg.epoch, node_data, ctx)
        if (
            self._await_commit_epoch is not None
            and msg.epoch >= self._await_commit_epoch
        ):
            self._await_commit_epoch = None

    async def _load_compacted(self, msg: LoadCompactedMsg):
        for idx, ctx in enumerate(self.ctxs):
            if msg.op_idx is not None and idx != msg.op_idx:
                continue
            if ctx.table_manager is not None:
                await ctx.table_manager.load_compacted(msg.table, msg.paths)

    # ----------------------------------------------------------------- close

    async def _close_chain(self, is_eod: bool):
        # a checkpoint flush may still be in flight; exceptions surface here
        await self._await_pending_flush()
        for idx, (op, ctx) in enumerate(zip(self.ops, self.ctxs)):
            wm = await op.on_close(ctx, self.collectors[idx], is_eod)
            if wm is not None:
                # run through the remainder of the chain, then downstream
                await self._chain_watermark(idx + 1, wm)
