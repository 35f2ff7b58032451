"""Collectors: route operator output onto downstream edge queues.

Capability parity with the reference's ArrowCollector + repartition
(/root/reference/crates/arroyo-operator/src/context.rs:506-610): keyed
shuffle edges hash the routing-key columns and slice one sub-batch per
destination partition; unkeyed shuffle edges rotate whole batches
round-robin (the reference slices round-robin with a random rotation — we
keep a deterministic per-subtask rotation so tests are reproducible);
forward edges are 1-1. Signals broadcast to every destination queue.
"""

from __future__ import annotations

import weakref
from typing import List, Optional

import pyarrow as pa

from .. import chaos
from ..metrics import BACKPRESSURE, BATCHES_SENT, BYTES_SENT, MESSAGES_SENT
from ..obs import timeline
from ..schema import StreamSchema
from ..types import SignalKind, SignalMessage
from .queues import BatchQueue, batch_bytes


class EdgeSender:
    def __init__(
        self,
        edge_type,
        schema: StreamSchema,
        queues: List[BatchQueue],
        src_subtask: int = 0,
    ):
        from ..graph.logical import EdgeType  # avoid import cycle

        self.edge_type = edge_type
        self.schema = schema
        self.queues = queues
        self.src_subtask = src_subtask
        self._rr = src_subtask  # round-robin cursor for unkeyed shuffles
        self._marker_rr = src_subtask  # separate cursor for latency markers
        self._is_forward = edge_type == EdgeType.FORWARD
        # conservation ledger (obs/audit.py): one sender-side attestation
        # tap per destination queue, built lazily on the first send so
        # config is resolved once. None entries = auditing off or a queue
        # the wiring didn't stamp (engine-internal previews).
        self._audit_taps: Optional[list] = None

    def _taps(self) -> list:
        if self._audit_taps is None:
            from ..obs import audit

            if audit.enabled():
                self._audit_taps = [
                    audit.EdgeTap(q.audit_edge)
                    if getattr(q, "audit_edge", None) else None
                    for q in self.queues
                ]
            else:
                self._audit_taps = [None] * len(self.queues)
        return self._audit_taps

    async def _send_data(self, idx: int, batch: pa.RecordBatch):
        """All data batches leave through here: attest to the queue's tap
        FIRST (the attestation states what the operator chain emitted),
        then pass the chaos dropped-flush seam — a fired drop means rows
        the sender attested never reach the receiver, which is exactly
        the lost-delivery shape the reconciler must flag."""
        tap = self._taps()[idx]
        if tap is not None:
            # the fingerprint of every column: milliseconds for a wide
            # batch, so it has a name of its own inside the sender's `emit`
            with timeline.phase("audit.attest", n=batch.num_rows):
                tap.observe(batch)
            if chaos.fire("audit.drop_batch", edge=tap.edge):
                return
        await self.queues[idx].send(batch)

    async def send_batch(self, batch: pa.RecordBatch):
        n = len(self.queues)
        if self._is_forward or n == 1:
            idx = self.src_subtask % n if self._is_forward else 0
            await self._send_data(idx, batch)
            return
        if self.schema.key_indices:
            parts = self.schema.partition(batch, n)
            for i, part in enumerate(parts):
                if part is not None and part.num_rows:
                    await self._send_data(i, part)
        else:
            self._rr = (self._rr + 1) % n
            await self._send_data(self._rr, batch)

    def seal_audit(self, epoch: int) -> None:
        """Seal every destination tap's running attestation at this
        epoch's barrier broadcast (the sender-side epoch cut)."""
        for tap in self._taps():
            if tap is not None:
                tap.seal(epoch)

    def drain_audit(self, epoch: int, out: dict) -> None:
        """Move this sender's sealed epoch attestations into `out`
        (edge -> [rows, digest]) for the checkpoint report."""
        for tap in self._taps():
            if tap is not None:
                v = tap.drain(epoch)
                if v is not None:
                    out[tap.edge] = [v[0], v[1]]

    async def broadcast(self, signal: SignalMessage):
        if signal.kind == SignalKind.BARRIER:
            self.seal_audit(signal.barrier.epoch)
        if self._is_forward:
            await self.queues[self.src_subtask % len(self.queues)].send(signal)
        else:
            for q in self.queues:
                await q.send(signal)

    async def send_marker(self, signal: SignalMessage):
        """Forward a latency marker to exactly ONE destination (Flink's
        latency-marker rule: broadcasting across every shuffle hop would
        multiply markers combinatorially along the depth of the graph).
        Rotates a dedicated cursor so all destination subtasks get
        sampled over time — deliberately separate from the unkeyed-data
        round-robin cursor, which must keep routing the exact same
        batches to the exact same queues (chaos drills compare output
        byte-identically with obs on and off)."""
        if self._is_forward:
            await self.queues[self.src_subtask % len(self.queues)].send(signal)
            return
        self._marker_rr = (self._marker_rr + 1) % len(self.queues)
        await self.queues[self._marker_rr].send(signal)


class Collector:
    """The tail collector of a subtask: fans output to all out edges and
    maintains tx counters."""

    def __init__(self, edges: List[EdgeSender], task_id: str = "",
                 job_id: str = ""):
        self.edges = edges
        self.task_id = task_id
        self._batch_counter = BATCHES_SENT.labels(job=job_id, task=task_id)
        self._msg_counter = MESSAGES_SENT.labels(job=job_id, task=task_id)
        self._bytes_counter = BYTES_SENT.labels(job=job_id, task=task_id)
        self._bp_gauge = BACKPRESSURE.labels(job=job_id, task=task_id)
        self._bp_tick = 0
        # the sampled update in collect() goes stale the moment a stream
        # quiesces (no more collect() calls ever re-sample it — ADVICE
        # r5), so the gauge also refreshes at scrape time: a weakly-bound
        # refresher recomputes occupancy on expose/snapshot and
        # unregisters itself once this collector is garbage-collected
        ref = weakref.ref(self)

        def _bp_now():
            c = ref()
            if c is None:
                return None
            return max(
                (q.fullness() for e in c.edges for q in e.queues),
                default=0.0,
            )

        self._bp_gauge.set_refresher(_bp_now)
        # sink-side hook: engine-level capture of terminal output (preview)
        self.collected: Optional[list] = None

    # backpressure needs sampling granularity, not per-batch accuracy:
    # recomputing the max over every out-queue on every collect() added a
    # python generator walk to the hottest path (ADVICE r4)
    _BP_SAMPLE_EVERY = 16

    async def collect(self, batch: pa.RecordBatch):
        if batch.num_rows == 0:
            return
        self._batch_counter.inc()
        self._msg_counter.inc(batch.num_rows)
        self._bytes_counter.inc(batch_bytes(batch))
        # fleet observatory: emit time (partitioning + queue sends,
        # INCLUDING any backpressure wait) is its own timeline phase —
        # a batch stuck here points downstream, not at this operator
        # (a blocked send books its wait as `queue.wait` inside it: emit's
        # self time is the rest. It awaits, so it is not annotated: over a
        # full queue its interval covers whatever the other tasks do)
        with timeline.phase("emit", task=self.task_id, n=batch.num_rows,
                            annotate=False):
            for edge in self.edges:
                await edge.send_batch(batch)
        self._bp_tick += 1
        if self._bp_tick == 1 or self._bp_tick % self._BP_SAMPLE_EVERY == 0:
            # post-send occupancy of the most-loaded out queue: 1.0 means
            # the next send blocks (downstream is the bottleneck)
            self._bp_gauge.set(max(
                (q.fullness() for e in self.edges for q in e.queues),
                default=0.0,
            ))

    async def broadcast(self, signal: SignalMessage):
        for edge in self.edges:
            await edge.broadcast(signal)

    @property
    def is_terminal(self) -> bool:
        """No out edges: this subtask ends the pipeline (sink / preview
        tail) — latency markers arriving here measure end-to-end."""
        return not self.edges

    async def forward_marker(self, signal: SignalMessage):
        """Latency markers go to one destination per out edge (see
        EdgeSender.send_marker)."""
        for edge in self.edges:
            await edge.send_marker(signal)
