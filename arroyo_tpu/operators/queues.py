"""Bounded dataflow queues, counted in both batches and bytes.

Capability parity with the reference's batch_bounded channel
(/root/reference/crates/arroyo-operator/src/context.rs:91-196): capacity
counts items AND bytes so one huge batch can't blow memory while many tiny
batches can't add unbounded latency. Signals (watermarks/barriers/stop) are
always accepted — they are tiny and must never deadlock the control flow —
but data sends block (backpressure) when either bound is hit.
"""

from __future__ import annotations

import asyncio
import dataclasses
import weakref
from collections import deque
from typing import Optional

import pyarrow as pa

from ..metrics import QUEUE_BYTES, QUEUE_SIZE
from ..obs import timeline
from ..types import SignalMessage


def batch_bytes(batch: pa.RecordBatch) -> int:
    return batch.get_total_buffer_size()


class QueueClosed(Exception):
    pass


class BatchQueue:
    """One edge queue between a (src_subtask, dst_subtask) pair."""

    def __init__(self, max_batches: int, max_bytes: int, name: str = "",
                 job: str = ""):
        self.max_batches = max(1, max_batches)
        self.max_bytes = max(1, max_bytes)
        self.name = name
        self._items: deque = deque()
        self._bytes = 0
        self._closed = False
        self._readable = asyncio.Event()
        self._writable = asyncio.Event()
        self._writable.set()
        # the job label lets the cardinality GC (Registry.drop_job) drop a
        # stopped job's queue series in one pass — multiplexed workers
        # otherwise accumulate every churned job's gauges forever
        labels = {"queue": name, **({"job": job} if job else {})}
        self._size_gauge = QUEUE_SIZE.labels(**labels) if name else None
        self._bytes_gauge = QUEUE_BYTES.labels(**labels) if name else None
        if name:
            # the push/pop updates only run on the producer/consumer hot
            # paths, so a scrape between events (or after the last event —
            # a quiesced or torn-down edge) would report whatever occupancy
            # happened to be stored last. Same staleness class as the
            # backpressure gauge (PR 1): refresh at scrape time through a
            # weak reference, unregistering once the queue is collected so
            # autoscaler samples never read a dead edge as live depth.
            ref = weakref.ref(self)

            def _size_now():
                q = ref()
                return None if q is None else float(len(q._items))

            def _bytes_now():
                q = ref()
                return None if q is None else float(q._bytes)

            self._size_gauge.set_refresher(_size_now)
            self._bytes_gauge.set_refresher(_bytes_now)

    def qsize(self) -> int:
        return len(self._items)

    def fullness(self) -> float:
        """0..1 occupancy against whichever bound (count or bytes) is
        closer to blocking the sender — the backpressure signal. Clamped:
        signals bypass capacity checks and one oversized batch may exceed
        the byte bound, so raw occupancy can pass the limit."""
        return min(1.0, max(len(self._items) / self.max_batches,
                            self._bytes / self.max_bytes))

    def _has_capacity(self) -> bool:
        return len(self._items) < self.max_batches and self._bytes < self.max_bytes

    def _update_gauges(self):
        if self._size_gauge is not None:
            self._size_gauge.set(len(self._items))
            self._bytes_gauge.set(self._bytes)

    async def send(self, item, nbytes: Optional[int] = None):
        """Send a data batch; blocks when the queue is at capacity."""
        if self._closed:
            raise QueueClosed(self.name)
        if isinstance(item, SignalMessage):
            self._push(item, 0)
            return
        if nbytes is None:
            nbytes = batch_bytes(item)
        if not self._has_capacity():
            # backpressure: the wait has a name of its own, so the sender's
            # `emit` keeps only the time it worked
            with timeline.phase("queue.wait", task=self.name,
                                annotate=False):
                while not self._has_capacity():
                    self._writable.clear()
                    await self._writable.wait()
                    if self._closed:
                        raise QueueClosed(self.name)
        self._push(item, nbytes)

    def _push(self, item, nbytes: int):
        self._items.append((item, nbytes))
        self._bytes += nbytes
        self._readable.set()
        self._update_gauges()

    async def recv(self):
        while not self._items:
            if self._closed:
                raise QueueClosed(self.name)
            self._readable.clear()
            await self._readable.wait()
        item, nbytes = self._items.popleft()
        self._bytes -= nbytes
        if self._has_capacity():
            self._writable.set()
        self._update_gauges()
        return item

    def close(self):
        self._closed = True
        self._readable.set()
        self._writable.set()


@dataclasses.dataclass
class InputQueue:
    """A subtask input: the queue plus its logical input index (which in-edge
    it belongs to — joins distinguish left=0/right=1) and alignment state."""

    queue: BatchQueue
    logical_input: int = 0
    src_task: str = ""
    blocked: bool = False  # barrier arrived, holding until alignment
    finished: bool = False  # EndOfData seen
