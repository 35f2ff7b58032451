"""TCP data plane: Arrow IPC record batches between workers.

Capability parity with the reference's network manager
(/root/reference/crates/arroyo-worker/src/network_manager.rs): raw TCP
carrying Arrow-IPC-encoded RecordBatches with a fixed routing header
`Quad{src_node, src_subtask, dst_node, dst_subtask}`
(network_manager.rs:170-236, write_message_and_header:551, read_message:605);
one outgoing connection per (remote worker, edge); incoming frames route to
the destination subtask's local input queue; backpressure propagates from
the bounded in-process queues through per-connection flow control
(the pump only reads the next outgoing batch after the socket write
drains). Signals ride the same framing msgpack-encoded.

Frame layout (little-endian):
  magic u32 = 0xA77051  | kind u8 (0=data,1=signal,2=hello)
  src_node u32 | src_subtask u32 | dst_node u32 | dst_subtask u32
  payload_len u64 | sent_ns u64 | trace_len u16
  trace bytes (msgpack {"t": trace_id, "s": span_id}, flight recorder)
  payload bytes

Multi-tenancy: node ids are per-job, so quads collide across jobs
multiplexed onto one worker. Each connection therefore opens with ONE
hello frame (kind=2, payload msgpack {"ns": "<job_id>@<incarnation>"})
binding every subsequent frame on that connection to the sender job's
route namespace; the server routes on (ns, quad). The incarnation
(controller schedule counter) additionally fences a straggler connection
from a torn-down incarnation of the SAME job out of the fresh
incarnation's queues.

Every frame header carries the sender's wall-clock send timestamp, which
the receiver folds into the `arroyo_exchange_frame_seconds` histogram;
the trace preamble attaches to signal frames carrying barrier context and
to every obs.frame_sample_every'th data frame (sampled exchange spans).
"""

from __future__ import annotations

import asyncio
import io
import struct
import time
from typing import Dict, Optional, Tuple

import msgpack
import pyarrow as pa

from .. import chaos, obs
from ..metrics import EXCHANGE_FRAME_SECONDS
from ..types import (
    CheckpointBarrier,
    LatencyMarker,
    SignalKind,
    SignalMessage,
    Watermark,
    WatermarkKind,
)
from ..utils.logging import get_logger
from ..operators.queues import BatchQueue

logger = get_logger("network")

MAGIC = 0xA77051
_HEADER = struct.Struct("<IBIIIIQQH")

Quad = Tuple[int, int, int, int]  # src_node, src_sub, dst_node, dst_sub


def encode_signal(sig: SignalMessage) -> bytes:
    out = {"kind": sig.kind.value}
    if sig.watermark is not None:
        out["wm_kind"] = sig.watermark.kind.value
        out["wm_ts"] = sig.watermark.timestamp
    if sig.barrier is not None:
        b = sig.barrier
        out["barrier"] = [b.epoch, b.min_epoch, b.timestamp, b.then_stop]
        if b.trace_id:
            # flight-recorder context rides the barrier across workers
            out["barrier"] += [b.trace_id, b.span_id]
    if sig.marker is not None:
        m = sig.marker
        out["marker"] = [m.source_task, m.seq, m.stamp_ns]
    return msgpack.packb(out)


def decode_signal(data: bytes) -> SignalMessage:
    obj = msgpack.unpackb(data, raw=False)
    kind = SignalKind(obj["kind"])
    wm = None
    barrier = None
    marker = None
    if "wm_kind" in obj:
        wm = Watermark(WatermarkKind(obj["wm_kind"]), obj.get("wm_ts"))
    if "barrier" in obj:
        e, m, t, s = obj["barrier"][:4]
        extra = obj["barrier"][4:]
        barrier = CheckpointBarrier(
            e, m, t, s,
            trace_id=extra[0] if extra else "",
            span_id=extra[1] if len(extra) > 1 else "",
        )
    if "marker" in obj:
        marker = LatencyMarker(*obj["marker"][:3])
    return SignalMessage(kind, wm, barrier, marker)


def encode_batch(batch: pa.RecordBatch) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


def decode_batch(data: bytes) -> pa.RecordBatch:
    with pa.ipc.open_stream(pa.py_buffer(data)) as r:
        batches = list(r)
    if len(batches) == 1:
        return batches[0]
    return pa.Table.from_batches(batches).combine_chunks().to_batches()[0]


def write_frame(writer: asyncio.StreamWriter, quad: Quad, item,
                trace: Optional[dict] = None) -> None:
    if isinstance(item, SignalMessage):
        kind, payload = 1, encode_signal(item)
    else:
        kind, payload = 0, encode_batch(item)
    tbytes = msgpack.packb(trace) if trace else b""
    writer.write(
        _HEADER.pack(MAGIC, kind, *quad, len(payload), time.time_ns(),
                     len(tbytes))
    )
    if tbytes:
        writer.write(tbytes)
    writer.write(payload)


def write_hello(writer: asyncio.StreamWriter, ns: str) -> None:
    """Bind this connection to a job route namespace (first frame)."""
    payload = msgpack.packb({"ns": ns})
    writer.write(
        _HEADER.pack(MAGIC, 2, 0, 0, 0, 0, len(payload), time.time_ns(), 0)
    )
    writer.write(payload)


async def read_frame(reader: asyncio.StreamReader):
    """Returns (quad, item, sent_ns, trace-dict-or-None)."""
    header = await reader.readexactly(_HEADER.size)
    magic, kind, sn, ss, dn, ds, plen, sent_ns, tlen = _HEADER.unpack(header)
    if magic != MAGIC:
        raise ValueError(f"bad frame magic {magic:#x}")
    trace = None
    if tlen:
        trace = msgpack.unpackb(await reader.readexactly(tlen), raw=False)
    payload = await reader.readexactly(plen)
    if kind == 2:
        item = msgpack.unpackb(payload, raw=False)  # hello dict
    elif kind == 1:
        item = decode_signal(payload)
    else:
        item = decode_batch(payload)
    return (sn, ss, dn, ds), kind, item, sent_ns, trace


def _set_nodelay(writer: asyncio.StreamWriter) -> None:
    """Disable Nagle on data-plane sockets: frames are latency-sensitive
    and often tiny (watermarks, per-window join batches) — Nagle plus
    delayed ACK costs 40-200 ms PER HOP, which stacks across the
    multi-edge paths of a split pipeline. Throughput is unaffected: the
    pump already writes whole frames and drains."""
    sock = writer.get_extra_info("socket")
    if sock is not None:
        import socket as _socket

        try:
            sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        except OSError:
            pass  # e.g. TLS-wrapped transport without raw socket access


class DataPlaneServer:
    """Accepts peer connections and routes frames into local input queues
    (reference `Senders`)."""

    def __init__(self, bind: str = "127.0.0.1", port: int = 0):
        self.bind = bind
        self.port = port
        # (ns, (src_node, src_sub, dst_node, dst_sub)) -> local queue;
        # ns is the sender job's "<job_id>@<incarnation>" namespace
        # (quads collide across multiplexed jobs)
        self.routes: Dict[tuple, BatchQueue] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._handlers: set = set()  # the open connections' tasks

    def register(self, quad: Quad, queue: BatchQueue, ns: str = ""):
        self.routes[(ns, quad)] = queue

    def unregister_ns(self, ns: str):
        """Per-job teardown: drop every route of one job namespace so a
        co-resident job's routes stay live (and a straggler connection of
        the torn-down job routes nowhere instead of into fresh queues)."""
        for key in [k for k in self.routes if k[0] == ns]:
            del self.routes[key]

    async def start(self) -> int:
        from ..utils.tls import data_server_context

        self._server = await asyncio.start_server(
            self._handle, self.bind, self.port, ssl=data_server_context()
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.port

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter):
        _set_nodelay(writer)
        peer = writer.get_extra_info("peername")
        lat_handles: Dict[Quad, object] = {}
        ns = ""  # bound by the connection's hello frame
        me = asyncio.current_task()
        self._handlers.add(me)
        try:
            while True:
                quad, kind, item, sent_ns, trace = await read_frame(reader)
                if kind == 2:
                    ns = item.get("ns", "")
                    continue
                latency = max(0, time.time_ns() - sent_ns) / 1e9
                h = lat_handles.get(quad)
                if h is None:
                    # job label: the cardinality GC drops a stopped job's
                    # exchange series with the rest of its families
                    h = lat_handles[quad] = EXCHANGE_FRAME_SECONDS.labels(
                        task=f"{quad[2]}-{quad[3]}",
                        job=ns.split("@", 1)[0],
                    )
                h.observe(latency)
                if trace and "t" in trace and obs.enabled():
                    # sampled frame span: spans the wire time, parented to
                    # the sender's span so hops line up in trace dumps
                    import os as _os

                    obs.recorder().record({
                        "trace_id": trace["t"], "span_id": obs.new_span_id(),
                        "parent_id": trace.get("s"), "name": "exchange.frame",
                        "cat": "network", "ts": sent_ns / 1e3,
                        "dur": latency * 1e6,
                        "attrs": {
                            "edge": f"{quad[0]}-{quad[1]}->"
                                    f"{quad[2]}-{quad[3]}",
                        },
                        "events": [], "pid": _os.getpid(), "tid": 0,
                    })
                queue = self.routes.get((ns, quad))
                if queue is None:
                    logger.warning("no route for %s/%s from %s", ns, quad,
                                   peer)
                    continue
                if kind == 0 and chaos.fire(
                    "audit.dup_frame",
                    edge=getattr(queue, "audit_edge", None)
                    or f"{quad[0]}:{quad[1]}->{quad[2]}:{quad[3]}",
                ):
                    # duplicated data-frame delivery past the TCP layer:
                    # the receiver tap attests the rows twice while the
                    # sender attested them once — the conservation
                    # reconciler must name this edge+epoch
                    await queue.send(item)
                await queue.send(item)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            self._handlers.discard(me)
            writer.close()

    async def stop(self):
        if self._server is not None:
            self._server.close()
            # `wait_closed` waits for every connection's handler (Python
            # 3.12), and one blocked on the full queue of a torn-down
            # incarnation, which nobody drains, never returns by itself
            for handler in list(self._handlers):
                handler.cancel()
            await self._server.wait_closed()


class RemoteEdgeSender:
    """Pumps a local queue over TCP to a remote worker: the sender side of
    one (edge, dst_subtask) pair. Each edge pair gets its OWN connection —
    sharing one socket across edges would couple their backpressure: a
    blocked input (e.g. awaiting checkpoint barrier alignment) must never
    stall delivery of another edge's frames (the reference keeps one
    connection per (worker, edge) for the same reason,
    network_manager.rs:41-106). The bounded local queue provides
    backpressure; the pump blocks on socket drain."""

    def __init__(self, address: str, quad: Quad, queue: BatchQueue,
                 on_error=None, ns: str = ""):
        self.address = address
        self.quad = quad
        self.queue = queue
        self.on_error = on_error
        self.ns = ns  # sender job's route namespace (hello frame)
        self.task: Optional[asyncio.Task] = None
        self.writer: Optional[asyncio.StreamWriter] = None

    async def start(self):
        from ..utils.tls import data_client_context

        spec = chaos.fire("network.connect_delay", quad=self.quad,
                          address=self.address)
        if spec is not None:
            await asyncio.sleep(float(spec.param("delay", 0.2)))
        host, port = self.address.rsplit(":", 1)
        ctx, server_name = data_client_context()
        _, self.writer = await asyncio.open_connection(
            host, int(port), ssl=ctx,
            server_hostname=server_name if ctx is not None else None,
        )
        _set_nodelay(self.writer)
        write_hello(self.writer, self.ns)
        await self.writer.drain()
        self.task = asyncio.ensure_future(self._pump())

    async def _pump(self):
        from ..operators.queues import QueueClosed

        sample_every = obs.frame_sample_every()
        n_frames = 0
        # exchange attribution: the pump task belongs to one job (the ns
        # is "<job_id>@<incarnation>"), so frame serialization + socket
        # drain time lands on that tenant's exchange phase
        job_id = self.ns.split("@", 1)[0] if self.ns else ""
        obs.attribution.set_job(job_id)
        try:
            while True:
                try:
                    item = await self.queue.recv()
                except QueueClosed:
                    return
                if chaos.fire("network.drop_connection", quad=self.quad):
                    self.writer.close()
                    raise ConnectionResetError(
                        "chaos[network.drop_connection]: injected "
                        f"data-plane drop on edge {self.quad}"
                    )
                spec = chaos.fire("network.partial_frame", quad=self.quad)
                if spec is not None:
                    # emit a torn frame: full header, half the payload. The
                    # receiver's readexactly must fail (never deliver it).
                    if isinstance(item, SignalMessage):
                        kind, payload = 1, encode_signal(item)
                    else:
                        kind, payload = 0, encode_batch(item)
                    self.writer.write(
                        _HEADER.pack(MAGIC, kind, *self.quad, len(payload),
                                     time.time_ns(), 0)
                    )
                    self.writer.write(payload[: max(1, len(payload) // 2)])
                    await self.writer.drain()
                    self.writer.close()
                    raise ConnectionResetError(
                        "chaos[network.partial_frame]: injected torn frame "
                        f"on edge {self.quad}"
                    )
                trace = None
                n_frames += 1
                if (sample_every and not isinstance(item, SignalMessage)
                        and n_frames % sample_every == 1 and obs.enabled()):
                    # sampled data-frame trace header: one exchange span
                    # per edge track in the dump, grouped by edge
                    sn, ss, dn, ds = self.quad
                    trace = {"t": f"exchange/{sn}-{ss}_{dn}-{ds}"}
                t0 = time.perf_counter()
                write_frame(self.writer, self.quad, item, trace)
                await self.writer.drain()
                if not isinstance(item, SignalMessage):
                    obs.timeline.note(
                        "exchange", time.perf_counter() - t0,
                        task=f"{self.quad[0]}-{self.quad[1]}",
                    )
                if isinstance(item, SignalMessage) and item.kind in (
                    SignalKind.END_OF_DATA, SignalKind.STOP
                ):
                    return
        except Exception as e:  # noqa: BLE001 - network boundary
            logger.exception("remote edge pump %s -> %s failed",
                             self.quad, self.address)
            if self.on_error is not None:
                self.on_error(self.quad, e)
        finally:
            if self.writer is not None:
                self.writer.close()
