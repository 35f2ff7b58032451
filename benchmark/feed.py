"""How events get into the program and results get out of it.

Two connectors registered through the program's public registry
(`arroyo_tpu.connectors.base.register_connector`): the source
`bench_nexmark`, which hands the engine the Arrow batches a producer thread
built ahead of time, and the sink `bench_sink`, which stamps each arriving
batch with the host's monotonic clock and keeps it. Both find their `Feed`
by the `feed` option of the SQL text, so nothing inside `arroyo_tpu/` knows
the benchmark exists.

The schedule is open-loop: event `n` has event time `n / nominal_rate`
seconds, delivered on time or not. A traffic file names the mode:

- `steady`: a batch of `chunk_seconds` of stream leaves when its last
  event has happened on the wall clock; the feed records how late it left.
- `catchup`: the same stream offered as fast as the engine's backpressure
  takes it; the nominal rate fixes events per window, the system the pace.

Set-up feeds `warm_event_seconds` of event time as fast as the engine takes
it and waits for its results; the timed window is exactly `seconds` of wall
after that. A timer thread closes it: the engine may hold the source inside
one acceptance for many seconds (a window close of q7 takes ~19 s), so the
source's own loop cannot. What was accepted by the deadline counts; the
batch in flight does not, and is the last the source sends.
"""

from __future__ import annotations

import asyncio
import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from gen import nexmark as gen

NS = 1_000_000_000
FEEDS: Dict[str, "Feed"] = {}


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The parameters of one traffic file."""

    mode: str                      # "steady" | "catchup"
    nominal_rate: float            # events per second of event time
    warm_event_seconds: float      # event time fed during set-up
    first_event: int = 0           # sequence number the job starts from
    batch_rows: int = 8192         # catchup: rows per batch
    chunk_seconds: float = 0.02    # steady: stream seconds per batch
    look_ahead_batches: int = 64   # producer's bound

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names - {"why", "rehearsal"}
        if unknown:
            raise ValueError(f"traffic file: unknown keys {sorted(unknown)}")
        if "nominal_rate" not in d:
            raise ValueError("traffic file: no nominal_rate")
        t = cls(**{k: v for k, v in d.items() if k in names})
        if t.mode not in ("steady", "catchup"):
            raise ValueError(f"traffic mode {t.mode!r}")
        return t

    def rows_per_batch(self) -> int:
        if self.mode == "steady":
            return max(1, int(round(self.nominal_rate * self.chunk_seconds)))
        return self.batch_rows


@dataclasses.dataclass
class Fault:
    """A control run's broken guarantee: one event lost or delivered
    twice at the source."""

    kind: str          # "drop" | "dup"
    event: int         # sequence number


class Feed:
    """One run's stream, its pacing and what came back."""

    def __init__(self, traffic: Traffic, seed: int, seconds: float,
                 fault: Optional[Fault] = None):
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.fault = fault
        self.rate = float(traffic.nominal_rate)
        self.n_first = int(traffic.first_event)
        # the warm-up is a whole number of batches of the window's own size,
        # so that set-up walks the shapes the window will use
        rows = traffic.rows_per_batch()
        self.n_warm = self.n_first + rows * -(
            -int(round(traffic.warm_event_seconds * self.rate)) // rows)
        self._q: "queue.Queue" = queue.Queue(traffic.look_ahead_batches)
        self._stop = threading.Event()
        self._producer = threading.Thread(
            target=self._produce, name="bench-producer", daemon=True)
        self.producer_error: Optional[BaseException] = None
        # set by run.py: called at the window's edges, the start on the
        # engine's thread, the end on a timer's
        self.on_window_start: Callable[[], None] = lambda: None
        self.on_window_end: Callable[[], None] = lambda: None
        self.annotate = None            # jax.profiler.TraceAnnotation or None
        # what the run recorded
        self.t_first_poll: Optional[float] = None    # the source's loop began
        self.t_warm_fed: Optional[float] = None      # the warm-up was accepted
        self.t_window_start: Optional[float] = None
        self.t_window_end: Optional[float] = None
        self.n_window_start: Optional[int] = None   # first event of the window
        self.n_window_end: Optional[int] = None     # one past the last in it
        self._closing = threading.Lock()
        self.n_delivered = self.n_first              # one past the last accepted
        self.late_s: List[float] = []                # steady: per batch
        self.starved = 0                             # source polls with no batch
        self.arrivals: List[tuple] = []              # (monotonic_ns, batch)
        self.last_arrived_ts = -1                    # newest result `_timestamp`
        # (epoch, wall ns at which the program says it initiated the
        # barrier), as the source passed each one into the dataflow
        self.barriers: List[tuple] = []
        self.gate_timed_out = False
        # set by run.py from the configuration: after a stall of the event
        # loop longer than this (a first compile in a checkout) the window
        # waits until checkpoints flow again (`_settled`)
        self.long_stall_s = float("inf")
        self.published_epoch: Callable[[], int] = lambda: 0
        self.long_stall_end_ns: Optional[int] = None     # wall clock
        self.longest_stall_s = 0.0      # of the whole run, for the log
        # set by run.py from the configuration and its reference: which
        # results are due, and when (`schedule.py`)
        self.watermark_delay_ns = NS
        self.schedule = None

    # -- the schedule -------------------------------------------------------

    def event_time_ns(self, n) -> np.ndarray:
        return gen.event_times(n, 0, self.rate)

    def first_event_at(self, t_ns):
        """The first sequence number whose event time is >= t_ns; an array
        of times gives an array."""
        t = np.asarray(t_ns, dtype=np.int64)
        n = np.ceil(t * self.rate / NS).astype(np.int64)
        while True:                     # the float's last place, both ways
            early = (n > 0) & (self.event_time_ns(np.maximum(n - 1, 0)) >= t)
            late = self.event_time_ns(n) < t
            if not (early.any() or late.any()):
                return n if n.ndim else int(n)
            n = n - early + late

    def due_wall(self, n: int) -> float:
        """Wall time (monotonic) at which event n is due in a paced run."""
        return self.t_window_start + (n + 1 - self.n_window_start) / self.rate

    # -- producer thread ----------------------------------------------------

    def start(self) -> None:
        self._producer.start()

    def close(self) -> None:
        self._stop.set()
        while True:                     # unblock a producer stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._producer.join(timeout=30)

    def _ranges(self):
        """[lo, hi) of each batch, warm-up and window alike."""
        rows = self.traffic.rows_per_batch()
        lo = self.n_first
        while True:
            yield lo, lo + rows
            lo += rows

    def _produce(self) -> None:
        try:
            for lo, hi in self._ranges():
                ns = np.arange(lo, hi, dtype=np.int64)
                f = self.fault
                if f is not None and lo <= f.event < hi:
                    if f.kind == "drop":
                        ns = ns[ns != f.event]
                    elif f.kind == "dup":
                        ns = np.sort(np.append(ns, f.event))
                    else:
                        raise ValueError(f"fault kind {f.kind!r}")
                batch = gen.gen_batch(ns, self.event_time_ns(ns), self.seed)
                item = (lo, hi, batch)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised by the source
            self.producer_error = e

    # -- the source's loop (engine thread) ----------------------------------

    def _mark(self, name: str) -> None:
        if self.annotate is not None:
            with self.annotate(name):
                pass

    async def _take(self, ctx, collector):
        """Next produced batch, polling control while the producer is
        behind. Returns (item, finish)."""
        while True:
            if self.producer_error is not None:
                raise RuntimeError("producer failed") from self.producer_error
            try:
                return self._q.get_nowait(), None
            except queue.Empty:
                self.starved += 1
                finish = await ctx.check_control(collector)
                if finish is not None:
                    return None, finish
                await asyncio.sleep(0.001)

    async def _watch_loop(self):
        """How long the event loop (the engine's, the controller's and the
        in-process workers' alike) goes without a turn."""
        while True:
            await self._nap(0.05)

    async def _nap(self, step: float) -> None:
        """Sleep `step` seconds and book by how much the loop overslept."""
        t = time.monotonic()
        await asyncio.sleep(step)
        lag = time.monotonic() - t - step
        self.longest_stall_s = max(self.longest_stall_s, lag)
        if lag > self.long_stall_s:
            self.long_stall_end_ns = time.time_ns()

    def _settled(self) -> bool:
        """No long stall, or a barrier that the program initiated after
        the last one has passed the source and its checkpoint is published:
        by then the controller has heard from its workers again, and what
        the stall left pending (a barrier whose fan-out timed out holds the
        epochs behind it until its 60 s deadline) is out of the way."""
        if self.long_stall_end_ns is None:
            return True
        after = [e for e, t in self.barriers if t > self.long_stall_end_ns]
        return bool(after) and self.published_epoch() >= after[0]

    async def _gate(self, ctx, collector, timeout: float = 900.0):
        """The window starts caught up: wait until the last close that the
        warm-up made due has reached the sink, so that the warm-up's own
        work (and, in a checkout's first run, its compiles) stays in
        set-up. The edge queues refill within the window's first second."""
        last_due = self.schedule.last_due(self.n_warm)
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            if (last_due is None or self.close_arrived(last_due)) and (
                    self._settled()):
                return None
            finish = await ctx.check_control(collector)
            if finish is not None:
                return finish
            await self._nap(0.01)       # the watcher may not have had its turn
        self.gate_timed_out = True
        return None

    async def drive(self, ctx, collector):
        watch = asyncio.ensure_future(self._watch_loop())
        try:
            return await self._drive(ctx, collector)
        finally:
            watch.cancel()

    async def _drive(self, ctx, collector):
        from arroyo_tpu.operators.base import SourceFinishType

        paced = self.traffic.mode == "steady"
        self.t_first_poll = time.monotonic()
        while True:
            finish = await ctx.check_control(collector)
            if finish is not None:
                return finish
            if self.t_window_start is None and self.n_delivered >= self.n_warm:
                self.t_warm_fed = time.monotonic()
                finish = await self._gate(ctx, collector)
                if finish is not None:
                    return finish
                self.n_window_start = self.n_delivered
                self.t_window_start = time.monotonic()
                self.on_window_start()
                timer = threading.Timer(self.seconds, self._close_window)
                timer.daemon = True
                timer.start()
            if self._window_over():
                self._close_window()    # if the timer is a moment behind
                return SourceFinishType.GRACEFUL
            item, finish = await self._take(ctx, collector)
            if finish is not None:
                return finish
            lo, hi, batch = item
            if paced and self.t_window_start is not None:
                due = self.due_wall(hi - 1)
                end = self.t_window_start + self.seconds
                self._mark("bench.source.wait.begin")
                await asyncio.sleep(max(min(due, end) - time.monotonic(), 0))
                self._mark("bench.source.wait.end")
                if due > end:           # not due inside the window
                    continue
                self.late_s.append(max(time.monotonic() - due, 0.0))
            await collector.collect(batch)
            self.n_delivered = hi
            await asyncio.sleep(0)

    def _window_over(self) -> bool:
        return (self.t_window_start is not None
                and time.monotonic() - self.t_window_start >= self.seconds)

    def _close_window(self) -> None:
        """At the deadline, on the timer's thread: what the engine has
        accepted by now is the window's, whatever it is busy with."""
        with self._closing:
            if self.t_window_end is not None:
                return
            self.n_window_end = self.n_delivered
            self.t_window_end = time.monotonic()
            self.on_window_end()

    # -- the sink (engine thread) -------------------------------------------

    def arrived(self, batch) -> None:
        self.arrivals.append((time.monotonic_ns(), batch))
        ts = _timestamps(batch)
        if len(ts):
            self.last_arrived_ts = max(self.last_arrived_ts, int(ts.max()))

    def close_arrived(self, window_end_ns: int) -> bool:
        """A result row carries its window's last nanosecond."""
        return self.last_arrived_ts >= window_end_ns - 1


def _timestamps(batch) -> np.ndarray:
    import pyarrow as pa

    names = batch.schema.names
    if "_timestamp" not in names:
        return np.empty(0, dtype=np.int64)
    return np.asarray(batch.column(names.index("_timestamp")).cast(pa.int64()))


def register() -> None:
    """Register `bench_nexmark` and `bench_sink` with the program."""
    from arroyo_tpu.connectors.base import Connector, register_connector
    from arroyo_tpu.operators.base import Operator, SourceOperator
    from arroyo_tpu.schema import StreamSchema

    schema = StreamSchema.from_fields(gen.FIELDS)
    assert schema.schema.equals(gen.SCHEMA)

    class BenchSource(SourceOperator):
        def __init__(self, feed: Feed):
            super().__init__("bench_nexmark")
            self.feed = feed
            self.out_schema = schema

        async def run(self, ctx, collector):
            return await self.feed.drive(ctx, collector)

        async def handle_checkpoint(self, barrier, ctx, collector):
            self.feed.barriers.append((barrier.epoch, barrier.timestamp))

    class BenchSink(Operator):
        def __init__(self, feed: Feed):
            super().__init__("bench_sink")
            self.feed = feed

        async def process_batch(self, batch, ctx, collector,
                                input_index: int = 0):
            if self.feed.annotate is not None:
                with self.feed.annotate("bench.sink"):
                    self.feed.arrived(batch)
            else:
                self.feed.arrived(batch)

    def _feed_of(options) -> dict:
        if options.get("feed") not in FEEDS:
            raise ValueError(f"no feed {options.get('feed')!r}")
        return {"feed": options["feed"]}

    @register_connector
    class BenchNexmark(Connector):
        name = "bench_nexmark"
        description = "benchmark: seeded NEXmark stream from a Feed"
        source = True

        def validate_options(self, options, schema):
            return _feed_of(options)

        def table_schema(self):
            return schema

        def make_source(self, config, schema):
            return BenchSource(FEEDS[config["feed"]])

    @register_connector
    class BenchSinkConnector(Connector):
        name = "bench_sink"
        description = "benchmark: keeps arriving batches with a timestamp"
        sink = True

        def validate_options(self, options, schema):
            return _feed_of(options)

        def make_sink(self, config, schema):
            return BenchSink(FEEDS[config["feed"]])
