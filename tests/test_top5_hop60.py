"""Upstream Arroyo's first-pipeline query at a deployment's shape (ISSUE 33):
the top 5 auctions of the last minute, refreshed every 2 s (hop 2 s / 60 s,
`ROW_NUMBER() OVER (PARTITION BY window ORDER BY count DESC, auction
DESC)`, `row_num <= 5`). The benchmark's own query text through planner,
engine, the hop operator and the ranking operator, at a small size on the
CPU, held to `benchmark/reference/top5.py` with limit 0: on a seeded
NEXmark stream (rows, and the rows each task took in and gave out), on a
stream built so that the fifth and sixth counts of a window tie, on one
whose windows hold fewer than five auctions, and across a checkpoint and a
restore. The ranking operator's phases carry the counts the ledger states.
"""

import asyncio
import importlib.util
import os

import numpy as np
import pyarrow as pa
import pytest

from arroyo_tpu.config import update
from arroyo_tpu.engine import Engine
from arroyo_tpu.obs import timeline
from arroyo_tpu.sql import plan_query

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = _load("top5_gen", "gen", "nexmark.py")
reference = _load("top5_reference", "reference", "top5.py")

S = 10**9
RATE = 1000.0            # events/s of event time: 60,000 a 60 s window
ORIGIN_NS = 1_700_000_000 * S
N_EVENTS = 90_000        # 90 s of event time: 15 full windows, 82,800 bids
BATCH = 1000
STREAMS = {}


class Stream:
    """One job's input batches, the bids among them, and what its sink
    received."""

    def __init__(self, batches, bid_ts, auction, pause_at=None):
        self.input = batches
        self.bid_ts = bid_ts
        self.auction = auction
        self.pause_at = pause_at        # a batch's index
        self.paused = False
        self.released = False
        self.batches = []

    @classmethod
    def nexmark(cls, seed, pause_at=None):
        ns = np.arange(N_EVENTS, dtype=np.int64)
        ts = gen.event_times(ns, ORIGIN_NS, RATE)
        is_bid, auction, _bidder, _price = gen.bids(ns, seed)
        batches = [gen.gen_batch(ns[i:i + BATCH], ts[i:i + BATCH], seed)
                   for i in range(0, N_EVENTS, BATCH)]
        return cls(batches, ts[is_bid], auction, pause_at)

    @classmethod
    def of_bids(cls, bid_ts, auction, rows=64):
        """A stream of bids alone, in order: rows of the NEXmark schema
        whose person and auction are null."""
        bid_ts = np.asarray(bid_ts, dtype=np.int64)
        auction = np.asarray(auction, dtype=np.int64)
        assert (np.diff(bid_ts) >= 0).all()
        batches = []
        for i in range(0, len(bid_ts), rows):
            ts, a = bid_ts[i:i + rows], auction[i:i + rows]
            n = len(ts)
            stamp = pa.array(ts).cast(pa.timestamp("ns"))
            bid = pa.StructArray.from_arrays(
                [pa.array(a), pa.array(np.full(n, 7, dtype=np.int64)),
                 pa.array(np.full(n, 100, dtype=np.int64)),
                 pa.array(["Apple"] * n), pa.array(["u"] * n), stamp,
                 pa.array([""] * n)],
                fields=list(gen.BID_T))
            batches.append(pa.RecordBatch.from_arrays(
                [pa.nulls(n, type=gen.PERSON_T),
                 pa.nulls(n, type=gen.AUCTION_T), bid, stamp],
                schema=gen.SCHEMA))
        return cls(batches, bid_ts, auction)

    def windows(self):
        """{window end: sorted (auction, count, row_num)} of what the sink
        got."""
        out = {}
        for batch in self.batches:
            names = batch.schema.names
            ends = np.asarray(batch.column(
                names.index("_timestamp")).cast(pa.int64())) + 1
            cols = [np.asarray(batch.column(names.index(c)))
                    for c in reference.COLUMNS]
            for end, *row in zip(ends.tolist(), *(c.tolist() for c in cols)):
                out.setdefault(end, []).append(tuple(row))
        return {end: sorted(rows) for end, rows in out.items()}

    def ends(self):
        """Every window end that holds a bid: a bounded stream's last
        watermark closes them all."""
        slide, size = reference.SLIDE_NS, reference.SIZE_NS
        first = int(self.bid_ts[0]) // slide * slide + slide
        last = int(self.bid_ts[-1]) // slide * slide + size
        return list(range(first, last + 1, slide))

    def want(self):
        rows = reference.compute(
            self.bid_ts, self.auction, None, None, self.ends())
        assert all(rows.values())
        return rows

    def flows(self):
        return reference.flows(
            self.bid_ts, self.auction, None, None, self.ends())


def _register():
    from arroyo_tpu.connectors.base import Connector, register_connector
    from arroyo_tpu.operators.base import (
        Operator, SourceFinishType, SourceOperator)
    from arroyo_tpu.schema import StreamSchema
    from arroyo_tpu.state.table_config import global_table

    schema = StreamSchema.from_fields(gen.FIELDS)

    class Source(SourceOperator):
        def __init__(self, stream):
            super().__init__("top5_source")
            self.stream = stream
            self.out_schema = schema
            self.n = 0

        def tables(self):
            return {"o": global_table("o")}

        async def on_start(self, ctx):
            if ctx.table_manager is not None:
                self.n = int(dict((await ctx.table("o")).items()).get(
                    "next", 0))

        async def handle_checkpoint(self, barrier, ctx, collector):
            if ctx.table_manager is not None:
                (await ctx.table("o")).put("next", self.n)

        async def run(self, ctx, collector):
            s = self.stream
            while self.n < len(s.input):
                finish = await ctx.check_control(collector)
                if finish is not None:
                    return finish
                if (s.pause_at is not None and self.n >= s.pause_at
                        and not s.released):
                    s.paused = True
                    await asyncio.sleep(0.005)
                    continue
                await collector.collect(s.input[self.n])
                self.n += 1
                await asyncio.sleep(0)
            return SourceFinishType.FINAL

    class Sink(Operator):
        def __init__(self, stream):
            super().__init__("top5_sink")
            self.stream = stream

        async def process_batch(self, batch, ctx, collector,
                                input_index: int = 0):
            self.stream.batches.append(batch)

    class _Base(Connector):
        def validate_options(self, options, schema):
            assert options["feed"] in STREAMS
            return {"feed": options["feed"]}

    @register_connector
    class SourceConnector(_Base):
        name = "top5_source"
        description = "tests: a bounded stream of NEXmark rows"
        source = True

        def table_schema(self):
            return schema

        def make_source(self, config, schema):
            return Source(STREAMS[config["feed"]])

    @register_connector
    class SinkConnector(_Base):
        name = "top5_sink"
        description = "tests: keeps what arrives"
        sink = True

        def make_sink(self, config, schema):
            return Sink(STREAMS[config["feed"]])


_register()


def sql_for(feed_id):
    with open(os.path.join(BENCH, "configs", "nexmark-top5-hop60.sql")) as f:
        return (f.read().replace("bench_nexmark", "top5_source")
                .replace("bench_sink", "top5_sink")
                .replace("{feed}", feed_id))


def task_flow(job_id):
    """{task: (rows received, rows sent)} of one job, from the counters the
    benchmark's conservation comparison reads."""
    from arroyo_tpu.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    flow = {}
    for i, name in enumerate(("arroyo_worker_messages_recv",
                              "arroyo_worker_messages_sent")):
        for labels, value in snap.get(name, []):
            if labels.get("job") == job_id:
                flow.setdefault(labels.get("task"), [0, 0])[i] = int(value)
    return {task: tuple(rs) for task, rs in flow.items()}


def run_top5(stream, job_id, stop_at_pause=False, storage=None):
    """Run the query over the stream. With `stop_at_pause` the job takes a
    checkpoint at the stream's pause and stops there."""
    feed_id = f"s{id(stream)}"
    STREAMS[feed_id] = stream

    async def go():
        plan = plan_query(sql_for(feed_id), parallelism=1)
        eng = Engine(plan.graph, job_id=job_id, storage_url=storage).start()
        if stop_at_pause:
            while not stream.paused:
                await asyncio.sleep(0.01)
            await eng.checkpoint_and_wait(then_stop=True)
            stream.released = True
        await eng.join(180)

    try:
        with update(tpu={"require_accelerator": False}):
            asyncio.run(go())
    finally:
        STREAMS.pop(feed_id, None)
    return stream


@pytest.fixture(scope="module")
def nexmark_run():
    """The query over 90 s of seeded NEXmark stream, with its ledger and
    its tasks' row counters."""
    timeline.clear()
    stream = run_top5(Stream.nexmark(33), "top5-nexmark")
    return {"stream": stream, "ledger": timeline.totals(),
            "flow": task_flow("top5-nexmark")}


def test_the_plan_is_source_one_chained_window_task_and_sink():
    """The node and edge list of this query at parallelism one: the hop
    count, the ranking's three nodes (`window_fn_input`, the
    WINDOW_FUNCTION node, `window_fn_select`) and the filter are forward
    neighbours at one parallelism, and the optimizer's existing rule chains
    them into the hop operator's node: no 360k-row batch crosses an edge."""
    STREAMS["plan"] = None
    try:
        graph = plan_query(sql_for("plan"), parallelism=1).graph
    finally:
        del STREAMS["plan"]
    nodes = {n.node_id: [op.operator.value for op in n.chain]
             for n in graph.nodes.values()}
    assert list(nodes.values()) == [
        ["connector_source", "expression_watermark", "arrow_value"],
        ["sliding_window_aggregate", "fused_segment", "window_function",
         "fused_segment"],
        ["connector_sink"]]
    window = graph.nodes[list(nodes)[1]]
    assert [op.description for op in window.chain] == [
        "sliding_window",
        "segment[select(count, auction, window) -> window_fn_input]",
        "row_number_over",
        "segment[window_fn_select -> select(auction, count, row_num) -> "
        "sink_cast]"]
    assert all(n.parallelism == 1 for n in graph.nodes.values())
    src, win, sink = nodes
    assert [(e.src, e.dst, e.edge_type.value, e.schema.schema.names,
             tuple(e.schema.key_indices)) for e in graph.edges] == [
        (src, win, "shuffle", ["auction", "_timestamp"], (0,)),
        (win, sink, "forward",
         ["auction", "count", "row_num", "_timestamp"], ())]


def test_the_job_emits_the_references_rows(nexmark_run):
    stream = nexmark_run["stream"]
    want = stream.want()
    # 45 + 29 windows, 15 of them full, five rows each
    assert len(want) == 74 and all(len(r) == 5 for r in want.values())
    assert stream.windows() == want


def test_each_task_took_in_and_gave_out_the_references_rows(nexmark_run):
    """`flows` off by 0: the one chained task behind the shuffle took every
    bid and gave five rows a window; the source gave every bid."""
    (what, rows_in, rows_out), = nexmark_run["stream"].flows()
    assert (rows_in, rows_out) == (82_800, 5 * 74)
    flow = nexmark_run["flow"]
    assert sorted(flow.values()) == sorted(
        [(0, rows_in), (rows_in, rows_out), (rows_out, 0)])


def test_the_ranking_books_its_phases_with_the_stated_counts(nexmark_run):
    led, stream = nexmark_run["ledger"], nexmark_run["stream"]
    groups = reference.groups(stream.bid_ts, stream.auction, stream.ends())
    assert groups > 74 * 100
    for leaf in ("rank.buffer", "rank.sort", "rank.build", "rank.emit"):
        assert led[leaf]["count"] == 74, leaf      # one bin a close
        assert led[leaf]["n"] == groups, leaf
        assert led[leaf]["total_s"] > 0
    # the hop operator's merge feeds it: every slot of a window's live bins
    assert led["close.take"]["count"] == 74
    assert led["close.take"]["n"] >= groups
    assert led["close.build"]["n"] == groups
    # the downstream operators run inside `rank.emit`, the ranking itself
    # inside the hop operator's emit: self time is what is left
    assert led["rank.emit"]["self_s"] <= led["rank.emit"]["total_s"]
    assert led["close.emit"]["self_s"] < led["close.emit"]["total_s"]


def _tied_stream():
    """Windows of 2 s slices in which auctions 10..15 get 9, 8, 7, 6 and
    then 5, 5, 5 bids (auctions 14, 15, 16): the fifth, sixth and seventh
    counts tie."""
    ts, auction = [], []
    for k in range(40):
        t0 = ORIGIN_NS + k * 2 * S
        bids = [a for a, c in ((10, 9), (11, 8), (12, 7), (13, 6), (14, 5),
                               (15, 5), (16, 5)) for _ in range(c)]
        # the tied auctions' bids interleave: arrival order must not decide
        bids = sorted(bids, key=lambda a: (a * 7 + k) % 5)
        ts += [t0 + i * 1_000_000 for i in range(len(bids))]
        auction += bids
    return Stream.of_bids(ts, auction)


def test_a_tie_at_the_fifth_place_goes_to_the_larger_auction():
    stream = run_top5(_tied_stream(), "top5-tie")
    got = stream.windows()
    assert got == stream.want()
    end = ORIGIN_NS + 60 * S            # the first full window: 30 slices
    assert got[end] == [(10, 270, 1), (11, 240, 2), (12, 210, 3),
                        (13, 180, 4), (16, 150, 5)]


def test_a_window_with_fewer_than_five_auctions_ranks_what_it_has():
    ts = [ORIGIN_NS + i * S // 4 for i in range(400)]       # 100 s
    auction = [20 + (i % 3 == 0) + (i % 7 == 0) for i in range(400)]
    stream = run_top5(Stream.of_bids(ts, auction), "top5-few")
    got, want = stream.windows(), stream.want()
    assert got == want and len(got) == 50 + 29
    assert {len(rows) for rows in got.values()} <= {1, 2, 3}
    assert [r[2] for r in got[ORIGIN_NS + 60 * S]] == [1, 2, 3]
    (_what, rows_in, rows_out), = stream.flows()
    assert (rows_in, rows_out) in task_flow("top5-few").values()
    assert rows_out == sum(len(r) for r in want.values()) < 5 * len(want)


def test_a_checkpoint_mid_stream_restores_to_the_same_answers(
        nexmark_run, tmp_path, monkeypatch):
    """The job stops at a checkpoint two thirds through (45 closed windows
    behind it, 30 bins live), and a second job restores from it: the hop
    operator's bins, the ranking operator's `emitted_up_to`, the source's
    position. What the sink got in all is the reference's rows, each
    once."""
    from arroyo_tpu.operators.window_fn import WindowFunctionOperator

    serialised = []
    real = WindowFunctionOperator.handle_checkpoint

    async def told(self, barrier, ctx, collector):
        rows = await real(self, barrier, ctx, collector)
        serialised.append(rows + 7)
        return serialised[-1]

    monkeypatch.setattr(WindowFunctionOperator, "handle_checkpoint", told)
    storage = str(tmp_path / "ckpt")
    stream = Stream.nexmark(33, pause_at=60)
    timeline.clear()
    run_top5(stream, "top5-restore", stop_at_pause=True, storage=storage)
    # the runner books what the operator says it serialised
    assert serialised and timeline.phase_totals("top5-restore")[
        "ckpt.capture"]["n"] == sum(serialised)
    monkeypatch.undo()
    before = stream.windows()
    assert 0 < len(before) < 74
    run_top5(stream, "top5-restore", storage=storage)
    assert stream.windows() == nexmark_run["stream"].windows()
    assert len(stream.windows()) == 74


def _ranking_operator(storage, job):
    """A ranking operator as the planner configures this query's, on a
    state backend of its own: (operator, context, table manager, backend)."""
    from arroyo_tpu.operators.context import OperatorContext
    from arroyo_tpu.operators.window_fn import WindowFunctionOperator
    from arroyo_tpu.schema import StreamSchema, add_timestamp_field
    from arroyo_tpu.state.backend import StateBackend
    from arroyo_tpu.state.table_manager import TableManager
    from arroyo_tpu.types import TaskInfo

    schema = StreamSchema(add_timestamp_field(pa.schema(
        [("count", pa.int64()), ("auction", pa.int64()),
         ("row_num", pa.int64())])))
    op = WindowFunctionOperator({
        "fn": "row_number", "partition_cols": [],
        "order_by": [[0, True], [1, True]], "schema": schema,
        "out_field": "row_num"})
    backend = StateBackend(storage, job).initialize()
    info = TaskInfo(job, 6, "row_number_over", 0, 1)
    tm = TableManager(backend, info, 0)
    ctx = OperatorContext(info, [schema], schema, None, table_manager=tm)
    return op, ctx, tm, backend


def test_a_bin_buffered_at_a_barrier_is_restored_and_ranked(tmp_path):
    """A barrier that finds the ranking operator holding a bin (a close's
    rows arrived, its watermark has not: upstream subtasks at different
    watermarks) writes the bin whole into the `wf` table; a restored
    operator ranks it to the same rows and drops what was emitted
    before."""
    from arroyo_tpu.operators.control import CheckpointCompletedResp
    from arroyo_tpu.types import CheckpointBarrier, Watermark

    storage = f"file://{tmp_path}/wf"
    rng = np.random.default_rng(33)

    def rows(end, n):
        return pa.RecordBatch.from_arrays(
            [pa.array(rng.integers(1, 40, n)),
             pa.array(rng.permutation(n) + 1000),
             pa.array(np.full(n, end - 1)).cast(pa.timestamp("ns"))],
            names=["count", "auction", "_timestamp"])

    class Out:
        def __init__(self):
            self.batches = []

        async def collect(self, batch):
            self.batches.append(batch)

    def ranked(out):
        return [tuple(r.values()) for b in out.batches
                for r in b.to_pylist()]

    first, second = rows(2 * S, 300), rows(4 * S, 500)

    async def before():
        op, ctx, tm, backend = _ranking_operator(storage, "wf")
        await tm.open(op.tables())
        await op.on_start(ctx)
        out = Out()
        await op.process_batch(first, ctx, out)
        await op.handle_watermark(Watermark.event_time(2 * S), ctx, out)
        assert op.emitted_up_to == 2 * S - 1 and len(out.batches) == 1
        await op.process_batch(second.slice(0, 200), ctx, out)
        await op.process_batch(second.slice(200), ctx, out)
        # the rows it serialised, for the runner's `ckpt.capture`
        assert await op.handle_checkpoint(
            CheckpointBarrier(1, 0, 0, False), ctx, out) == 500
        meta = await tm.checkpoint(1, None)
        backend.publish_checkpoint(1, {"6-0": CheckpointCompletedResp(
            "6-0", 6, 0, 1, subtask_metadata={"op0": meta}, watermark=None)})
        # what the operator gives without a failure
        await op.handle_watermark(Watermark.event_time(4 * S), ctx, out)
        return ranked(out)

    async def after():
        op, ctx, tm, backend = _ranking_operator(storage, "wf")
        assert backend.restore_epoch == 1
        await tm.open(op.tables())
        await op.on_start(ctx)
        assert op.emitted_up_to == 2 * S - 1
        assert {ts: sum(b.num_rows for b in bs)
                for ts, bs in op.bins.items()} == {4 * S - 1: 500}
        out = Out()
        await op.process_batch(first, ctx, out)     # a replay: dropped
        assert list(op.bins) == [4 * S - 1]
        await op.handle_watermark(Watermark.event_time(4 * S), ctx, out)
        return ranked(out)

    sound = asyncio.run(before())
    restored = asyncio.run(after())
    assert len(sound) == 800 and restored == sound[300:]
    top = sorted(restored, key=lambda r: r[2])[:5]
    assert [r[2] for r in top] == [1, 2, 3, 4, 5]
    assert top == sorted(
        restored, key=lambda r: (-r[0], -r[1]))[:5]


PARTITION_COLUMNS = {
    "integers": [pa.array([3, 1, 3, 1, 2, 3])],
    "strings": [pa.array(["c", "a", "c", "a", None, "c"])],
    # no hash to be had of a list: such a column partitions by its text
    "lists": [pa.array([[1, 2], [1], [1, 2], [1], [], [1, 2]])],
    "two columns": [pa.array([1, 0, 1, 0, 0, 1]),
                    pa.array(["x", "y", "x", "y", "x", "x"])],
}


@pytest.mark.parametrize("kind", sorted(PARTITION_COLUMNS))
def test_partition_by_columns_rank_each_partition_alone(kind):
    """PARTITION BY columns of any kind give exact partition ids: rows 0,
    2, 5 are one partition, 1 and 3 another, 4 a third; each is ranked by
    `v` descending from 1."""
    from arroyo_tpu.operators.window_fn import WindowFunctionOperator

    parts = PARTITION_COLUMNS[kind]
    table = pa.table(parts + [pa.array([10, 5, 30, 7, 1, 20])],
                     names=[f"p{i}" for i in range(len(parts))] + ["v"])
    op = WindowFunctionOperator({
        "fn": "row_number", "partition_cols": list(range(len(parts))),
        "order_by": [[len(parts), True]], "schema": None,
        "out_field": "row_num"})
    assert op._rank_values(table).tolist() == [3, 2, 1, 1, 1, 2]
