"""Required-field pruning at a source (ISSUE 31): where a source's row
would cross an edge whole (a fan-out, here), the planner narrows the table
to the fields the statements read. The plans, the cases that keep a whole
column or the whole row, the projection's nulls, and NEXmark q7 through the
normal path (controller, embedded worker, conservation ledger) against
`benchmark/reference/q7.py`, with a checkpoint of the parent's plan
restored under the narrowed one."""

import asyncio
import importlib.util
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from arroyo_tpu import obs
from arroyo_tpu.chaos.drill import _run_embedded
from arroyo_tpu.config import update
from arroyo_tpu.engine import Engine
from arroyo_tpu.graph.logical import EdgeType
from arroyo_tpu.metrics import REGISTRY
from arroyo_tpu.obs import audit, timeline
from arroyo_tpu.sql import plan_query, planner
from arroyo_tpu.udf import registry as udfs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = _load("pruning_gen", "gen", "nexmark.py")
reference = _load("pruning_reference", "reference", "q7.py")

RATE = 2000.0            # events/s of event time: 20,000 a 10 s window
ORIGIN_NS = 1_700_000_000 * 10**9
N_EVENTS = 60_000        # 30 s of event time, 55,200 bids
BATCH = 500
PAUSE_AT = 30_000
STREAMS = {"plan": None}

BID3 = pa.struct([("auction", pa.int64()), ("bidder", pa.int64()),
                  ("price", pa.int64())])
NARROW = pa.schema([pa.field("bid", BID3),
                    pa.field("_timestamp", pa.timestamp("ns"), False)])
DECLARED = 26            # NEXmark's 25 leaf fields and `_timestamp`


class Stream:
    """One job's seeded input and what its sink received."""

    def __init__(self, seed, pause_at=None):
        self.seed = seed
        self.pause_at = pause_at
        self.paused = self.released = False
        self.batches = []

    def bids(self):
        ns = np.arange(N_EVENTS, dtype=np.int64)
        is_bid, auction, bidder, price = gen.bids(ns, self.seed)
        ts = gen.event_times(ns[is_bid], ORIGIN_NS, RATE)
        return ts, auction, bidder, price

    def ends(self):
        ts = self.bids()[0]
        size = reference.SIZE_NS
        return range(int(ts[0]) // size * size + size,
                     int(ts[-1]) // size * size + size + 1, size)

    def windows(self):
        """{window end: sorted rows} of what the sink got."""
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


def _register():
    from arroyo_tpu.connectors.base import Connector, register_connector
    from arroyo_tpu.operators.base import (
        Operator, SourceFinishType, SourceOperator)
    from arroyo_tpu.schema import StreamSchema
    from arroyo_tpu.state.table_config import global_table

    schema = StreamSchema.from_fields(gen.FIELDS)

    class Source(SourceOperator):
        def __init__(self, stream):
            super().__init__("pruning_source")
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
            while self.n < N_EVENTS:
                finish = await ctx.check_control(collector)
                if finish is not None:
                    return finish
                if (s.pause_at is not None and self.n >= s.pause_at
                        and not s.released):
                    s.paused = True
                    await asyncio.sleep(0.005)
                    continue
                ns = np.arange(self.n, min(self.n + BATCH, N_EVENTS),
                               dtype=np.int64)
                await collector.collect(gen.gen_batch(
                    ns, gen.event_times(ns, ORIGIN_NS, RATE), s.seed))
                self.n = int(ns[-1]) + 1
                await asyncio.sleep(0)
            return SourceFinishType.FINAL

    class Sink(Operator):
        def __init__(self, stream):
            super().__init__("pruning_sink")
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
        name = "pruning_source"
        description = "tests: a bounded seeded NEXmark stream"
        source = True

        def table_schema(self):
            return schema

        def make_source(self, config, schema):
            return Source(STREAMS[config["feed"]])

    @register_connector
    class SinkConnector(_Base):
        name = "pruning_sink"
        description = "tests: keeps what arrives"
        sink = True

        def make_sink(self, config, schema):
            return Sink(STREAMS[config["feed"]])


_register()


def bench_sql(config, feed_id="plan"):
    """The benchmark's own query text over this file's source and sink."""
    with open(os.path.join(BENCH, "configs", f"{config}.sql")) as f:
        return (f.read().replace("bench_nexmark", "pruning_source")
                .replace("bench_sink", "pruning_sink")
                .replace("{feed}", feed_id))


NEXMARK = ("CREATE TABLE nexmark WITH "
           "(connector = 'pruning_source', feed = 'plan');\n")


def shape(graph):
    """A plan without its closures: nodes with their ops, edges with their
    schemas."""
    return (
        [(n.node_id, n.parallelism,
          [(op.operator.value, op.description) for op in n.chain])
         for n in graph.nodes.values()],
        [(e.src, e.dst, e.edge_type.value, str(e.schema.schema),
          e.schema.key_indices) for e in graph.edges],
    )


def source_edges(plan):
    return [e for e in plan.graph.edges
            if plan.graph.nodes[e.src].is_source]


def sent(plan):
    """{column: its children, or None for a column that is no struct} of
    what the plan's source sends; the same on each of its edges."""
    edges = source_edges(plan)
    assert edges and all(
        e.schema.schema.equals(edges[0].schema.schema) for e in edges)
    return {f.name: ([c.name for c in f.type]
                     if pa.types.is_struct(f.type) else None)
            for f in edges[0].schema.schema}


@pytest.fixture()
def unpruned(monkeypatch):
    """Plan as the parent of ISSUE 31 did: no table is ever narrowed."""
    def as_parent():
        monkeypatch.setattr(planner, "_narrowable_sources",
                            lambda *a, **k: {})
    return as_parent


# -- the plans ---------------------------------------------------------------


def test_q7_fans_out_bid_of_three_children_and_the_timestamp():
    plan = plan_query(bench_sql("nexmark-q7"))
    edges = source_edges(plan)
    assert [(e.src, e.dst, e.edge_type) for e in edges] == [
        (1, 2, EdgeType.FORWARD), (1, 5, EdgeType.FORWARD)]
    for e in edges:
        assert e.schema.schema.equals(NARROW)
    assert plan.source_fields == {"nexmark": (4, DECLARED)}
    # one stateless op behind the watermark, in the source's own node
    assert [op.operator.value for op in plan.graph.nodes[1].chain] == [
        "connector_source", "expression_watermark", "arrow_value"]
    # the consumers' own projections are what they were
    assert plan.graph.out_edges(2)[0].schema.schema.names == [
        "auction", "price", "bidder", "_timestamp"]


@pytest.mark.parametrize("config", ["nexmark-q5", "nexmark-q5-mesh4"])
def test_a_source_with_its_consumer_chained_behind_it_is_left_alone(
        config, unpruned):
    """q5's source is chained with its one projection: the raw row never
    leaves the task, so the plan is the parent's op for op."""
    plan = plan_query(bench_sql(config))
    assert plan.source_fields == {"nexmark": (DECLARED, DECLARED)}
    (edge,) = source_edges(plan)
    assert edge.edge_type is EdgeType.SHUFFLE
    assert edge.schema.schema.names == ["auction", "_timestamp"]
    assert [op.operator.value for op in plan.graph.nodes[1].chain] == [
        "connector_source", "expression_watermark", "arrow_value"]
    unpruned()
    assert shape(plan_query(bench_sql(config)).graph) == shape(plan.graph)


def test_q7s_stateful_operators_keep_the_parents_ids(unpruned):
    """State is filed under (node id, position in the node's chain): a
    checkpoint taken before the projection existed must find the source's,
    the watermark's, both windows' and the join's. Written down from the
    parent's plan (c3e5b57)."""
    want = {(1, 0): "connector_source", (1, 1): "expression_watermark",
            (3, 0): "tumbling_window_aggregate",
            (6, 0): "tumbling_window_aggregate", (10, 0): "instant_join",
            (13, 0): "connector_sink"}

    def ids(plan):
        return {(n.node_id, i): op.operator.value
                for n in plan.graph.nodes.values()
                for i, op in enumerate(n.chain)}

    narrowed = ids(plan_query(bench_sql("nexmark-q7")))
    assert {k: narrowed.get(k) for k in want} == want
    assert narrowed[(1, 2)] == "arrow_value"
    unpruned()
    parent = ids(plan_query(bench_sql("nexmark-q7")))
    assert {k: parent.get(k) for k in want} == want and (1, 2) not in parent
    # every node id of the parent's plan is there, and no other
    assert {n for n, _ in narrowed} == {n for n, _ in parent}


# -- what is kept where the analysis cannot tell ------------------------------

ALL = {"person": gen.PERSON_T, "auction": gen.AUCTION_T, "bid": gen.BID_T}
WHOLE_ROW = {**{k: [c.name for c in t] for k, t in ALL.items()},
             "_timestamp": None}
BID_PRICE = "SELECT bid.price AS p FROM nexmark;\n"


@udfs.udf(pa.int64(), [gen.BID_T], name="pruning_bid_udf")
def pruning_bid_udf(bids):
    return np.zeros(len(bids), dtype=np.int64)


DECLARED_T = """CREATE TABLE t (
  a BIGINT, b BIGINT, c BIGINT, d TEXT, ts TIMESTAMP,
  g BIGINT GENERATED ALWAYS AS (b + 1)
) WITH (connector = 'single_file', path = '/nowhere.json', format = 'json',
        type = 'source', event_time_field = 'ts');
"""

CASES = {
    # `*` reads whatever the table declares
    "star": (NEXMARK + BID_PRICE + "SELECT * FROM nexmark;", WHOLE_ROW),
    "qualified star": (
        NEXMARK + BID_PRICE + "SELECT nexmark.* FROM nexmark;", WHOLE_ROW),
    # a struct handed over whole keeps every child
    "a whole struct in a select list": (
        NEXMARK + BID_PRICE + "SELECT bid FROM nexmark;",
        {"bid": WHOLE_ROW["bid"], "_timestamp": None}),
    "a whole struct as a function's argument": (
        NEXMARK + BID_PRICE
        + "SELECT coalesce(auction, auction) IS NULL AS n FROM nexmark;",
        {"auction": WHOLE_ROW["auction"], "bid": ["price"],
         "_timestamp": None}),
    "a udf over a struct": (
        NEXMARK + BID_PRICE + "SELECT pruning_bid_udf(bid) AS u FROM nexmark;",
        {"bid": WHOLE_ROW["bid"], "_timestamp": None}),
    # a join's sides pass every column into its state
    "one table under two aliases in a join": (
        NEXMARK + "SELECT a.bid.price AS p, b.bid.bidder AS q FROM nexmark a "
        "JOIN nexmark b ON a.bid.auction = b.bid.auction;", WHOLE_ROW),
    # readers are taken together, wherever they stand
    "a view and a cte that read different children": (
        NEXMARK + "CREATE VIEW v AS SELECT bid.price AS p FROM nexmark;\n"
        "SELECT p FROM v;\n"
        "WITH c AS (SELECT bid.auction AS a FROM nexmark WHERE "
        "person IS NULL) SELECT a FROM c;",
        {"person": ["id"], "bid": ["auction", "price"], "_timestamp": None}),
    # what the table's own DDL names stays, read or not
    "generated and event-time columns": (
        DECLARED_T + "SELECT a FROM t;\nSELECT a + 1 AS a1 FROM t;",
        {"a": None, "b": None, "ts": None, "g": None, "_timestamp": None}),
    # `bid.auction` with `bid` the table's alias is the COLUMN auction;
    # with `bid` the struct it is the child: both readings are kept
    "a child named like a column, an alias named like a struct": (
        NEXMARK + "SELECT bid.auction AS x FROM nexmark bid;\n"
        "SELECT bid.price AS p FROM nexmark bid;",
        {"auction": WHOLE_ROW["auction"], "bid": ["auction", "price"],
         "_timestamp": None}),
    # q7's own case: the child `auction` of `bid` does not keep the column
    "a child named like a column": (
        NEXMARK + "SELECT bid.auction AS x FROM nexmark;\n" + BID_PRICE,
        {"bid": ["auction", "price"], "_timestamp": None}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_what_the_analysis_cannot_resolve_keeps_the_column_or_the_row(case):
    """Planning binds every expression against the narrowed schema (columns
    by index, children by name): a plan that comes out read nothing that
    was dropped."""
    sql, want = CASES[case]
    plan = plan_query(sql)
    assert len(source_edges(plan)) >= 2, "no fan-out: the case tests nothing"
    assert sent(plan) == want


# -- the projection -----------------------------------------------------------


def test_a_narrowed_struct_is_null_exactly_where_the_sources_was():
    plan = plan_query(bench_sql("nexmark-q7"))
    project = plan.graph.nodes[1].chain[2].config["py_fn"]
    ns = np.arange(8192, dtype=np.int64)
    raw = gen.gen_batch(ns, gen.event_times(ns, ORIGIN_NS, 25_000.0), 31)
    bid = raw.column(raw.schema.names.index("bid"))
    assert bid.null_count == 656        # the persons and the auctions
    out = project(raw)
    assert out.schema.equals(NARROW) and out.num_rows == 8192
    got = out.column(0)
    got.validate(full=True)
    assert got.null_count == 656 and got.is_null().equals(bid.is_null())
    for child in ("auction", "bidder", "price"):
        assert pc.struct_field(got, child).equals(pc.struct_field(bid, child))
    # `_timestamp` is the source's own column, not a copy of it
    ts = raw.column(raw.schema.names.index("_timestamp"))
    assert out.column(1).buffers()[1].address == ts.buffers()[1].address
    # a slice of a batch keeps its rows
    part = project(raw.slice(100, 50))
    assert part.column(0).equals(got.slice(100, 50))


# -- the engine ---------------------------------------------------------------


def task_flow():
    snap = REGISTRY.snapshot()
    flow = {}
    for i, name in enumerate(("arroyo_worker_messages_recv",
                              "arroyo_worker_messages_sent")):
        for labels, value in snap.get(name, []):
            if labels.get("job") == "pruning-q7":
                flow.setdefault(labels.get("task"), [0, 0])[i] = int(value)
    return {tuple(v) for v in flow.values()}


def test_q7_through_the_normal_path_equals_the_reference(tmp_path):
    obs.reset()
    stream = Stream(31)
    STREAMS["normal"] = stream
    try:
        _run_embedded(
            bench_sql("nexmark-q7", "normal"), "pruning-q7",
            str(tmp_path / "ck"), 1, 1, max_restarts=0,
            heartbeat_interval=0.1, heartbeat_timeout=30.0,
            checkpoint_interval=0.1, timeout=180.0)
    finally:
        del STREAMS["normal"]
    ts, auction, bidder, price = stream.bids()
    ends = stream.ends()
    # answers and conservation, both off by 0
    assert stream.windows() == reference.compute(
        ts, auction, bidder, price, ends)
    booked = task_flow()
    for _what, rows_in, rows_out in reference.flows(
            ts, auction, bidder, price, ends):
        assert (rows_in, rows_out) in booked
    # every edge attested at both ends over everything it carries
    status = audit.status()
    job = status["jobs"]["pruning-q7"]
    assert job["breach_count"] == 0 and job["epochs_reconciled"] >= 1
    assert len(job["edges"]) == 7
    for edge in job["edges"].values():
        assert edge["ok"] and edge["tx"] == edge["rx"]
    assert job["rows_attested"] > 0
    assert job["edges"]["1:0->2:0"]["tx"] == job["edges"]["1:0->5:0"]["tx"]
    # and no string was hashed to do it: none crosses an edge any more
    assert status["string_columns_hashed"] == 0
    assert "audit.fp.str" not in timeline.totals()
    booked = timeline.totals(job="pruning-q7")["plan.prune"]
    assert (booked["count"], booked["n"], booked["padded"]) == (
        1, 4, DECLARED)
    obs.reset()


def run_q7(stream, storage, stop_at_pause):
    feed_id = f"s{id(stream)}"
    STREAMS[feed_id] = stream

    async def go():
        plan = plan_query(bench_sql("nexmark-q7", feed_id))
        eng = Engine(plan.graph, job_id="pruning-restore",
                     storage_url=storage).start()
        if stop_at_pause:
            while not stream.paused:
                await asyncio.sleep(0.01)
            await eng.checkpoint_and_wait(then_stop=True)
            stream.released = True
        await eng.join(180)
        return plan

    try:
        with update(tpu={"require_accelerator": False}):
            return asyncio.run(go())
    finally:
        STREAMS.pop(feed_id, None)


def test_a_checkpoint_of_the_parents_plan_restores_under_the_narrowed_one(
        tmp_path, monkeypatch):
    storage = str(tmp_path / "ckpt")
    stream = Stream(32, PAUSE_AT)
    with monkeypatch.context() as m:
        m.setattr(planner, "_narrowable_sources", lambda *a, **k: {})
        before = run_q7(stream, storage, stop_at_pause=True)
    assert before.source_fields == {"nexmark": (DECLARED, DECLARED)}
    seen = stream.windows()
    ts, auction, bidder, price = stream.bids()
    want = reference.compute(ts, auction, bidder, price, stream.ends())
    assert 0 < len(seen) < len(want)
    after = run_q7(stream, storage, stop_at_pause=False)
    assert after.source_fields == {"nexmark": (4, DECLARED)}
    assert stream.windows() == want
