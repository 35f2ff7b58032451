"""NEXmark q5 at parallelism four (ISSUE 30): the benchmark's own query text
over a seeded NEXmark stream with the hot auction, through planner, engine
and window operators, with keyed window state sharded over four of the
virtual CPU devices `tests/conftest.py` provides.

What ties the shards to the whole: (a) the job at `tpu.mesh_devices` 4 and
the job at 0 emit, window for window, the rows `benchmark/reference/q5.py`
computes from the same bids, under both exchanges; (b) at a barrier the
four shards' live (bin, key) sets are disjoint, each on the shard its hash
owns, their union and their counts the one-device operator's, and a
checkpoint taken on four restores on four to the same answers; (c) the
mesh path books the one-device path's leaves plus its own counts; (d)
every jitted mesh program has a name of its own in a device trace.
"""

import asyncio
import importlib.util
import os

import numpy as np
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


gen = _load("mesh_q5_gen", "gen", "nexmark.py")
reference = _load("mesh_q5_reference", "reference", "q5.py")

RATE = 2000.0            # events/s of event time: 20,000 a 10 s window
ORIGIN_NS = 1_700_000_000 * 10**9
N_EVENTS = 50_000        # 25 s of event time, 46,000 bids
BATCH = 500
PAUSE_AT = 30_000
MESH_ONLY = {"mesh.ship", "mesh.ship.hot", "mesh.combine"}
STREAMS = {}


class Stream:
    """One job's seeded input and what its sink received."""

    def __init__(self, seed, pause_at=None):
        self.seed = seed
        self.pause_at = pause_at
        self.paused = False
        self.released = False
        self.batches = []

    def bids(self):
        ns = np.arange(N_EVENTS, dtype=np.int64)
        is_bid, auction, bidder, price = gen.bids(ns, self.seed)
        ts = gen.event_times(ns[is_bid], ORIGIN_NS, RATE)
        return ts, auction, bidder, price

    def windows(self):
        """{window end: sorted (auction, num)} of what the sink got."""
        import pyarrow as pa

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
            super().__init__("mesh_q5_source")
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
            super().__init__("mesh_q5_sink")
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
        name = "mesh_q5_source"
        description = "tests: a bounded seeded NEXmark stream"
        source = True

        def table_schema(self):
            return schema

        def make_source(self, config, schema):
            return Source(STREAMS[config["feed"]])

    @register_connector
    class SinkConnector(_Base):
        name = "mesh_q5_sink"
        description = "tests: keeps what arrives"
        sink = True

        def make_sink(self, config, schema):
            return Sink(STREAMS[config["feed"]])


_register()


def sql_for(feed_id):
    with open(os.path.join(BENCH, "configs", "nexmark-q5-mesh4.sql")) as f:
        return (f.read().replace("bench_nexmark", "mesh_q5_source")
                .replace("bench_sink", "mesh_q5_sink")
                .replace("{feed}", feed_id))


def keyed_window_op(eng):
    """The hop operator that counts per auction (the window max over its
    closes is a tumbling one)."""
    ops = [op for sub in eng.program.subtasks for op in sub.runner.ops
           if type(op).__name__ == "SlidingWindowOperator"]
    assert len(ops) == 1, ops
    return ops[0]


def snapshot(op):
    """{(bin, key): (slot, count)} of an operator's live state."""
    if hasattr(op.acc, "flush"):
        op.acc.flush()
    entries = list(op.dir.items())
    slots = np.asarray([s for _b, _k, s in entries], dtype=np.int64)
    counts = np.asarray(op.acc.gather(slots)[0])
    return {(b, tuple(k)): (int(s), int(c))
            for (b, k, s), c in zip(entries, counts.tolist())}


def run_q5(seed, mesh, exchange="auto", salted="auto", pause=None,
           storage=None, job_id="mesh-q5", stream=None):
    """Run the query over the seeded stream; returns (stream, what `pause`
    saw). `pause(eng)` runs at event PAUSE_AT, after a checkpoint whose
    barrier every operator has passed: 'stop' ends the job there."""
    stream = stream or Stream(seed, PAUSE_AT if pause else None)
    feed_id = f"s{id(stream)}"
    STREAMS[feed_id] = stream
    seen = {}
    settings = {"tpu": {"mesh_devices": mesh, "mesh_exchange": exchange,
                        "mesh_salted_tier": salted,
                        "require_accelerator": False}}

    async def go():
        plan = plan_query(sql_for(feed_id), parallelism=1)
        eng = Engine(plan.graph, job_id=job_id, storage_url=storage).start()
        if stream.pause_at is not None and not stream.released:
            while not stream.paused:
                await asyncio.sleep(0.01)
            if pause == "stop":
                await eng.checkpoint_and_wait(then_stop=True)
            else:
                await eng.checkpoint_and_wait()
                seen["pause"] = pause(eng)
            stream.released = True
        await eng.join(180)

    try:
        with update(**settings):
            asyncio.run(go())
    finally:
        STREAMS.pop(feed_id, None)
    return stream, seen.get("pause")


def expected_windows(stream):
    ts, auction, bidder, price = stream.bids()
    slide, size = reference.SLIDE_NS, reference.SIZE_NS
    first = int(ts[0]) // slide * slide + slide
    last = int(ts[-1]) // slide * slide + size
    want = reference.compute(ts, auction, bidder, price,
                             range(first, last + 1, slide))
    return {end: rows for end, rows in want.items() if rows}


def _need_four():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")


@pytest.fixture(scope="module")
def one_device():
    """The job at tpu.mesh_devices 0 (ops/aggregates.py's accumulator),
    paused at a barrier for its state, with the ledger of its run."""
    timeline.clear()
    stream, snap = run_q5(31, 0, pause=lambda eng: snapshot(
        keyed_window_op(eng)), job_id="mesh-q5-one")
    return {"windows": stream.windows(), "snapshot": snap,
            "ledger": timeline.totals(), "want": expected_windows(stream)}


@pytest.fixture(scope="module")
def four_shards():
    """The job at tpu.mesh_devices 4, exchange `device`, the window max
    salted over the mesh as on real chips."""
    _need_four()
    from arroyo_tpu.parallel.sharded_state import MESH_STATS

    def look(eng):
        op = keyed_window_op(eng)
        snap = snapshot(op)     # flushes what the accumulator buffered
        state = np.asarray(op.acc.state[0])
        return {"snapshot": snap, "shards": op.dir.n_shards,
                "live_locals": [set(np.nonzero(state[s])[0].tolist())
                                for s in range(op.dir.n_shards)],
                "owner_of": lambda keys: op.dir.owners_for(
                    [np.asarray(keys, dtype=np.int64)], len(keys))}

    timeline.clear()
    before = dict(MESH_STATS)
    stream, seen = run_q5(31, 4, "device", "mesh", pause=look,
                          job_id="mesh-q5-four")
    return {"windows": stream.windows(), "ledger": timeline.totals(),
            "mesh": {k: MESH_STATS[k] - before[k] for k in MESH_STATS},
            **seen}


def test_the_one_device_job_emits_the_references_windows(one_device):
    assert len(one_device["want"]) >= 15
    assert one_device["windows"] == one_device["want"]


@pytest.mark.parametrize("exchange,salted", [("device", "mesh"),
                                             ("host_fed", "auto")])
def test_four_shards_emit_the_references_windows(one_device, exchange,
                                                 salted):
    _need_four()
    stream, _ = run_q5(31, 4, exchange, salted,
                       job_id=f"mesh-q5-{exchange}")
    got = stream.windows()
    assert got == one_device["want"]
    assert got == one_device["windows"]


def test_the_shards_are_disjoint_and_their_union_is_the_whole(
        one_device, four_shards):
    from arroyo_tpu.parallel.sharded_state import STRIDE

    mesh, whole = four_shards["snapshot"], one_device["snapshot"]
    # the same (bin, key) groups with the same counts, window for window
    assert len(whole) > 1000
    assert {g: c for g, (_s, c) in mesh.items()} == {
        g: c for g, (_s, c) in whole.items()}
    # each on the shard its key's hash owns, whatever the bin: a hot
    # auction's hop bins all live on ONE shard
    groups = sorted(mesh)
    owners = four_shards["owner_of"]([k[0] for _b, k in groups])
    by_key = {}
    for (b, k), owner in zip(groups, owners.tolist()):
        assert mesh[(b, k)][0] // STRIDE == owner
        assert by_key.setdefault(k, owner) == owner
    assert len(set(by_key.values())) == four_shards["shards"] == 4
    # and on the devices: a shard's live slots are its directory's own
    for shard, live in enumerate(four_shards["live_locals"]):
        mine = {s % STRIDE for s, _c in mesh.values()
                if s // STRIDE == shard}
        assert mine and live == mine


def test_a_checkpoint_taken_on_four_restores_on_four(one_device, tmp_path):
    _need_four()
    storage = str(tmp_path / "ckpt")
    stream, _ = run_q5(31, 4, "device", "mesh", pause="stop",
                       storage=storage, job_id="mesh-q5-restore")
    before = stream.windows()
    assert 0 < len(before) < len(one_device["want"])
    run_q5(31, 4, "device", "mesh", storage=storage,
           job_id="mesh-q5-restore", stream=stream)
    assert stream.windows() == one_device["want"]


def test_the_mesh_books_the_one_device_leaves_and_its_counts(
        one_device, four_shards):
    led, one = four_shards["ledger"], one_device["ledger"]
    for leaf in ("agg.pack", "agg.enqueue", "agg.gather", "agg.read"):
        assert led[leaf]["count"] > 0 and one[leaf]["count"] > 0, leaf
        assert led[leaf]["total_s"] > 0
    # the real rows of a step and what its buffers held
    for led_ in (led, one):
        assert 0 < led_["agg.enqueue"]["n"] <= led_["agg.enqueue"]["padded"]
    assert led["agg.pack"]["n"] == led["agg.enqueue"]["n"]
    ship, hot = led["mesh.ship"], led["mesh.ship.hot"]
    assert ship["count"] == hot["count"] > 0 and ship["total_s"] == 0
    assert 0 < ship["n"] <= ship["padded"]
    assert hot["padded"] == ship["n"]
    # a hot auction's bins live on one shard: a flush that spans few hot
    # auctions (here ~2; a chip's 60,000-row flush ~39) is far from even
    assert ship["n"] / 4 < hot["n"] <= ship["n"]
    # the counters the benchmark snapshots are the same counts
    mesh = four_shards["mesh"]
    assert mesh["rows_sent"] == ship["n"]
    assert mesh["rows_sent"] + mesh["rows_padded"] == ship["padded"]
    assert mesh["rows_busiest"] == hot["n"]
    # one table serves both paths: what the mesh books beyond the
    # one-device job is its own counts, and that job books none of them
    assert not MESH_ONLY & set(one)
    assert set(led) - set(one) <= MESH_ONLY


def test_the_program_summary_carries_the_steps_rows(four_shards):
    from arroyo_tpu.obs import device as obs_device

    route = obs_device.summary()["programs"]["mesh.route"]
    assert 0 < route["rows"] <= route["padded_rows"]
    assert route["rows"] >= four_shards["mesh"]["rows_sent"]


def test_the_host_fed_route_books_its_combiner_and_a_reset():
    _need_four()
    import jax

    from arroyo_tpu.ops.aggregates import AggSpec
    from arroyo_tpu.parallel import ShardedAccumulator, key_mesh
    from arroyo_tpu.parallel.sharded_state import MeshSlotDirectory

    acc = ShardedAccumulator([AggSpec("count", None, "num")],
                             key_mesh(jax.devices()[:4]), exchange="host_fed")
    d = MeshSlotDirectory(4)
    keys = np.where(np.arange(4000) % 2 == 0, 7, np.arange(4000))
    slots = d.assign(np.zeros(4000, dtype=np.int64), [keys])
    timeline.clear()
    acc.update(slots, {})
    uniq = np.unique(slots)
    got = acc.gather(uniq)[0]
    assert int(np.asarray(got).sum()) == 4000
    acc.reset_slots(uniq)
    assert int(np.asarray(acc.gather(uniq)[0]).sum()) == 0
    led = timeline.totals()
    assert led["mesh.combine"]["n"] == 4000
    assert led["mesh.ship"]["n"] == len(uniq) == led["agg.enqueue"]["n"]
    assert led["agg.reset"]["count"] == 1
    assert led["agg.gather"]["count"] == led["agg.read"]["count"] == 2


def test_every_mesh_program_has_its_own_name_in_a_trace():
    """A device trace names a program `jit_<function>`: each jitted mesh
    function is named for its program (`mesh.route` -> `mesh_route`)."""
    _need_four()
    import jax

    from arroyo_tpu.ops.aggregates import AggSpec
    from arroyo_tpu.parallel import ShardedAccumulator, key_mesh

    mesh = key_mesh(jax.devices()[:4])
    keyed = ShardedAccumulator([AggSpec("count", None, "num")], mesh,
                               exchange="device")
    salted = ShardedAccumulator([AggSpec("count", None, "num")], mesh, salted=True)
    programs = [
        keyed._route_step(16, 16), keyed._direct_step(),
        keyed._sliced_gather_program(), keyed._sliced_take_program(),
        keyed._sliced_reset_program(), keyed._sliced_restore_program(),
        salted._gather_program(), salted._take_program(),
        salted._reset_program(), salted._restore_program(),
        salted._gather_free_program(),
    ]
    names = {p.program: p.fn.__name__ for p in programs}
    assert names == {p: p.replace(".", "_") for p in (
        "mesh.route", "mesh.step_direct", "mesh.sgather",
        "mesh.stake", "mesh.sreset", "mesh.srestore", "mesh.gather",
        "mesh.take", "mesh.reset", "mesh.restore", "mesh.gather_free")}
    # and that is the module's name where XLA compiles it
    slots = np.arange(64, dtype=np.int64).reshape(4, 16)
    text = programs[0].fn.lower(
        keyed.state, slots, np.ones((4, 16), dtype=np.int64)).as_text()
    assert "jit_mesh_route" in text.split("\n", 1)[0]
