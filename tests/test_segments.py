"""Fused segment runtime: plan-time fusion and the one composed view a
fused segment runs — value-identical to the unfused per-operator plan,
nothing held between batches."""

import asyncio
import json
import types

import pyarrow as pa
import pytest

from arroyo_tpu.config import update
from arroyo_tpu.engine import Engine
from arroyo_tpu.engine.segments import FusedSegmentOperator, plan_runs
from arroyo_tpu.graph.logical import OperatorName
from arroyo_tpu.metrics import REGISTRY
from arroyo_tpu.sql import plan_query

NEXMARK_DDL = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '20000', message_count = '40000',
  start_time = '0'
);
"""

CHAIN_SQL = NEXMARK_DDL + """
CREATE TABLE sink (
  auction BIGINT, price_eur BIGINT, bidder BIGINT
) WITH (connector = 'blackhole', type = 'sink');
INSERT INTO sink
SELECT auction, price_eur, bidder FROM (
  SELECT auction, price_eur - price_eur % 10 AS price_eur, bidder FROM (
    SELECT bid.auction as auction, bid.price * 100 / 121 as price_eur,
           bid.bidder as bidder
    FROM nexmark WHERE bid IS NOT NULL
  )
);
"""

PREVIEW_SQL = NEXMARK_DDL + """
SELECT auction, price_eur - price_eur % 10 AS price_eur, bidder FROM (
  SELECT bid.auction as auction, bid.price * 100 / 121 as price_eur,
         bid.bidder as bidder
  FROM nexmark WHERE bid IS NOT NULL
);
"""


def canon(rows):
    return sorted(json.dumps(r, sort_keys=True, default=str) for r in rows)


def run_engine(sql, results=None, timeout=120):
    plan = plan_query(sql, preview_results=results)

    async def go():
        eng = Engine(plan.graph).start()
        await eng.join(timeout)

    asyncio.run(go())
    return plan


def seg_counts():
    snap = REGISTRY.snapshot()
    disp = sum(
        v for _l, v in snap.get("arroyo_segment_dispatches_total", [])
    )
    batches = sum(
        v for _l, v in snap.get("arroyo_segment_batches_total", [])
    )
    return disp, batches


# -- plan-time fusion --------------------------------------------------------


def test_plan_fuses_stateless_run_into_one_segment():
    with update(engine={"segment_fusion": True}):
        plan = plan_query(CHAIN_SQL)
    segs = [
        op
        for n in plan.graph.nodes.values()
        for op in n.chain
        if op.operator == OperatorName.FUSED_SEGMENT
    ]
    assert len(segs) == 1
    # select + normalize + select + sink_cast
    assert len(segs[0].config["ops"]) == 4
    # no stray members left behind
    assert not any(
        op.config.get("segment_member")
        for n in plan.graph.nodes.values()
        for op in n.chain
    )


def test_fusion_off_annotates_members_for_ab_accounting():
    with update(engine={"segment_fusion": False}):
        plan = plan_query(CHAIN_SQL)
    members = [
        op
        for n in plan.graph.nodes.values()
        for op in n.chain
        if op.config.get("segment_member")
    ]
    leads = [op for op in members if op.config.get("segment_lead")]
    assert len(members) == 4 and len(leads) == 1
    assert not any(
        op.operator == OperatorName.FUSED_SEGMENT
        for n in plan.graph.nodes.values()
        for op in n.chain
    )


def test_single_value_op_runs_are_not_fused():
    from arroyo_tpu.graph.logical import ChainedOp

    chain = [
        ChainedOp(OperatorName.CONNECTOR_SOURCE, {}),
        ChainedOp(OperatorName.ARROW_VALUE, {}),
        ChainedOp(OperatorName.TUMBLING_WINDOW_AGGREGATE, {}),
        ChainedOp(OperatorName.ARROW_VALUE, {}),
        ChainedOp(OperatorName.ARROW_VALUE, {}),
    ]
    assert plan_runs(chain) == [(3, 5)]


def test_segment_config_json_round_trips_nested_op_lists():
    """FUSED_SEGMENT configs nest member op dicts in a LIST — the config
    (un)serializer must recurse through lists (StreamSchema and bytes
    values inside member configs survive the round trip)."""
    from arroyo_tpu.graph.logical import _config_json, _config_unjson
    from arroyo_tpu.schema import StreamSchema

    schema = StreamSchema(
        pa.schema([pa.field("a", pa.int64()), pa.field("b", pa.float64())]),
        (0,),
    )
    cfg = {
        "ops": [
            {"operator": "arrow_value",
             "config": {"schema": schema, "blob": b"\x01\x02"},
             "description": "select"},
            {"operator": "arrow_key", "config": {}, "description": "key"},
        ],
        "schema": schema,
    }
    out = _config_unjson(json.loads(json.dumps(_config_json(cfg))))
    assert out["ops"][0]["config"]["blob"] == b"\x01\x02"
    rt = out["ops"][0]["config"]["schema"]
    assert rt.schema.equals(schema.schema)
    assert tuple(rt.key_indices) == (0,)
    assert out["ops"][1] == {"operator": "arrow_key", "config": {},
                             "description": "key"}


# -- execution ---------------------------------------------------------------


def nested(inner_select, where, outer="auction, v"):
    return NEXMARK_DDL + f"""
SELECT {outer} FROM (
  SELECT {inner_select} FROM nexmark WHERE {where}
);
"""


FUSED_VS_UNFUSED = {
    "nexmark_chain": (PREVIEW_SQL, 512),
    # a person is one event in fifty: at 16 rows a batch most batches
    # hold none, and the segment drops them whole
    "all_filtered_batches": (nested(
        "person.id AS auction, person.id * 3 AS v",
        "person IS NOT NULL"), 16),
    # no IS NOT NULL: a person's or an auction's row has a null `bid`, so
    # nulls reach the kleene AND / OR, which keep (true OR null) and drop
    # (true AND null) as the unfused kernels do
    "nulls_reach_kleene_and_or": (nested(
        "bid.auction AS auction, bid.price AS v, "
        "(bid.price > 500 OR bid.auction % 2 = 0) AS either, "
        "(bid.price > 500 AND bid.auction % 2 = 0) AS both",
        "(bid.price > 100 OR person IS NOT NULL) "
        "AND (bid.auction % 3 = 0 OR bid.bidder % 2 = 0)",
        outer="auction, v, either, both"), 128),
    "numeric_chain_cast_between": (nested(
        "bid.auction AS auction, "
        "CAST(bid.price AS DOUBLE) / 3 AS v, "
        "CAST(bid.price / 7 AS INT) AS small, -bid.bidder AS neg",
        "bid IS NOT NULL AND bid.price BETWEEN 100 AND 100000 "
        "AND bid.auction NOT BETWEEN 1010 AND 1020",
        outer="auction, v + small AS v, neg % 5 AS m"), 512),
    # q1's currency conversion as NEXmark writes it, and libm besides
    "float64_chain": (nested(
        "bid.auction AS auction, bid.price * 0.908 AS v",
        "bid IS NOT NULL",
        outer="auction, CAST(v AS BIGINT) AS eur, sqrt(v) AS r, "
              "ln(v + 1.0) AS l, v - floor(v) AS frac"), 512),
}


@pytest.mark.parametrize("case", list(FUSED_VS_UNFUSED))
def test_fused_output_byte_identical_to_unfused(case):
    sql, batch_rows = FUSED_VS_UNFUSED[case]
    outs = {}
    for fusion in (True, False):
        REGISTRY.reset()
        with update(engine={"segment_fusion": fusion},
                    tpu={"enabled": False},
                    pipeline={"source_batch_size": batch_rows}):
            results = []
            plan = run_engine(sql, results)
            outs[fusion] = results
        fused = [op for n in plan.graph.nodes.values() for op in n.chain
                 if op.operator == OperatorName.FUSED_SEGMENT]
        assert bool(fused) == fusion, "the case's chain did not fuse"
    assert len(outs[True]) == len(outs[False]) > 0
    assert canon(outs[True]) == canon(outs[False])


def test_dispatches_per_batch_collapse_at_least_3x():
    dpb = {}
    for fusion in (True, False):
        REGISTRY.reset()
        with update(engine={"segment_fusion": fusion},
                    tpu={"enabled": False}):
            run_engine(CHAIN_SQL)
        disp, batches = seg_counts()
        assert batches > 0
        dpb[fusion] = disp / batches
    assert dpb[True] == pytest.approx(1.0)
    assert dpb[False] / dpb[True] >= 3.0


def test_windowed_aggregate_downstream_of_segment_is_exact():
    """A tumbling aggregate fed by a fused segment must see every
    pre-watermark row before the watermark (or window counts would drop
    rows)."""
    sql = NEXMARK_DDL + """
    CREATE TABLE sink (a BIGINT, c BIGINT)
    WITH (connector = 'blackhole', type = 'sink');
    INSERT INTO sink
    SELECT auction, count(*) FROM (
      SELECT auction, price_eur FROM (
        SELECT bid.auction as auction,
               bid.price * 100 / 121 as price_eur
        FROM nexmark WHERE bid IS NOT NULL
      )
    )
    GROUP BY 1, tumble(interval '5 second');
    """
    outs = {}
    for fusion in (True, False):
        REGISTRY.reset()
        with update(engine={"segment_fusion": fusion},
                    tpu={"enabled": False},
                    pipeline={"source_batch_size": 128}):
            plan = plan_query(sql)
            segs = [
                op for n in plan.graph.nodes.values() for op in n.chain
                if op.operator == OperatorName.FUSED_SEGMENT
            ]
            if fusion:
                assert segs, "chain did not fuse"
            results = []
            run_engine(sql, results)
            outs[fusion] = results
    assert canon(outs[True]) == canon(outs[False])


def test_segment_emits_before_the_watermark_and_holds_nothing():
    """The view emits eagerly: a batch's output is collected inside
    `process_batch`, so a watermark behind it passes through unchanged
    and in order, and a barrier and a close find nothing to flush — the
    operator leaves all three to `Operator`'s defaults."""
    from arroyo_tpu.operators.base import Operator
    from arroyo_tpu.types import CheckpointBarrier, Watermark

    with update(engine={"segment_fusion": True}):
        plan = plan_query(CHAIN_SQL)
    seg = next(op for n in plan.graph.nodes.values() for op in n.chain
               if op.operator == OperatorName.FUSED_SEGMENT)
    op = FusedSegmentOperator(seg.config["ops"], None, "t")
    for hook in ("handle_watermark", "handle_checkpoint", "on_close"):
        assert getattr(type(op), hook) is getattr(Operator, hook), hook
    assert not hasattr(op, "drain") and not hasattr(op, "is_fused_segment")

    batches = []
    orig = FusedSegmentOperator.process_batch

    async def cap(self, batch, ctx, collector, input_index=0):
        batches.append(batch)
        return await orig(self, batch, ctx, collector, input_index)

    FusedSegmentOperator.process_batch = cap
    try:
        run_engine(CHAIN_SQL)
    finally:
        FusedSegmentOperator.process_batch = orig
    batch = next(b for b in batches if op._run_host(b) is not None)

    events = []

    class Collector:
        async def collect(self, out):
            events.append(("batch", out.num_rows))

    ctx = types.SimpleNamespace(
        task_info=types.SimpleNamespace(job_id="j", task_id="t-0"))

    async def go():
        col = Collector()
        await op.process_batch(batch, ctx, col)
        wm = Watermark.event_time(7)
        events.append(("watermark", await op.handle_watermark(wm, ctx, col)))
        await op.handle_checkpoint(CheckpointBarrier(1, 0, 0, False), ctx, col)
        events.append(("barrier", None))
        assert await op.on_close(ctx, col, True) is None
        return wm

    wm = asyncio.run(go())
    assert events == [("batch", op._run_host(batch).num_rows),
                      ("watermark", wm), ("barrier", None)]


# -- metrics / observability -------------------------------------------------


def test_segment_families_and_summary():
    REGISTRY.reset()
    with update(engine={"segment_fusion": True}, tpu={"enabled": False}):
        run_engine(CHAIN_SQL)
    from arroyo_tpu.obs import device as obs_device

    summ = obs_device.summary()
    assert summ["segments"], "device summary carries no segment ledger"
    (name, entry), = list(summ["segments"].items())[:1] or [(None, None)]
    assert name and name.startswith("segment.")
    assert entry.get("fused_ops") == 4
    assert entry.get("host_dispatches", 0) > 0


def test_exposition_includes_segment_families():
    REGISTRY.reset()
    with update(engine={"segment_fusion": True}, tpu={"enabled": False}):
        run_engine(CHAIN_SQL)
    text = REGISTRY.expose()
    assert "arroyo_segment_dispatch_seconds" in text
    assert "arroyo_segment_fused_ops" in text
    assert "arroyo_segment_dispatches_total" in text
