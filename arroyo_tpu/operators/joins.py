"""Join operators: instant (windowed) join and expiring non-windowed join.

Capability parity with the reference's join operators
(/root/reference/crates/arroyo-worker/src/arrow/instant_join.rs:412,
join_with_expiration.rs:264): the instant join buffers left/right rows per
zero-width bin (rows of the same emitted window share one _timestamp) and
joins bin-by-bin when the watermark passes; the expiring join buffers both
sides in time-key state with a TTL and emits matches symmetrically as rows
arrive. The bin-local equi-join runs on Arrow's C++ hash join
(pa.Table.join); residual predicates carry ON-clause semantics — a plain
post-filter for inner joins, and for outer joins an inner+residual pass
followed by an anti-join that re-emits unmatched preserved-side rows
null-padded (see _join_tables).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa

from ..engine.construct import register_operator
from ..graph.logical import OperatorName
from ..obs import timeline
from ..schema import StreamSchema, TIMESTAMP_FIELD
from ..types import WatermarkKind
from ..utils.logging import get_logger
from .base import Operator

logger = get_logger("joins")

_JOIN_TYPE_MAP = {
    "inner": "inner",
    "left": "left outer",
    "right": "right outer",
    "full": "full outer",
}


class JoinBase(Operator):
    flow_class = "buffering"  # buffers both sides; emits on match/expiry

    def __init__(self, config: dict, name: str):
        super().__init__(name)
        self.n_keys = int(config["n_keys"])
        self.join_type = config["join_type"]
        self.out_schema: StreamSchema = config["schema"]
        self.left_fields: List[str] = config["left_fields"]
        self.right_fields: List[str] = config["right_fields"]
        self.left_schema = config.get("left_schema")  # StreamSchema of jl
        self.right_schema = config.get("right_schema")
        self.residual = config.get("residual_py")
        self._host_join_reasons: set = set()

    def _note_host_join(self, reason: str) -> None:
        """A join that ran on the arrow host join although the device
        probe is active; each reason is logged once."""
        if reason not in self._host_join_reasons:
            self._host_join_reasons.add(reason)
            logger.info("join %s: host join with the device probe "
                        "active: %s", self.name, reason)

    def _filter_to_range(self, batch: pa.RecordBatch, ctx):
        """Row-level key-range filter for restored state: replays every
        pre-restart subtask's buffers but keeps only rows this subtask owns
        (same hash as the shuffle on the __key columns) — restore after
        rescale re-reads overlapping ranges like the window operators."""
        p = ctx.task_info.parallelism
        if p <= 1:
            return batch
        from ..types import server_for_hash_array

        schema = StreamSchema(batch.schema, tuple(range(self.n_keys)))
        owners = server_for_hash_array(schema.hash_keys(batch), p)
        mask = owners == ctx.task_info.task_index
        if mask.all():
            return batch
        if not mask.any():
            return None
        return batch.filter(pa.array(mask))

    def _device_inner_join(
        self, left_nt: pa.Table, right_nt: pa.Table
    ) -> Optional[pa.Table]:
        """Bin-local inner equi-join via the jitted device probe
        (ops/device_join.py), producing the same column layout as
        pa.Table.join(..., coalesce_keys=True, right_suffix='_right').
        Returns None when the device path doesn't apply (probe tier
        off, join below the row floor, key types the probe can't code) —
        the caller runs the arrow host join; why is logged once per
        reason."""
        from ..config import config
        from ..ops import device_join
        from ..ops._jax import device_join_active

        if not device_join_active():
            return None  # host tier: said once at open
        floor = config().tpu.device_join_min_rows
        if left_nt.num_rows + right_nt.num_rows < floor:
            self._note_host_join(
                f"bins below tpu.device_join_min_rows={floor}")
            return None
        lkeys = [f"__key{i}" for i in range(self.n_keys)]
        with timeline.phase("join.prep"):
            prep = device_join.prepare_join_keys(left_nt, right_nt, lkeys)
        if prep is None:
            self._note_host_join("a key type the probe cannot code")
            return None
        lcols, rcols, lsel, rsel = prep
        with timeline.phase("join.probe", n=len(lcols[0])):
            li, ri = device_join.probe(lcols, rcols)
        with timeline.phase("join.take", n=len(li)):
            if lsel is not None:
                li = lsel[li]
            if rsel is not None:
                ri = rsel[ri]
            l_take = pa.array(li)
            r_take = pa.array(ri)
            arrays, names = [], []
            lset = set(left_nt.column_names)
            for name in left_nt.column_names:
                arrays.append(left_nt.column(name).take(l_take))
                names.append(name)
            for name in right_nt.column_names:
                if name in lkeys:
                    continue  # coalesced join keys
                out = name + "_right" if name in lset else name
                arrays.append(right_nt.column(name).take(r_take))
                names.append(out)
            # from_arrays, not a dict: duplicate output names must survive
            # exactly like the arrow join's suffix behavior
            return pa.Table.from_arrays(arrays, names=names)

    def _inner_join(self, left_nt: pa.Table, right_nt: pa.Table) -> pa.Table:
        """Inner equi-join on the __key columns: device probe when
        eligible, arrow C++ hash join otherwise."""
        joined = self._device_inner_join(left_nt, right_nt)
        if joined is not None:
            return joined
        lkeys = [f"__key{i}" for i in range(self.n_keys)]
        # the host tier's probe, under the device probe's name
        with timeline.phase("join.probe", n=left_nt.num_rows):
            return left_nt.join(
                right_nt,
                keys=lkeys,
                right_keys=lkeys,
                join_type="inner",
                left_suffix="",
                right_suffix="_right",
                coalesce_keys=True,
            )

    def _join_tables(
        self, left: pa.Table, right: pa.Table, ts_value: int
    ) -> Optional[pa.RecordBatch]:
        """Bin-local equi-join + residual + output schema normalization.

        For outer joins the residual predicate is part of the ON condition,
        not a post-filter: a preserved-side row whose matches all fail the
        residual must still be emitted null-padded, and null-padded rows
        must not be dropped by a null-valued residual. We join inner with
        the residual, then anti-join to synthesize the null-padded rows
        (reference behavior comes from DataFusion's join filters)."""
        lkeys = [f"__key{i}" for i in range(self.n_keys)]
        with timeline.phase("join.prep", key=ts_value):
            left_nt = _flatten_structs(left.drop_columns([TIMESTAMP_FIELD]))
            right_nt = _flatten_structs(
                right.drop_columns([TIMESTAMP_FIELD]))
        if self.residual is None or self.join_type == "inner":
            if self.join_type == "inner":
                joined = self._inner_join(left_nt, right_nt)
            else:
                with timeline.phase("join.probe", n=left_nt.num_rows):
                    joined = left_nt.join(
                        right_nt,
                        keys=lkeys,
                        right_keys=lkeys,
                        join_type=_JOIN_TYPE_MAP[self.join_type],
                        left_suffix="",
                        right_suffix="_right",
                        coalesce_keys=True,
                    )
            # the output table: projection and the residual predicate
            with timeline.phase("join.take", key=ts_value,
                                n=joined.num_rows):
                batch = self._project(joined, ts_value)
                if batch is None:
                    return None
                if self.residual is not None:
                    batch = batch.filter(self.residual(batch))
                return batch if batch.num_rows else None

        import pyarrow.compute as pc

        left_i = left_nt.append_column(
            "__lidx", pa.array(np.arange(left_nt.num_rows, dtype=np.int64))
        )
        right_i = right_nt.append_column(
            "__ridx", pa.array(np.arange(right_nt.num_rows, dtype=np.int64))
        )
        joined = self._inner_join(left_i, right_i)
        parts: List[pa.RecordBatch] = []
        matched_l = np.empty(0, dtype=np.int64)
        matched_r = np.empty(0, dtype=np.int64)
        if joined.num_rows:
            batch = self._project(joined, ts_value)
            mask = pc.fill_null(self.residual(batch), False)
            mask_np = np.asarray(mask)
            if mask_np.any():
                matched_l = np.unique(
                    np.asarray(joined.column("__lidx").combine_chunks())[
                        mask_np
                    ]
                )
                matched_r = np.unique(
                    np.asarray(joined.column("__ridx").combine_chunks())[
                        mask_np
                    ]
                )
                parts.append(batch.filter(mask))
        if self.join_type in ("left", "full"):
            unmatched = np.setdiff1d(
                np.arange(left_nt.num_rows, dtype=np.int64), matched_l
            )
            if len(unmatched):
                pad = left_nt.take(pa.array(unmatched)).join(
                    right_nt.slice(0, 0),
                    keys=lkeys,
                    right_keys=lkeys,
                    join_type="left outer",
                    left_suffix="",
                    right_suffix="_right",
                    coalesce_keys=True,
                )
                part = self._project(pad, ts_value)
                if part is not None:
                    parts.append(part)
        if self.join_type in ("right", "full"):
            unmatched = np.setdiff1d(
                np.arange(right_nt.num_rows, dtype=np.int64), matched_r
            )
            if len(unmatched):
                pad = left_nt.slice(0, 0).join(
                    right_nt.take(pa.array(unmatched)),
                    keys=lkeys,
                    right_keys=lkeys,
                    join_type="right outer",
                    left_suffix="",
                    right_suffix="_right",
                    coalesce_keys=True,
                )
                part = self._project(pad, ts_value)
                if part is not None:
                    parts.append(part)
        parts = [p for p in parts if p is not None and p.num_rows]
        if not parts:
            return None
        if len(parts) == 1:
            return parts[0]
        return (
            pa.Table.from_batches(parts).combine_chunks().to_batches()[0]
        )

    def _project(
        self, joined: pa.Table, ts_value: int
    ) -> Optional[pa.RecordBatch]:
        if joined.num_rows == 0:
            return None
        arrays = []
        for f in self.out_schema.schema:
            if f.name == TIMESTAMP_FIELD:
                arrays.append(
                    pa.array(
                        np.full(joined.num_rows, ts_value, dtype=np.int64)
                    ).cast(f.type)
                )
                continue
            arrays.append(_take_col(joined, f))
        return pa.RecordBatch.from_arrays(
            arrays, schema=self.out_schema.schema
        )


_SEP = "\x01"  # struct-flattening separator (acero rejects struct columns)


def _flatten_structs(t: pa.Table) -> pa.Table:
    arrays, names = [], []
    changed = False
    for f in t.schema:
        col = t.column(f.name).combine_chunks()
        if pa.types.is_struct(f.type):
            changed = True
            for j in range(f.type.num_fields):
                arrays.append(col.field(j))
                names.append(f"{f.name}{_SEP}{f.type.field(j).name}")
        else:
            arrays.append(col)
            names.append(f.name)
    if not changed:
        return t
    return pa.table(dict(zip(names, arrays)))


def _take_col(joined: pa.Table, f: pa.Field) -> pa.Array:
    if pa.types.is_struct(f.type):
        base = f.name[:-6] if f.name.endswith("_right") else f.name
        children = []
        for j in range(f.type.num_fields):
            cn = f.type.field(j).name
            col = None
            for cand in (f"{f.name}{_SEP}{cn}", f"{base}{_SEP}{cn}_right",
                         f"{base}{_SEP}{cn}"):
                if cand in joined.column_names:
                    col = joined.column(cand).combine_chunks()
                    break
            if col is None:
                raise KeyError(f"join output missing struct child {f.name}.{cn}")
            if not col.type.equals(f.type.field(j).type):
                col = col.cast(f.type.field(j).type)
            children.append(col)
        return pa.StructArray.from_arrays(
            children, names=[f.type.field(j).name
                             for j in range(f.type.num_fields)]
        )
    col = None
    for cand in (f.name, f.name + "_right"):
        if cand in joined.column_names:
            col = joined.column(cand)
            break
    if col is None:
        raise KeyError(
            f"join output missing column {f.name}; have {joined.column_names}"
        )
    col = col.combine_chunks()
    if not col.type.equals(f.type):
        col = col.cast(f.type)
    return col


class InstantJoinOperator(JoinBase):
    """Windowed join: rows arrive already windowed (one _timestamp per
    window); buffer per bin and join when the watermark passes the bin.

    The buffers LIVE in the side time-key tables (ijl/ijr) rather than an
    operator-local dict: the tables stage checkpoint deltas automatically
    and give cold bins the disk spill tier (state.memory_budget_bytes) —
    a join holding many windows in flight is bounded by disk, not RAM,
    and spilled bins are memory-mapped back exactly when the watermark
    drains them."""

    def __init__(self, config: dict):
        super().__init__(config, "instant_join")
        self.emitted_up_to: Optional[int] = None
        # side tables (durable via the table manager, or operator-local
        # spill-only instances when the job has no state backend)
        self._tables: Optional[List] = None
        self._durable = False

    _SIDE_TABLES = ("ijl", "ijr")

    def tables(self):
        from ..state.table_config import global_table, time_key_table

        # retention -1: bins emit at wm >= ts, so restore keeps exactly
        # ts > wm. Buffered input batches ARE the delta rows (incremental
        # checkpoints write only batches buffered since the last epoch).
        key_fields = tuple(f"__key{i}" for i in range(self.n_keys))
        return {
            "ij": global_table("ij"),
            **{
                name: time_key_table(
                    name, retention_nanos=-1, key_fields=key_fields
                )
                for name in self._SIDE_TABLES
            },
        }

    async def on_start(self, ctx):
        from ..ops.device_join import log_probe_tier

        log_probe_tier(self)
        if ctx.table_manager is not None:
            self._durable = True
            self._tables = [
                await ctx.table(name) for name in self._SIDE_TABLES
            ]
            table = await ctx.table("ij")
            for snap in table.all_values():
                if snap.get("emitted_up_to") is not None:
                    self.emitted_up_to = max(
                        self.emitted_up_to or 0, snap["emitted_up_to"]
                    )
                for ts_s, sides in snap.get("bins", {}).items():
                    for side in (0, 1):
                        for blob in sides[str(side)]:
                            b = self._filter_to_range(_ipc_read(blob), ctx)
                            if b is not None and b.num_rows:
                                # legacy full-snapshot rows have no delta
                                # files; re-persist at the next checkpoint
                                self._tables[side].insert(b)
        else:
            # stateless run: same buffer + spill semantics, no durability
            from ..state.table_config import time_key_table
            from ..state.tables import TimeKeyTable

            self._tables = [
                TimeKeyTable(time_key_table(name, retention_nanos=-1))
                for name in self._SIDE_TABLES
            ]

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            table = await ctx.table("ij")
            table.put(
                ctx.task_info.task_index,
                {
                    "emitted_up_to": self.emitted_up_to,
                    "subtask": ctx.task_info.task_index,
                    "bins": {},
                },
            )
            # skip persisting rows whose bin already emitted this epoch
            if self.emitted_up_to is not None:
                for t in self._tables:
                    t.prune_dirty(
                        lambda b: _batch_max_ts(b) > self.emitted_up_to
                    )

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        tnp = np.asarray(
            batch.column(batch.schema.names.index(TIMESTAMP_FIELD)).cast(
                pa.int64()
            )
        )
        if self.emitted_up_to is not None:
            live = tnp > self.emitted_up_to
            if not live.all():
                if not live.any():
                    return
                batch = batch.filter(pa.array(live))
        if batch.num_rows:
            self._tables[input_index].insert(
                batch, stage_dirty=self._durable
            )

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME:
            return watermark
        t = watermark.timestamp
        bins: Dict[int, Dict[int, List[pa.RecordBatch]]] = {}
        for side in (0, 1):
            for ts, b in self._tables[side].take_bins_upto(t):
                bins.setdefault(ts, {0: [], 1: []})[side].append(b)
        for ts in sorted(bins):
            sides = bins[ts]
            left, right = sides[0], sides[1]
            if not left and not right:
                continue
            if self.join_type == "inner" and (not left or not right):
                continue
            if self.join_type == "left" and not left:
                continue
            if self.join_type == "right" and not right:
                continue
            with timeline.phase("join.buffer", key=ts) as ph:
                lt = _concat(left) or _empty_from_schema(
                    self.left_schema, right[0], self.n_keys
                )
                rt = _concat(right) or _empty_from_schema(
                    self.right_schema, left[0], self.n_keys
                )
                ph.n = lt.num_rows + rt.num_rows
            out = self._join_tables(lt, rt, ts_value=ts)
            if out is not None:
                with timeline.phase("join.emit", key=ts, n=out.num_rows,
                                    annotate=False):
                    await collector.collect(out)
            self.emitted_up_to = max(self.emitted_up_to or 0, ts)
        return watermark


def _concat(batches: List[pa.RecordBatch]) -> Optional[pa.Table]:
    if not batches:
        return None
    return pa.Table.from_batches(batches)


def _batch_max_ts(batch: pa.RecordBatch) -> int:
    ts = np.asarray(
        batch.column(batch.schema.names.index(TIMESTAMP_FIELD)).cast(
            pa.int64()
        )
    )
    return int(ts.max()) if len(ts) else -(1 << 62)


def _empty_from_schema(schema, opposite: pa.RecordBatch,
                       n_keys: int) -> pa.Table:
    """Empty table for a side with no rows in a bin (outer joins). Uses the
    side's full declared schema so payload columns exist (and the outer join
    emits nulls for them); falls back to key columns typed from the opposite
    side when no schema was configured."""
    if schema is not None:
        s = schema.schema if hasattr(schema, "schema") else schema
        return pa.table({f.name: pa.array([], type=f.type) for f in s})
    arrays = [
        pa.array([], type=opposite.schema.field(i).type) for i in range(n_keys)
    ]
    names = [f"__key{i}" for i in range(n_keys)]
    arrays.append(pa.array([], type=pa.timestamp("ns")))
    names.append(TIMESTAMP_FIELD)
    return pa.table(dict(zip(names, arrays)))


class JoinWithExpirationOperator(JoinBase):
    """Non-windowed append join: symmetric hash join with TTL'd buffers
    (reference join_with_expiration.rs)."""

    def __init__(self, config: dict):
        super().__init__(config, "join")
        self.ttl = int(config.get("ttl_nanos", 24 * 3600 * 1_000_000_000))
        if self.join_type != "inner":
            raise ValueError(
                "non-windowed outer joins require updating semantics"
            )
        self.buffers: Dict[int, List[pa.RecordBatch]] = {0: [], 1: []}
        self._dirty: Dict[int, List[pa.RecordBatch]] = {0: [], 1: []}

    _SIDE_TABLES = ("jbl", "jbr")

    def tables(self):
        from ..state.table_config import global_table, time_key_table

        # retention = TTL: the same cutoff the operator's own watermark
        # eviction applies, so restored rows match live-buffer trimming
        key_fields = tuple(f"__key{i}" for i in range(self.n_keys))
        return {
            "jb": global_table("jb"),
            **{
                name: time_key_table(
                    name, retention_nanos=self.ttl, key_fields=key_fields
                )
                for name in self._SIDE_TABLES
            },
        }

    async def on_start(self, ctx):
        from ..ops.device_join import log_probe_tier

        log_probe_tier(self)
        if ctx.table_manager is not None:
            table = await ctx.table("jb")
            for snap in table.all_values():
                for side in (0, 1):
                    for blob in snap.get(str(side), []):
                        b = self._filter_to_range(_ipc_read(blob), ctx)
                        if b is not None and b.num_rows:
                            self.buffers[side].append(b)
                            # legacy full-snapshot rows have no delta
                            # files; re-persist at the next checkpoint
                            self._dirty[side].append(b)
            for side, name in enumerate(self._SIDE_TABLES):
                t = await ctx.table(name)
                for b in t.all_batches():
                    if b.num_rows:
                        self.buffers[side].append(b)
                t.clear_batches()

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            table = await ctx.table("jb")
            table.put(
                ctx.task_info.task_index,
                {"subtask": ctx.task_info.task_index},
            )
            for side, name in enumerate(self._SIDE_TABLES):
                dirty = self._dirty[side]
                self._dirty[side] = []
                if dirty:
                    t = await ctx.table(name)
                    for b in dirty:
                        t.write_delta(b)

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        other = self.buffers[1 - input_index]
        if other:
            mine = pa.Table.from_batches([batch])
            other_t = pa.Table.from_batches(other)
            left_t = mine if input_index == 0 else other_t
            right_t = other_t if input_index == 0 else mine
            out = self._join_symmetric(left_t, right_t)
            if out is not None:
                await collector.collect(out)
        self.buffers[input_index].append(batch)
        self._dirty[input_index].append(batch)

    def _join_symmetric(self, lt: pa.Table, rt: pa.Table):
        """Inner join keeping _timestamp = max(left_ts, right_ts) per row."""
        import pyarrow.compute as pc

        lt2 = _flatten_structs(lt.rename_columns(
            [c if c != TIMESTAMP_FIELD else "__lts" for c in lt.column_names]
        ))
        rt2 = _flatten_structs(rt.rename_columns(
            [c if c != TIMESTAMP_FIELD else "__rts" for c in rt.column_names]
        ))
        joined = self._inner_join(lt2, rt2)
        if joined.num_rows == 0:
            return None
        ts = pc.max_element_wise(
            joined.column("__lts").cast(pa.int64()).combine_chunks(),
            joined.column("__rts").cast(pa.int64()).combine_chunks(),
        )
        arrays = []
        for f in self.out_schema.schema:
            if f.name == TIMESTAMP_FIELD:
                arrays.append(ts.cast(f.type))
                continue
            arrays.append(_take_col(joined, f))
        batch = pa.RecordBatch.from_arrays(arrays, schema=self.out_schema.schema)
        if self.residual is not None:
            mask = self.residual(batch)
            batch = batch.filter(mask)
            if batch.num_rows == 0:
                return None
        return batch

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME or self.ttl <= 0:
            return watermark
        cutoff = watermark.timestamp - self.ttl
        for side in (0, 1):
            kept = []
            for b in self.buffers[side]:
                ts = np.asarray(
                    b.column(b.schema.names.index(TIMESTAMP_FIELD)).cast(
                        pa.int64()
                    )
                )
                mask = ts >= cutoff
                if mask.all():
                    kept.append(b)
                elif mask.any():
                    kept.append(b.filter(pa.array(mask)))
            self.buffers[side] = kept
        return watermark


def _ipc_write(batch: pa.RecordBatch) -> bytes:
    import io

    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


def _ipc_read(blob: bytes) -> pa.RecordBatch:
    with pa.ipc.open_stream(pa.py_buffer(blob)) as r:
        batches = list(r)
    t = pa.Table.from_batches(batches).combine_chunks()
    return t.to_batches()[0] if t.num_rows else batches[0]


class LookupJoinOperator(Operator):
    """Lookup join against an external store (reference lookup_join.rs:274):
    each batch's join keys resolve through the connector's LookupConnector
    (reference connector.rs:421; caching, when any, lives in the connector's
    lookup implementation — e.g. the redis lookup keeps a TTL'd cache);
    inner joins drop misses, left joins emit nulls."""

    def __init__(self, config: dict):
        super().__init__("lookup_join")
        self.connector_name = config["connector"]
        self.connector_config = config["connector_config"]
        self.key_col: int = config["key_col"]
        self.join_type: str = config.get("join_type", "inner")
        self.right_fields: List[str] = config["right_fields"]
        self.out_schema: StreamSchema = config["schema"]
        self.lookup = None

    async def on_start(self, ctx):
        from ..connectors import get_connector

        conn = get_connector(self.connector_name)
        if not hasattr(conn, "make_lookup"):
            raise ValueError(
                f"connector {self.connector_name} does not support lookups"
            )
        self.lookup = conn.make_lookup(self.connector_config)

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        import json

        keys = batch.column(self.key_col).to_pylist()
        rows = []
        hits = []
        for k in keys:
            raw = self.lookup.lookup(str(k))
            if raw is None:
                hits.append(self.join_type == "left")
                rows.append({})
            else:
                hits.append(True)
                rows.append(json.loads(raw) if isinstance(raw, (bytes, str))
                            else raw)
        mask = pa.array(hits)
        kept = batch.filter(mask)
        kept_rows = [r for r, h in zip(rows, hits) if h]
        if kept.num_rows == 0:
            return
        arrays = []
        for f in self.out_schema.schema:
            if f.name in self.right_fields:
                arrays.append(
                    pa.array([r.get(f.name) for r in kept_rows], type=f.type)
                )
            else:
                arrays.append(kept.column(kept.schema.names.index(f.name)))
        await collector.collect(
            pa.RecordBatch.from_arrays(arrays, schema=self.out_schema.schema)
        )


@register_operator(OperatorName.LOOKUP_JOIN)
def _make_lookup(config: dict) -> Operator:
    return LookupJoinOperator(config)


@register_operator(OperatorName.INSTANT_JOIN)
def _make_instant(config: dict) -> Operator:
    return InstantJoinOperator(config)


@register_operator(OperatorName.JOIN)
def _make_join(config: dict) -> Operator:
    if config.get("mode") == "updating":
        from .updating_join import make_updating_join

        return make_updating_join(config)
    return JoinWithExpirationOperator(config)
