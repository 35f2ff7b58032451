"""SQL window functions over event-time windows.

Capability parity with the reference's window_fn.rs
(/root/reference/crates/arroyo-worker/src/arrow/window_fn.rs): rows of a
windowed stream buffer per bin (all rows of one emitted window share a
_timestamp); when the watermark passes a bin, the window functions
(ROW_NUMBER / RANK / DENSE_RANK ... OVER (PARTITION BY ... ORDER BY ...))
evaluate over the bin's rows and the augmented rows emit. The reference
runs a DataFusion BoundedWindowAggExec per bin; here the ranking kernels
are numpy lexsort-based.

The phase ledger (`obs/timeline.py`): `rank.buffer` per batch (`n` = rows
taken in); per bin closed, `key` = the bin's timestamp: `rank.sort` (`n` =
rows ranked: the bin's table, the lexsort, the rank), `rank.build` (`n` =
rows of the output batch) and `rank.emit` (self time: the operators behind
it run inside). `handle_checkpoint` returns the buffered rows it serialised,
which the runner books as `n` of the task's `ckpt.capture`. What survives a
filter on the rank downstream is not this operator's to count.
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
from .base import Operator

SUPPORTED = ("row_number", "rank", "dense_rank", "count")


class WindowFunctionOperator(Operator):
    flow_class = "buffering"  # buffers partitions until the watermark closes them

    def __init__(self, config: dict):
        super().__init__("window_fn")
        self.fn: str = config["fn"]  # row_number | rank | dense_rank
        if self.fn not in SUPPORTED:
            raise ValueError(f"unsupported window function {self.fn}")
        self.partition_cols: List[int] = list(config.get("partition_cols", []))
        # [(col_idx, descending)]
        self.order_by: List[tuple] = [tuple(o) for o in config.get("order_by", [])]
        self.out_schema: StreamSchema = config["schema"]
        self.out_field: str = config["out_field"]
        self.bins: Dict[int, List[pa.RecordBatch]] = {}
        self.emitted_up_to: Optional[int] = None

    def tables(self):
        from ..state.table_config import global_table

        return {"wf": global_table("wf")}

    async def on_start(self, ctx):
        if ctx.table_manager is not None:
            from .joins import _ipc_read

            table = await ctx.table("wf")
            for snap in table.all_values():
                if snap.get("emitted_up_to") is not None:
                    self.emitted_up_to = max(
                        self.emitted_up_to or 0, snap["emitted_up_to"]
                    )
                for ts_s, blobs in snap.get("bins", {}).items():
                    self.bins.setdefault(int(ts_s), []).extend(
                        _ipc_read(b) for b in blobs
                    )

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            from .joins import _ipc_write

            table = await ctx.table("wf")
            table.put(
                ctx.task_info.task_index,
                {
                    "emitted_up_to": self.emitted_up_to,
                    "subtask": ctx.task_info.task_index,
                    "bins": {
                        str(ts): [_ipc_write(b) for b in batches]
                        for ts, batches in self.bins.items()
                    },
                },
            )
            return sum(b.num_rows for bs in self.bins.values() for b in bs)

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        with timeline.phase("rank.buffer", n=batch.num_rows):
            ts = np.asarray(
                batch.column(batch.schema.names.index(TIMESTAMP_FIELD)).cast(
                    pa.int64()
                )
            )
            if self.emitted_up_to is not None:
                live = ts > self.emitted_up_to
                if not live.all():
                    if not live.any():
                        return
                    batch = batch.filter(pa.array(live))
                    ts = ts[live]
            for t in np.unique(ts):
                mask = ts == t
                self.bins.setdefault(int(t), []).append(
                    batch.filter(pa.array(mask)) if not mask.all() else batch
                )

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME:
            return watermark
        t = watermark.timestamp
        for ts in sorted(b for b in self.bins if b <= t):
            batches = self.bins.pop(ts)
            with timeline.phase("rank.sort", key=ts) as ph:
                table = pa.Table.from_batches(batches).combine_chunks()
                ph.n = table.num_rows
                values = self._rank_values(table) if ph.n else None
            if values is not None:
                with timeline.phase("rank.build", key=ts, n=len(values)):
                    out = self._build_output(table, values)
                with timeline.phase("rank.emit", key=ts, n=out.num_rows,
                                    annotate=False):
                    await collector.collect(out)
            self.emitted_up_to = max(self.emitted_up_to or 0, ts)
        return watermark

    def _rank_values(self, table: pa.Table) -> np.ndarray:
        """The window function's value for every row of one bin."""
        n = table.num_rows
        if self.partition_cols:
            from .windows import _batch_group_codes

            # exact partition ids (a hash of the columns could merge two
            # partitions in silence)
            part_ids = _batch_group_codes(
                [table.column(c).to_numpy(zero_copy_only=False)
                 for c in self.partition_cols], n)
        else:
            part_ids = np.zeros(n, dtype=np.int64)
        # order keys (last key = primary in lexsort)
        sort_keys = []
        for col_idx, desc in reversed(self.order_by):
            col = np.asarray(
                table.column(col_idx).to_numpy(zero_copy_only=False)
            )
            if col.dtype == object:
                _, col = np.unique(col, return_inverse=True)
            sort_keys.append(-col if desc else col)
        sort_keys.append(part_ids)
        order = np.lexsort(sort_keys)
        ranks = self._rank(part_ids[order], sort_keys, order)
        values = np.empty(n, dtype=np.int64)
        values[order] = ranks
        return values

    def _build_output(self, table: pa.Table,
                      values: np.ndarray) -> pa.RecordBatch:
        arrays = [table.column(f.name).combine_chunks()
                  if f.name != self.out_field else pa.array(values, type=f.type)
                  for f in self.out_schema.schema]
        return pa.RecordBatch.from_arrays(arrays, schema=self.out_schema.schema)

    def _rank(self, sorted_parts: np.ndarray, sort_keys, order) -> np.ndarray:
        """Vectorized ranking over partition-sorted rows: positions come
        from a cumulative count reset at partition starts; rank/dense_rank
        additionally detect ties on the order keys."""
        n = len(sorted_parts)
        idx = np.arange(n, dtype=np.int64)
        new_part = np.empty(n, dtype=bool)
        new_part[0] = True
        np.not_equal(sorted_parts[1:], sorted_parts[:-1], out=new_part[1:])
        # index of each row's partition start
        part_start = np.maximum.accumulate(np.where(new_part, idx, 0))
        pos = idx - part_start + 1  # 1-based position within partition
        if self.fn in ("row_number", "count"):
            return pos
        keys_sorted = [np.asarray(k)[order] for k in sort_keys[:-1]]
        new_group = new_part.copy()
        for k in keys_sorted:
            new_group[1:] |= k[1:] != k[:-1]
        if self.fn == "dense_rank":
            # count of group starts within the partition
            group_num = np.cumsum(new_group)
            return group_num - group_num[part_start] + 1
        # rank: position of the first row of each tie group
        group_start = np.maximum.accumulate(np.where(new_group, idx, 0))
        return group_start - part_start + 1


@register_operator(OperatorName.WINDOW_FUNCTION)
def _make_window_fn(config: dict) -> Operator:
    return WindowFunctionOperator(config)
