"""Updating (non-windowed) aggregates with retractions.

Capability parity with the reference's incremental_aggregator.rs
(/root/reference/crates/arroyo-worker/src/arrow/incremental_aggregator.rs):
unbounded GROUP BY over an append stream maintains per-key accumulators;
changed keys are flushed on a tick interval, emitting a retract row (the
previously emitted values) followed by the new row, tagged via the
`__updating_meta` struct column (arroyo-rpc/src/lib.rs:333
updating_meta_fields); a TTL evicts idle keys (reference updating_cache.rs).

Aggregation arithmetic runs on the shared device accumulator
(ops/aggregates.py) — count/sum/avg are incrementally updatable; min/max are
valid over append-only input (monotone). With `retractable` set (the input
is itself an updating stream), retract rows apply with sign -1 and a
per-key live-row count deletes keys whose rows have all been retracted
(emitting a final retraction). Invertible aggregates (count/sum/avg,
variance/regression, multisets) consume retractions directly; the planner
marks everything else (min/max/median/UDAF/...) with `replay`, which
re-aggregates from a value -> signed-count multiset at emission
(reference incremental_aggregator.rs raw-value replay).
"""

from __future__ import annotations

import time
import uuid
from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa

from ..engine.construct import register_operator
from ..graph.logical import OperatorName
from ..schema import TIMESTAMP_FIELD, UPDATING_META_FIELD
from .base import Operator
from ..ops.directory import _to_py
from .windows import WindowOperatorBase


class UpdatingAggregateOperator(WindowOperatorBase):
    # slot-based state protocol end-to-end (single bin 0): the accumulator
    # shards across the device mesh like tumbling/sliding; key->shard
    # routing happens in MeshSlotDirectory.assign and updates ride the
    # in-step all_to_all (reference incremental_aggregator.rs:77-90 treats
    # the updating aggregate like any keyed operator)
    _mesh_ok = True

    def __init__(self, config: dict):
        super().__init__(config, "updating_aggregate")
        from ..config import config as get_config

        self.flush_interval = float(
            config.get(
                "flush_interval",
                get_config().pipeline.update_aggregate_flush_interval,
            )
        )
        ttl = config.get(
            "ttl_nanos",
            int(get_config().pipeline.update_aggregate_ttl * 1e9),
        )
        self.ttl_nanos: Optional[int] = int(ttl) if ttl else None
        # key tuple -> last emitted finalized values (None = never emitted)
        self.emitted: Dict[tuple, List] = {}
        self.dirty: set = set()
        self.last_seen: Dict[tuple, int] = {}
        self.max_ts = 0  # max event time seen (flush timestamp fallback)
        # retraction-consuming mode: input rows carry __updating_meta and
        # apply with sign -1 when is_retract; live row-count per key drives
        # key deletion once everything contributing has been retracted
        self.retractable: bool = bool(config.get("retractable"))
        self.meta_col: Optional[int] = config.get("meta_col")
        self.live: Dict[tuple, int] = {}
        # keys changed / deleted since the last checkpoint (incremental)
        self._ckpt_dirty: set = set()
        self._ckpt_dead: set = set()

    def tables(self):
        from ..state.table_config import global_table, time_key_table

        # incremental per-key rows: __ts = key's last_seen (retention = the
        # operator's own idle-key TTL), upserts + __dead tombstones; newest
        # row per key wins on restore
        return {
            "u": global_table("u"),
            "ui": time_key_table(
                "ui",
                retention_nanos=self.ttl_nanos,
                timestamp_field="__ts",
                key_fields=self._delta_key_fields(),
            ),
        }

    def tick_interval(self) -> Optional[float]:
        return self.flush_interval

    async def on_start(self, ctx):
        self._capture_key_meta(ctx)
        if ctx.table_manager is not None:
            table = await ctx.table("u")
            from .windows import _snaps_for_me

            for snap in _snaps_for_me(table, ctx, bool(self.key_cols)):
                self._restore_rows(snap, ctx)
                emitted_rows = snap.get("emitted", [])
                key_rows = [kv for kv, _ in emitted_rows]
                # range-mask on the VALUES (pre-interning), matching the
                # shuffle hash, like _restore_rows does
                mask = (
                    self._range_mask(key_rows, ctx) if key_rows else None
                )
                for i, (key_vals, vals) in enumerate(emitted_rows):
                    if mask is not None and not mask[i]:
                        continue
                    self.emitted[self.codec.key(key_vals)] = vals
                ls_rows = snap.get("last_seen", [])
                ls_mask = (
                    self._range_mask([kv for kv, _ in ls_rows], ctx)
                    if ls_rows else None
                )
                for i, (key_vals, seen) in enumerate(ls_rows):
                    if ls_mask is not None and not ls_mask[i]:
                        continue
                    self.last_seen[self.codec.key(key_vals)] = seen
                lv_rows = snap.get("live", [])
                lv_mask = (
                    self._range_mask([kv for kv, _ in lv_rows], ctx)
                    if lv_rows else None
                )
                for i, (key_vals, cnt) in enumerate(lv_rows):
                    if lv_mask is not None and not lv_mask[i]:
                        continue
                    self.live[self.codec.key(key_vals)] = cnt
            await self._restore_updating_incremental(ctx)
        # everything restored must re-verify against emitted on next flush;
        # it is also checkpoint-dirty so a legacy full snapshot gets
        # re-persisted as incremental rows at the first post-restore epoch
        for _, key, _slot in self.dir.items():
            self.dirty.add(key)
            self._ckpt_dirty.add(key)

    async def handle_checkpoint(self, barrier, ctx, collector):
        # flush before the barrier so checkpointed emitted-state matches
        # the snapshot (restores re-emit nothing)
        await self._flush(ctx, collector)
        if ctx.table_manager is None:
            return
        table = await ctx.table("u")
        if self._use_incremental():
            delta = self._build_updating_delta()
            if delta is not None:
                (await ctx.table("ui")).write_delta(delta)
            table.put(
                ctx.task_info.task_index,
                {
                    "bins": [], "keys": [], "values": [],
                    "emitted": [], "last_seen": [],
                    "subtask": ctx.task_info.task_index,
                },
            )
            return
        snap = self._snapshot_rows()
        snap["subtask"] = ctx.task_info.task_index
        snap["emitted"] = [
            [self.codec.values(k), v]
            for k, v in self.emitted.items()
        ]
        snap["last_seen"] = [
            [self.codec.values(k), v]
            for k, v in self.last_seen.items()
        ]
        if self.retractable:
            snap["live"] = [
                [self.codec.values(k), v]
                for k, v in self.live.items()
            ]
        table.put(ctx.task_info.task_index, snap)

    def _build_updating_delta(self) -> Optional[pa.RecordBatch]:
        """Upsert rows for keys touched since the last epoch + __dead
        tombstones for retract-deleted keys. __ts is the key's last_seen so
        the TTL retention prunes idle keys from restore exactly like the
        live eviction does."""
        import msgpack

        slot_map = self._dirty_slot_map(self._ckpt_dirty)
        keys = list(slot_map)
        dead = list(self._ckpt_dead)
        self._ckpt_dirty = set()
        self._ckpt_dead = set()
        if not keys and not dead:
            return None
        n_phys = len(self.acc.phys)
        if keys:
            slots = np.asarray([slot_map[k] for k in keys], dtype=np.int64)
            values = self.acc.snapshot(slots)
        else:
            values = [np.empty(0, dtype=s.dtype) for s in self.acc.state]
        all_keys = keys + dead
        ts = np.asarray(
            [self.last_seen.get(k, self.max_ts) for k in keys]
            + [self.max_ts] * len(dead),
            dtype=np.int64,
        )
        arrays = [pa.array(ts)]
        names = ["__ts"]
        key_rows = [tuple(self.codec.values(k)) for k in all_keys]
        for i, arr in enumerate(self.codec.delta_arrays_from_values(key_rows)):
            arrays.append(arr)
            names.append(f"__k{i}")
        for j in range(n_phys):
            vj = np.asarray(values[j])
            col = np.concatenate([vj, np.zeros(len(dead), dtype=vj.dtype)])
            arrays.append(pa.array(col))
            names.append(f"__v{j}")
        arrays.append(
            pa.array(
                [
                    msgpack.packb(self.emitted[k])
                    if self.emitted.get(k) is not None
                    else None
                    for k in keys
                ]
                + [None] * len(dead),
                type=pa.binary(),
            )
        )
        names.append("__emitted")
        arrays.append(
            pa.array(
                np.asarray(
                    [self.live.get(k, 0) for k in keys] + [0] * len(dead),
                    dtype=np.int64,
                )
            )
        )
        names.append("__live")
        arrays.append(
            pa.array([False] * len(keys) + [True] * len(dead))
        )
        names.append("__dead")
        return pa.RecordBatch.from_arrays(arrays, names=names)

    async def _restore_updating_incremental(self, ctx):
        import msgpack

        if self._key_types is None:
            return
        table = await ctx.table("ui")
        newest: Dict[tuple, Optional[tuple]] = {}
        n_phys = len(self.acc.phys)
        for b in table.all_batches():
            names = b.schema.names
            ts = np.asarray(b.column(names.index("__ts")))
            key_cols = self.codec.columns_from_delta(b)
            vals = [
                np.asarray(b.column(names.index(f"__v{j}")))
                for j in range(n_phys)
            ]
            emitted = b.column(names.index("__emitted")).to_pylist()
            live = np.asarray(b.column(names.index("__live")))
            dead = np.asarray(b.column(names.index("__dead")))
            for r in range(b.num_rows):
                kv = tuple(c[r] for c in key_cols)
                newest[kv] = (
                    None
                    if dead[r]
                    else (
                        int(ts[r]),
                        [v[r] for v in vals],
                        emitted[r],
                        int(live[r]),
                    )
                )
        rows = [(kv, v) for kv, v in newest.items() if v is not None]
        table.clear_batches()
        if not rows:
            return
        mask = self._range_mask([list(kv) for kv, _ in rows], ctx)
        if mask is not None:
            rows = [rv for rv, m in zip(rows, mask) if m]
            if not rows:
                return
        cols: List[list] = [[] for _ in range(n_phys)]
        keys_l = []
        for kv, (ts_, vv, _, _) in rows:
            keys_l.append(list(kv))
            for j, v in enumerate(vv):
                cols[j].append(v)
        self._restore_rows(
            {
                "bins": [0] * len(rows),
                "keys": keys_l,
                "values": cols,
            },
            ctx,
        )
        for kv, (ts_, _, em, lv) in rows:
            key = self.codec.key(list(kv))
            self.last_seen[key] = ts_
            if em is not None:
                self.emitted[key] = msgpack.unpackb(em, raw=False)
            if self.retractable:
                self.live[key] = lv

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        self._capture_key_meta(ctx)
        ts = ctx.in_schemas[0].timestamps(batch)
        bins = np.zeros(batch.num_rows, dtype=np.int64)  # single bin
        keys = self.codec.columns(batch, self.key_cols)
        slots = self.dir.assign(bins, keys)
        self._ensure_capacity()
        signs = None
        if self.retractable:
            is_retract = np.asarray(
                batch.column(self.meta_col).field("is_retract")
                .to_numpy(zero_copy_only=False)
            )
            signs = np.where(is_retract, -1, 1).astype(np.int64)
        self.acc.update(slots, self._agg_input_cols(batch), signs=signs)
        now = int(ts.max()) if len(ts) else 0
        self.max_ts = max(self.max_ts, now)
        # mark touched keys dirty: O(unique-in-batch) via the directory's
        # reverse map, not O(live keys)
        if signs is not None:
            # per-unique-slot signed row delta, O(batch) memory (bincount
            # over raw slot ids would size by the largest live slot)
            uniq, inv = np.unique(slots, return_inverse=True)
            per_uniq = np.bincount(inv, weights=signs)
        else:
            uniq = np.unique(slots)
        for i, entry in enumerate(self.dir.keys_for_slots(uniq)):
            if entry is not None:
                _, key = entry
                self.dirty.add(key)
                self._ckpt_dirty.add(key)
                self._ckpt_dead.discard(key)
                self.last_seen[key] = now
                if signs is not None:
                    self.live[key] = self.live.get(key, 0) + int(per_uniq[i])

    def _dirty_slot_map(self, key_set) -> dict:
        """slot per live key for the (usually small) dirty set — point
        lookups, O(dirty), on every table (python dict / native C++
        probe / mesh per-shard dispatch)."""
        return self.dir.slots_for_keys(0, list(key_set))

    async def handle_tick(self, tick, ctx, collector):
        await self._flush(ctx, collector)
        self._evict(ctx)

    async def handle_watermark(self, watermark, ctx, collector):
        # flush BEFORE forwarding so downstream sees the deltas ahead of the
        # watermark (the end-of-stream watermark must trail the final
        # retract/append pairs, or downstream TTLs act on stale state)
        await self._flush(ctx, collector)
        return watermark

    async def on_close(self, ctx, collector, is_eod: bool):
        if is_eod:
            await self._flush(ctx, collector)
        return None

    async def _flush(self, ctx, collector):
        """Emit retract/append pairs for keys whose aggregate changed
        (reference handle_tick :994 + set_retract_metadata :1026)."""
        if not self.dirty:
            return
        slot_map = self._dirty_slot_map(self.dirty)
        keys = list(slot_map)
        self.dirty.clear()
        if not keys:
            return
        retract_keys: List[tuple] = []
        retract_vals: List[List] = []
        append_keys: List[tuple] = []
        append_vals: List[List] = []
        if self.retractable:
            # keys whose every contributing row was retracted: emit a final
            # retraction of the last emitted values and drop all state
            dead = [k for k in keys if self.live.get(k, 0) <= 0]
            if dead:
                keys = [k for k in keys if self.live.get(k, 0) > 0]
                for k in dead:
                    old = self.emitted.pop(k, None)
                    if old is not None:
                        retract_keys.append(k)
                        retract_vals.append(old)
                    self.last_seen.pop(k, None)
                    self.live.pop(k, None)
                    self._ckpt_dead.add(k)
                    self._ckpt_dirty.discard(k)
                freed = self.dir.remove(0, dead)
                if len(freed):
                    self.acc.reset_slots(freed)
        if keys:
            slots = np.asarray([slot_map[k] for k in keys], dtype=np.int64)
            agg_cols = self.acc.finalize(self.acc.gather(slots))
            # one C-level tolist per column instead of a numpy-scalar
            # .item() per cell (object columns pass through unchanged)
            col_lists = [
                c.tolist() if isinstance(c, np.ndarray)
                and c.dtype.kind != "O" else c
                for c in agg_cols
            ]
            for i, key in enumerate(keys):
                new_vals = [_to_py(c[i]) for c in col_lists]
                old = self.emitted.get(key)
                if old == new_vals:
                    continue
                if old is not None:
                    retract_keys.append(key)
                    retract_vals.append(old)
                append_keys.append(key)
                append_vals.append(new_vals)
                self.emitted[key] = new_vals
        if self._serve_view is not None:
            # StateServe: mirror the flushed aggregates into the serve
            # view — appends overwrite the key, a fully-retracted key
            # stages a tombstone (sealed at the next capture)
            view = self._serve_view
            for key, vals in zip(append_keys, append_vals):
                view.stage(
                    view.canon_key(self.codec.values(key)),
                    dict(zip(view.value_names, vals)),
                )
            for key, old in zip(retract_keys, retract_vals):
                if key not in self.emitted:  # final retraction (dead key)
                    view.stage_tomb(
                        view.canon_key(self.codec.values(key))
                    )
        if not retract_keys and not append_keys:
            return
        # flushes before the first watermark stamp rows with the max
        # event time seen — a zero timestamp would look ancient to
        # downstream event-time TTLs and get evicted immediately
        ts = ctx.watermarks.current_nanos() or self.max_ts
        if retract_keys:
            await collector.collect(
                self._build_updating(retract_keys, retract_vals, True, ts)
            )
        if append_keys:
            await collector.collect(
                self._build_updating(append_keys, append_vals, False, ts)
            )

    def _build_updating(
        self, keys: List[tuple], vals: List[List], is_retract: bool, ts: int
    ) -> pa.RecordBatch:
        n = len(keys)
        arrays = []
        for f in self.out_schema.schema:
            if f.name == TIMESTAMP_FIELD:
                arrays.append(
                    pa.array(np.full(n, ts, dtype=np.int64)).cast(f.type)
                )
            elif f.name == UPDATING_META_FIELD:
                from ..schema import updating_meta_array

                arrays.append(updating_meta_array(n, is_retract))
            elif f.name in (self._key_names or []):
                arrays.append(self.codec.arrow_from_keys(
                    self._key_names.index(f.name), keys))
            else:
                ai = next(
                    j for j, s in enumerate(self.specs) if s.name == f.name
                )
                arrays.append(pa.array([v[ai] for v in vals], type=f.type))
        return pa.RecordBatch.from_arrays(arrays, schema=self.out_schema.schema)

    def _evict(self, ctx):
        """TTL eviction of idle keys (reference updating_cache.rs)."""
        if not self.ttl_nanos:
            return
        wm = ctx.watermarks.current_nanos()
        if wm is None:
            return
        from ..types import WATERMARK_END

        if wm >= WATERMARK_END:
            return  # end-of-stream marker, not a real event time
        cutoff = wm - self.ttl_nanos
        stale = [k for k, seen in self.last_seen.items() if seen < cutoff]
        if not stale:
            return
        freed = self.dir.remove(0, stale)
        if len(freed):
            self.acc.reset_slots(freed)
        for k in stale:
            self.last_seen.pop(k, None)
            self.emitted.pop(k, None)
            self.live.pop(k, None)
            self.dirty.discard(k)
            # retention alone ages these rows out of restore; no tombstone
            # needed since eviction == the retention cutoff itself
            self._ckpt_dirty.discard(k)


@register_operator(OperatorName.UPDATING_AGGREGATE)
def _make_updating(config: dict) -> Operator:
    return UpdatingAggregateOperator(config)
