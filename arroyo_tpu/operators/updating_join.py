"""Updating (non-windowed) joins with retractions.

Capability parity with the reference's updating join support
(/root/reference/crates/arroyo-sql-testing/src/test/queries/
updating_{inner,left,right,full}_join.sql + planner plan/join.rs updating
path): both sides materialize per join key; every arriving append/retract
incrementally emits the delta of the join result as append/retract rows
tagged with __updating_meta, including the null-padded transitions of
outer joins (a side's first match retracts its null-padded row; losing the
last match re-emits it).

Streams reaching this operator are post-shuffle (keyed on the equi keys),
so each subtask owns its key range. Rates here are typically
post-aggregation, so the per-row host loop favors correctness; state
checkpoints as msgpack'd row lists per key.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from ..schema import StreamSchema, TIMESTAMP_FIELD, UPDATING_META_FIELD
from .base import Operator


class UpdatingJoinOperator(Operator):
    flow_class = "buffering"  # retract/append streams decouple in/out counts

    def __init__(self, config: dict):
        super().__init__("updating_join")
        self.n_keys = int(config["n_keys"])
        self.join_type = config["join_type"]  # inner | left | right | full
        self.out_schema: StreamSchema = config["schema"]
        key_names = {f"__key{i}" for i in range(self.n_keys)}
        skip = key_names | {TIMESTAMP_FIELD, UPDATING_META_FIELD}
        # SOURCE payload column names per side (input batch names) and the
        # OUTPUT names they map to (right side may be _right-renamed,
        # positionally aligned with the source order)
        self.left_src: List[str] = [
            f.name for f in config["left_schema"].schema
            if f.name not in skip
        ]
        self.left_out: List[str] = self.left_src
        self.right_src: List[str] = [
            f.name for f in config["right_schema"].schema
            if f.name not in skip
        ]
        self.right_out: List[str] = config["right_fields"]
        self.residual = config.get("residual_py")
        from ..config import config as get_config

        ttl = config.get(
            "ttl_nanos", int(get_config().pipeline.update_aggregate_ttl * 1e9)
        )
        self.ttl_nanos: Optional[int] = int(ttl) if ttl else None
        # key -> list of payload tuples (may contain duplicates)
        self.state: List[Dict[tuple, List[tuple]]] = [{}, {}]
        self.last_seen: Dict[tuple, int] = {}
        # columnar mirror of one side's store for the device-probe bulk
        # path: (key pa arrays, payload python column lists); rebuilt
        # lazily when that side's state has mutated
        self._col_cache: List[Optional[tuple]] = [None, None]
        # per side: list (per key col) of arrow chunks mirroring the
        # python key lists, plus the types they were built with
        self._key_arr_cache: List[Optional[list]] = [None, None]
        self._key_arr_types: List[Optional[list]] = [None, None]
        # sticky per-side flag: a null join key ever stored disables the
        # bulk path (per-row null semantics are authoritative) without
        # paying a store scan per batch; conservatively never cleared
        self._store_has_null_key: List[bool] = [False, False]
        self._lmap = {f: i for i, f in enumerate(self.left_out)}
        self._rmap = {f: i for i, f in enumerate(self.right_out)}
        self._kmap = {f"__key{i}": i for i in range(self.n_keys)}

    def tables(self):
        from ..state.table_config import global_table

        return {"uj": global_table("uj")}

    async def on_start(self, ctx):
        from ..ops.device_join import log_probe_tier

        log_probe_tier(self)
        if ctx.table_manager is not None:
            table = await ctx.table("uj")
            for snap in table.all_values():
                for side in (0, 1):
                    for key_vals, rows in snap[str(side)]:
                        key = tuple(key_vals)
                        if self._owns(key, ctx):
                            self.state[side].setdefault(key, []).extend(
                                tuple(r) for r in rows
                            )
                            if any(k is None for k in key):
                                self._store_has_null_key[side] = True
        self._col_cache = [None, None]

    def _owns(self, key: tuple, ctx) -> bool:
        p = ctx.task_info.parallelism
        if p <= 1:
            return True
        from ..types import hash_arrays, hash_column, server_for_hash_array

        cols = [
            hash_column(np.asarray([k])) for k in key
        ]
        owner = server_for_hash_array(hash_arrays(cols), p)[0]
        return owner == ctx.task_info.task_index

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            table = await ctx.table("uj")
            table.put(
                ctx.task_info.task_index,
                {
                    "subtask": ctx.task_info.task_index,
                    "0": [
                        [list(k), [list(r) for r in rows]]
                        for k, rows in self.state[0].items()
                    ],
                    "1": [
                        [list(k), [list(r) for r in rows]]
                        for k, rows in self.state[1].items()
                    ],
                },
            )

    # -- processing ---------------------------------------------------------

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        side = input_index
        schema_names = batch.schema.names
        src_fields = self.left_src if side == 0 else self.right_src
        ts = int(
            np.asarray(
                batch.column(schema_names.index(TIMESTAMP_FIELD)).cast(
                    pa.int64()
                )
            ).max()
        )
        out = self._inner_bulk(batch, side, ts)
        if out is not None:
            if out.num_rows:
                await collector.collect(out)
            return
        rows = batch.to_pylist()
        # deltas accumulate IN INPUT ORDER as (is_retract, row) so a
        # retract never overtakes the append it cancels within a batch
        deltas: List[Tuple[bool, tuple]] = []
        for row in rows:
            key = tuple(
                _norm(row[f"__key{i}"]) for i in range(self.n_keys)
            )
            payload = tuple(_norm(row[f]) for f in src_fields)
            meta = row.get(UPDATING_META_FIELD)
            self.last_seen[key] = ts
            if meta and meta.get("is_retract"):
                self._retract_row(side, key, payload, deltas)
            else:
                self._append_row(side, key, payload, deltas)
        # emit maximal same-kind runs as batches, preserving order
        i = 0
        while i < len(deltas):
            j = i
            while j < len(deltas) and deltas[j][0] == deltas[i][0]:
                j += 1
            batch_out = self._build(
                [d[1] for d in deltas[i:j]], deltas[i][0], ts
            )
            if batch_out is not None and batch_out.num_rows:
                await collector.collect(batch_out)
            i = j

    # -- device-probe bulk path (inner, append-only batches) ----------------

    def _inner_bulk(self, batch, side: int, ts: int):
        """Bulk inner-join delta for an all-append batch via the device
        merge-join probe (VERDICT r3 item 4: updating join's inner core
        rides ops/device_join.py): batch rows x the OTHER side's stored
        rows matched in one probe, output assembled columnar, state
        bulk-appended. Returns None when ineligible — per-row path.

        Sequential-equivalence: an append-only single-side batch only
        ever joins against the other side's STORE (same-side and
        same-batch rows never pair), and inner joins emit no outer
        transitions, so the bulk result equals the per-row loop's."""
        if self.join_type != "inner" or self.n_keys == 0:
            return None
        from ..config import config as get_config

        cfg = get_config().tpu
        from ..ops._jax import device_join_active

        if not device_join_active():
            return None
        # cheap per-batch disqualifiers BEFORE any O(store) work (key
        # scan, mirror rebuild): key-type codability, null keys anywhere
        # (per-row dict-equality semantics are authoritative for nulls),
        # retracts in the batch
        from ..ops import device_join

        names = batch.schema.names
        kcols = [f"__key{i}" for i in range(self.n_keys)]
        from ..ops.device_join import _codable

        if not all(
            _codable(batch.schema.field(names.index(k)).type)
            for k in kcols
        ):
            return None
        if any(
            batch.column(names.index(k)).null_count for k in kcols
        ) or self._store_has_null_key[0] or self._store_has_null_key[1]:
            return None
        if UPDATING_META_FIELD in names:
            retracts = batch.column(
                names.index(UPDATING_META_FIELD)
            ).field("is_retract")
            import pyarrow.compute as pc

            if pc.any(retracts).as_py():
                return None
        other_rows = sum(
            len(v) for v in self.state[1 - side].values()
        )
        if batch.num_rows + other_rows < cfg.device_join_min_rows:
            return None
        try:
            other_tab, other_payload_cols = self._other_side_cache(
                1 - side, batch
            )
        except (pa.ArrowInvalid, pa.ArrowTypeError, TypeError):
            return None
        bt = pa.table({k: batch.column(names.index(k)) for k in kcols})
        prep = device_join.prepare_join_keys(bt, other_tab, kcols)
        if prep is None:
            return None
        lcols, rcols, lsel, rsel = prep
        if lsel is not None or rsel is not None:
            # null join keys present: the per-row path's dict-equality
            # semantics (None == None matches) stay authoritative
            return None
        bi, si = device_join.probe(lcols, rcols)
        out = self._assemble_bulk(batch, side, bi, si,
                                  other_payload_cols, ts)
        self._bulk_append_state(batch, side, ts)
        return out

    def _other_side_cache(self, other: int, batch):
        """(key table, payload column lists) mirror of state[other].
        The mirror is plain python column lists: rebuilt with one
        O(store) pass after per-row mutations, EXTENDED in place by the
        bulk path's own appends (the common all-append stream never
        rebuilds). Arrow key arrays are cached as CHUNKS alongside the
        lists — the steady all-append state appends one chunk per batch
        instead of reconverting the whole store every call (ADVICE r4:
        the O(store) pa.array conversion dominated large stores)."""
        if self._col_cache[other] is None:
            store = self.state[other]
            n_fields = len(
                self.left_src if other == 0 else self.right_src
            )
            key_cols: List[list] = [[] for _ in range(self.n_keys)]
            pay_cols: List[list] = [[] for _ in range(n_fields)]
            for key, rows in store.items():
                for r in rows:
                    for i in range(self.n_keys):
                        key_cols[i].append(key[i])
                    for j in range(n_fields):
                        pay_cols[j].append(r[j])
            self._col_cache[other] = (key_cols, pay_cols)
            self._key_arr_cache[other] = None  # chunks rebuild below
        key_cols, pay_cols = self._col_cache[other]
        # key column types from the batch's key columns so the probe
        # compares like with like (ints stay ints, strings strings)
        names = batch.schema.names
        types = []
        for i in range(self.n_keys):
            t = batch.schema.field(names.index(f"__key{i}")).type
            if pa.types.is_timestamp(t):
                t = pa.int64()  # _norm stores int nanos
            types.append(t)
        if (self._key_arr_cache[other] is None
                or self._key_arr_types[other] != types):
            self._key_arr_cache[other] = [
                [pa.array(key_cols[i], type=types[i])]
                for i in range(self.n_keys)
            ]
            self._key_arr_types[other] = types
        arrays = {
            f"__key{i}": pa.chunked_array(self._key_arr_cache[other][i],
                                          type=types[i])
            for i in range(self.n_keys)
        }
        return pa.table(arrays), pay_cols

    def _assemble_bulk(self, batch, side, bi, si, other_payload_cols, ts):
        names = batch.schema.names
        n = len(bi)
        bi_a = pa.array(bi)
        lmap, rmap, kmap = self._lmap, self._rmap, self._kmap
        my_src = self.left_src if side == 0 else self.right_src
        my_map = lmap if side == 0 else rmap
        other_map = rmap if side == 0 else lmap
        arrays = []
        for f in self.out_schema.schema:
            if f.name in kmap:
                col = batch.column(
                    names.index(f"__key{kmap[f.name]}")
                )
                arrays.append(col.take(bi_a).cast(f.type))
            elif f.name == TIMESTAMP_FIELD:
                arrays.append(
                    pa.array(np.full(n, ts, dtype=np.int64)).cast(f.type)
                )
            elif f.name == UPDATING_META_FIELD:
                from ..schema import updating_meta_array

                arrays.append(updating_meta_array(n, False))
            elif f.name in my_map:
                src_name = my_src[my_map[f.name]]
                arrays.append(
                    batch.column(names.index(src_name))
                    .take(bi_a).cast(f.type)
                )
            elif f.name in other_map:
                vals = other_payload_cols[other_map[f.name]]
                arrays.append(
                    _col(vals, f.type).take(pa.array(si))
                )
            else:
                raise KeyError(f"updating join output missing {f.name}")
        out = pa.RecordBatch.from_arrays(
            arrays, schema=self.out_schema.schema
        )
        if self.residual is not None:
            out = out.filter(self.residual(out))
        return out

    def _bulk_append_state(self, batch, side, ts):
        names = batch.schema.names
        src = self.left_src if side == 0 else self.right_src
        key_lists = [
            [_norm(v) for v in
             batch.column(names.index(f"__key{i}")).to_pylist()]
            for i in range(self.n_keys)
        ]
        pay_lists = [
            [_norm(v) for v in batch.column(names.index(f)).to_pylist()]
            for f in src
        ]
        store = self.state[side]
        for r in range(batch.num_rows):
            key = tuple(kl[r] for kl in key_lists)
            payload = tuple(c[r] for c in pay_lists)
            store.setdefault(key, []).append(payload)
            self.last_seen[key] = ts
        # extend this side's mirror in place instead of invalidating it:
        # alternating left/right append streams would otherwise rebuild
        # the full opposite-side mirror every batch
        cache = self._col_cache[side]
        if cache is not None:
            ck, cp = cache
            for i in range(self.n_keys):
                ck[i].extend(key_lists[i])
            for j in range(len(pay_lists)):
                cp[j].extend(pay_lists[j])
            kac = self._key_arr_cache[side]
            if kac is not None:
                # one appended arrow chunk per batch keeps the chunked
                # key arrays in lockstep with the python lists; a
                # cross-side type mismatch (no key coercion between
                # sides) must degrade to a rebuild, not kill the task
                try:
                    for i in range(self.n_keys):
                        kac[i].append(pa.array(
                            key_lists[i], type=self._key_arr_types[side][i]
                        ))
                        if len(kac[i]) > 64:
                            # bound chunk count (and the per-probe concat
                            # cost) on long all-append streams
                            kac[i] = [
                                pa.chunked_array(kac[i]).combine_chunks()
                            ]
                except (pa.ArrowInvalid, pa.ArrowTypeError):
                    self._key_arr_cache[side] = None

    # join-delta helpers: rows are (key, left_payload|None, right_payload|None)

    def _null_padded(self, side: int, key: tuple, payload: tuple) -> tuple:
        return (key, payload, None) if side == 0 else (key, None, payload)

    def _joined(self, key: tuple, l: tuple, r: tuple) -> tuple:
        return (key, l, r)

    def _append_row(self, side, key, payload, deltas):
        out_append = _DeltaSink(deltas, False)
        out_retract = _DeltaSink(deltas, True)
        mine = self.state[side].setdefault(key, [])
        other = self.state[1 - side].get(key, [])
        other_outer = (
            self.join_type in ("left", "full") if side == 1
            else self.join_type in ("right", "full")
        )
        my_outer = (
            self.join_type in ("left", "full") if side == 0
            else self.join_type in ("right", "full")
        )
        if other:
            for o in other:
                l, r = (payload, o) if side == 0 else (o, payload)
                out_append.append(self._joined(key, l, r))
            # first row on MY side: the other side's null-padded rows retract
            if not mine and other_outer:
                for o in other:
                    out_retract.append(self._null_padded(1 - side, key, o))
        elif my_outer:
            out_append.append(self._null_padded(side, key, payload))
        mine.append(payload)
        self._col_cache[side] = None
        if any(k is None for k in key):
            self._store_has_null_key[side] = True

    def _retract_row(self, side, key, payload, deltas):
        out_append = _DeltaSink(deltas, False)
        out_retract = _DeltaSink(deltas, True)
        mine = self.state[side].get(key, [])
        try:
            mine.remove(payload)
        except ValueError:
            return  # retraction for an unknown row: drop
        self._col_cache[side] = None
        other = self.state[1 - side].get(key, [])
        other_outer = (
            self.join_type in ("left", "full") if side == 1
            else self.join_type in ("right", "full")
        )
        my_outer = (
            self.join_type in ("left", "full") if side == 0
            else self.join_type in ("right", "full")
        )
        if other:
            for o in other:
                l, r = (payload, o) if side == 0 else (o, payload)
                out_retract.append(self._joined(key, l, r))
            # last row on MY side gone: other side's rows become null-padded
            if not mine and other_outer:
                for o in other:
                    out_append.append(self._null_padded(1 - side, key, o))
        elif my_outer:
            out_retract.append(self._null_padded(side, key, payload))
        if not mine:
            self.state[side].pop(key, None)

    async def handle_watermark(self, watermark, ctx, collector):
        """TTL eviction of idle keys (the reference bounds updating state
        with updating_cache.rs the same way). Evicted keys silently drop
        their materialized rows — late retractions for them are ignored."""
        from ..types import WATERMARK_END, WatermarkKind

        if (
            watermark.kind == WatermarkKind.EVENT_TIME
            and self.ttl_nanos
            and watermark.timestamp < WATERMARK_END
        ):
            cutoff = watermark.timestamp - self.ttl_nanos
            stale = [k for k, seen in self.last_seen.items() if seen < cutoff]
            for k in stale:
                self.state[0].pop(k, None)
                self.state[1].pop(k, None)
                self.last_seen.pop(k, None)
            if stale:
                self._col_cache = [None, None]
        return watermark

    def serve_stage_snapshot(self, view) -> None:
        """Serve the join's current row set per key (ISSUE 20
        satellite). Called by seal_op at checkpoint capture: each key's
        joined rows — cross product when both sides match, null-padded
        per outer semantics otherwise — stage as `{"rows": [...]}`
        with output field names, the same shape a sink would
        accumulate. Snapshot cost is O(state), which is already this
        operator's per-checkpoint norm (handle_checkpoint puts the
        whole store). Keys whose row set vanished since the last
        capture are tombstoned; null-component keys are skipped (null
        never equals anything, so no row can join on it). register_op
        refuses residual joins a view entirely (see _view_plan)."""
        from ..serve.store import _plain

        left_outer = self.join_type in ("left", "full")
        right_outer = self.join_type in ("right", "full")
        prev = getattr(self, "_serve_join_keys", set())
        cur: set = set()
        for key in set(self.state[0]) | set(self.state[1]):
            if any(k is None for k in key):
                continue
            l_rows = self.state[0].get(key, [])
            r_rows = self.state[1].get(key, [])
            rows: List[dict] = []
            if l_rows and r_rows:
                for l in l_rows:
                    for r in r_rows:
                        row = dict(zip(self.left_out, l))
                        row.update(zip(self.right_out, r))
                        rows.append(row)
            elif l_rows and left_outer:
                pad = dict.fromkeys(self.right_out)
                for l in l_rows:
                    rows.append({**dict(zip(self.left_out, l)), **pad})
            elif r_rows and right_outer:
                pad = dict.fromkeys(self.left_out)
                for r in r_rows:
                    rows.append({**pad, **dict(zip(self.right_out, r))})
            if not rows:
                continue  # inner join with a lone side: nothing visible
            ck = view.canon_key(key)
            view.stage(
                ck,
                {"rows": [{f: _plain(v) for f, v in r.items()}
                          for r in rows]},
            )
            cur.add(ck)
        for ck in prev - cur:
            view.stage_tomb(ck)
        self._serve_join_keys = cur

    # -- output -------------------------------------------------------------

    def _build(self, rows: List[tuple], is_retract: bool, ts: int):
        n = len(rows)
        lmap, rmap, kmap = self._lmap, self._rmap, self._kmap
        arrays = []
        for f in self.out_schema.schema:
            if f.name in kmap:
                ki = kmap[f.name]
                arrays.append(
                    pa.array([r[0][ki] for r in rows], type=f.type)
                )
            elif f.name == TIMESTAMP_FIELD:
                arrays.append(
                    pa.array(np.full(n, ts, dtype=np.int64)).cast(f.type)
                )
            elif f.name == UPDATING_META_FIELD:
                from ..schema import updating_meta_array

                arrays.append(updating_meta_array(n, is_retract))
            elif f.name in lmap:
                li = lmap[f.name]
                arrays.append(_col(
                    [r[1][li] if r[1] is not None else None for r in rows],
                    f.type,
                ))
            elif f.name in rmap:
                ri = rmap[f.name]
                arrays.append(_col(
                    [r[2][ri] if r[2] is not None else None for r in rows],
                    f.type,
                ))
            else:
                raise KeyError(f"updating join output missing {f.name}")
        batch = pa.RecordBatch.from_arrays(
            arrays, schema=self.out_schema.schema
        )
        if self.residual is not None:
            mask = self.residual(batch)
            batch = batch.filter(mask)
        return batch


def _norm(v):
    """State values must be msgpack-serializable and hashable; pandas
    Timestamps become int nanos."""
    if isinstance(v, pd.Timestamp):
        return v.value
    return v


class _DeltaSink:
    """Appends (is_retract, row) onto the shared in-order delta list."""

    __slots__ = ("deltas", "is_retract")

    def __init__(self, deltas, is_retract):
        self.deltas = deltas
        self.is_retract = is_retract

    def append(self, row):
        self.deltas.append((self.is_retract, row))


def _col(vals, t: pa.DataType) -> pa.Array:
    if pa.types.is_timestamp(t):
        return pa.array(vals, type=pa.int64()).cast(t)
    return pa.array(vals, type=t)


def make_updating_join(config: dict) -> Operator:
    return UpdatingJoinOperator(config)
