"""Windowed aggregation operators: tumbling, sliding (hop), session.

Capability parity with the reference's window operators
(/root/reference/crates/arroyo-worker/src/arrow/
{tumbling,sliding,session}_aggregating_window.rs): event-time bins advance
with the watermark; tumbling emits a bin when the watermark passes its end;
sliding maintains slide-granularity partials merged per emitted window;
session windows gap-merge per key and emit when the watermark passes
last-event + gap. Late rows (whose windows already emitted) are dropped.

TPU-native redesign: instead of one DataFusion partial-aggregation stream
per bin, all (bin, key) groups share flat device accumulator arrays
(ops/aggregates.py) updated by one jitted scatter-reduce per batch; the
host-side SlotDirectory owns group->slot assignment. Emission gathers slots
to host once per watermark advance. Output rows carry
_timestamp = window_end - 1ns (inside the window, reference behavior) and
optional window start/end columns.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa

from ..engine.construct import register_operator
from ..graph.logical import OperatorName
from ..obs import timeline
from ..ops.aggregates import (
    AggSpec,
    float_state_stays_on_host,
    make_accumulator,
)
from ..ops.directory import KeyCodec, _to_py, make_directory
from ..schema import StreamSchema, TIMESTAMP_FIELD
from ..types import WatermarkKind
from ..utils.logging import get_logger
from .base import Operator
from .session_table import SessionTable

logger = get_logger("windows")


def _specs_from_config(config: dict) -> List[AggSpec]:
    return [
        AggSpec(
            kind=a["kind"],
            col=a.get("col"),
            name=a["name"],
            is_float=a.get("is_float", False),
            udaf=a.get("udaf"),
            col2=a.get("col2"),
            param=a.get("param"),
            distinct=a.get("distinct", False),
            replay=a.get("replay", False),
        )
        for a in config["aggregates"]
    ]


class WindowOperatorBase(Operator):
    """Shared machinery: accumulator, directory, output batch building."""

    flow_class = "buffering"  # holds rows across barriers until windows fire

    def __init__(self, config: dict, name: str):
        super().__init__(name)
        self.specs = _specs_from_config(config)
        self.key_cols: List[int] = list(config.get("key_cols", []))
        self.out_schema: StreamSchema = config["schema"]
        self.window_start_field: Optional[str] = config.get("window_start_field")
        self.window_end_field: Optional[str] = config.get("window_end_field")
        self.window_field: Optional[str] = config.get("window_field")
        self.backend = config.get("backend")
        mesh_n = self._mesh_devices(config)
        # planner marks aggregates whose every grouping key is the
        # window itself (one group per bin): hash ownership would
        # starve most shards, so those run SALTED — rows spread
        # round-robin across all shards, folded at gather. Device
        # phys ops are all fold-able (add/min/max); host-state
        # aggregates (UDAF buffers / multisets) are keyed by GLOBAL
        # slot and folded host-side, so they ride along unchanged.
        salted = bool(config.get("mesh_salted"))
        if mesh_n >= 2 and salted and not self._salted_on_mesh(mesh_n):
            # window-global groupings have no key axis to shard: on a
            # VIRTUAL (forced host-platform) mesh the salted spread
            # costs S x serial scatter work for a handful of groups, so
            # the stage runs on the standard single-device tier instead
            # (state stays device-resident; the keyed stages around it
            # keep the mesh exchange). Real chip meshes keep salting —
            # there the spread buys S x scatter bandwidth.
            mesh_n = 0
        if mesh_n >= 2:
            from ..parallel import ShardedAccumulator, key_mesh

            from ..config import config as config_fn

            self.acc = ShardedAccumulator(
                self.specs,
                key_mesh(self._mesh_device_list(mesh_n)),
                rows_per_shard=config_fn().tpu.mesh_rows_per_shard,
                salted=salted,
                flush_rows=config_fn().tpu.mesh_flush_rows,
            )
        else:
            self.acc = make_accumulator(self.specs, backend=self.backend)
        self._mesh_n, self._salted = mesh_n, salted
        # the (bin, key) -> slot table and the codec of its keys: both are
        # settled by the key types, which arrive with the first schema
        # (_capture_key_meta). A table that never sees a key needs none:
        # sessions allocate slots from theirs before that.
        self.dir = None if self._uses_assign else self._make_directory(None)
        self.codec: Optional[KeyCodec] = None
        self._key_types: Optional[List[pa.DataType]] = None
        self._key_names: Optional[List[str]] = None
        # columnar chunks of (slots, bins, key columns) touched since the
        # last checkpoint; captured at assign time so delta building is
        # O(dirty), not O(live keys). Kept columnar (numpy) — building a
        # python tuple per touched slot dominated high-cardinality
        # workloads. Deduped by slot (keep-last) at delta-build time.
        self._dirty_chunks: List[tuple] = []
        # rows across _dirty_chunks and the size right after the last
        # coalesce: chunks are squashed (keep-last per slot) whenever the
        # row count doubles past the floor, bounding memory between
        # checkpoints at O(distinct dirty slots) even when a hot key is
        # touched every batch over a long checkpoint interval
        self._dirty_rows = 0
        self._dirty_base = 0

    # whether rows reach their slots through the table's assign() (tumbling,
    # sliding, updating aggregates); session windows allocate slots in
    # blocks and keep their keys themselves (operators/session_table.py)
    _uses_assign = True
    # operators whose state protocol is slot-based end to end can run on
    # the mesh-sharded accumulator (tumbling, sliding, session)
    _mesh_ok = False

    def _mesh_devices(self, config: dict) -> int:
        if not self._mesh_ok or self.backend == "numpy":
            return 0
        n = self._cfg_mesh_devices(config)
        if n >= 2 and float_state_stays_on_host(self.specs):
            return 0  # make_accumulator below takes the numpy tier
        return n

    @staticmethod
    def _cfg_mesh_devices(config: dict) -> int:
        from ..config import config as config_fn

        n = config.get("mesh_devices")
        if n is None:
            n = config_fn().tpu.mesh_devices
        # deliberately NOT gated on require_accelerator/device_tier_active:
        # mesh mode only engages on an explicit mesh_devices >= 2, and
        # running it over a virtual CPU mesh is a supported deployment
        # (the multichip dryrun and the mesh tests validate sharding
        # compilation without accelerator hardware)
        return int(n or 0) if config_fn().tpu.enabled else 0

    @staticmethod
    def _mesh_device_list(n: int):
        from ..ops._jax import get_jax

        devices = get_jax().devices()
        if len(devices) < n:
            raise ValueError(
                f"tpu.mesh_devices={n} but only {len(devices)} devices "
                "are visible"
            )
        return devices[:n]

    def _salted_on_mesh(self, mesh_n: int) -> bool:
        """Should a SALTED (window-global) aggregate shard across the
        mesh? tpu.mesh_salted_tier: 'mesh' / 'single' force it; 'auto'
        salts only real chip meshes (parallel/mesh.mesh_is_virtual)."""
        from ..config import config as config_fn
        from ..parallel import key_mesh
        from ..parallel.mesh import mesh_is_virtual

        tier = str(getattr(config_fn().tpu, "mesh_salted_tier", "auto")
                   or "auto")
        if tier not in ("auto", "mesh", "single"):
            raise ValueError(
                f"tpu.mesh_salted_tier must be auto|mesh|single, "
                f"got {tier!r}"
            )
        if tier != "auto":
            return tier == "mesh"
        return not mesh_is_virtual(key_mesh(self._mesh_device_list(mesh_n)))

    def _make_directory(self, key_types):
        return make_directory(
            key_types, mesh_shards=self._mesh_n, salted=self._salted,
            uses_assign=self._uses_assign,
        )

    def _capture_key_meta(self, ctx):
        if self._key_types is None:
            in_schema = ctx.in_schemas[0].schema
            self._key_types = [in_schema.field(i).type for i in self.key_cols]
            self._key_names = [in_schema.field(i).name for i in self.key_cols]
            if self.dir is None:
                self.dir = self._make_directory(self._key_types)
            self.codec = KeyCodec(self._key_types, self.dir.key_encoding)
            self._log_tier()

    def _log_tier(self):
        """The line a window operator logs once, when its key types
        have settled the directory: which tiers it took, on what
        platform."""
        from ..ops import _jax

        acc = self.acc
        exchange = getattr(acc, "_exchange", None)
        note = ""
        if exchange:
            note = f" mesh={acc.n_shards} exchange={exchange}"
        elif acc.backend == "numpy" and float_state_stays_on_host(self.specs):
            note = " (float64 accumulators: no IEEE float64 on this device)"
        logger.info(
            "window %s: accumulator=%s%s directory=%s platform=%s "
            "capacity=%d",
            self.name, acc.backend, note,
            type(self.dir).__name__, _jax.platform(), acc.capacity,
        )

    def _ensure_capacity(self):
        need = self.dir.required_capacity()
        if need > self.acc.capacity - 1:
            with timeline.phase("win.grow") as ph:
                self.acc.grow(need + 1)
                ph.n = self.acc.capacity

    def _scatter(self, batch: pa.RecordBatch, bins: np.ndarray,
                 keys: List[np.ndarray], ctx):
        """The shared tail of a batch in the tumbling and sliding
        operators: (bin, key) -> slot, room for the new slots, the dirty
        marks of an incremental checkpoint, then the accumulator's scatter.
        Each step is a leaf of the phase ledger."""
        live = self.dir.n_live
        with timeline.phase("dir.assign", n=len(bins)):
            slots = self.dir.assign(bins, keys)
        # a count, no duration: the slots this call created (`assign`
        # frees none), beside the rows `dir.assign` books
        timeline.note("dir.new", 0.0, n=self.dir.n_live - live)
        self._ensure_capacity()
        if ctx.table_manager is not None and self._use_incremental():
            with timeline.phase("win.dirty", n=len(slots)):
                self._mark_dirty(slots, bins, keys)
        with timeline.phase("win.cols", n=batch.num_rows):
            cols = self._agg_input_cols(batch)
        self.acc.update(slots, cols)

    # -- incremental checkpoints --------------------------------------------
    # Window state checkpoints write only the (bin, key) groups whose slots
    # changed since the previous epoch into an expiring_time_key table; the
    # cumulative file list rides in the manifest and retention (keyed to the
    # row's window-end timestamp) prunes emitted windows on restore.
    # Mirrors the reference's incremental ExpiringTimeKeyTable design
    # (/root/reference/crates/arroyo-state/src/tables/
    # expiring_time_key_map.rs:53, flush in table_manager.rs:368).

    def _mark_dirty(self, slots: np.ndarray, bins: np.ndarray,
                    key_cols: List[np.ndarray]):
        """Record (bin, portable key) per touched slot. A stale mapping
        (slot emitted+freed before the checkpoint) writes a row whose bin
        is already behind the watermark — pruned by retention on restore —
        so no directory scan is ever needed."""
        if not len(slots):
            return
        uniq, first = np.unique(slots, return_index=True)
        norm = []
        for c in key_cols:
            c = np.asarray(c)
            if c.dtype == np.uint64:
                c = c.view(np.int64)
            elif c.dtype.kind == "M":
                c = c.view("i8")
            norm.append(c[first])
        self._dirty_chunks.append(
            (uniq, np.asarray(bins)[first].astype(np.int64, copy=False),
             norm)
        )
        self._dirty_rows += len(uniq)
        # amortized O(1) per row: squash only once the count doubles
        # since the last squash (floor 64k rows)
        if self._dirty_rows > max(65536, 2 * self._dirty_base):
            self._dirty_chunks = [self._coalesce_dirty()]
            self._dirty_rows = self._dirty_base = len(
                self._dirty_chunks[0][0]
            )

    def _coalesce_dirty(self) -> tuple:
        """Concatenate all dirty chunks and keep the LAST mark per slot
        (a slot freed and reassigned must report its newest (bin, key))."""
        chunks = self._dirty_chunks
        slots = np.concatenate([c[0] for c in chunks])
        bins = np.concatenate([c[1] for c in chunks])
        n_kc = len(chunks[0][2])
        key_cols = [
            np.concatenate([c[2][i] for c in chunks]) for i in range(n_kc)
        ]
        _, idx_rev = np.unique(slots[::-1], return_index=True)
        keep = len(slots) - 1 - idx_rev
        return slots[keep], bins[keep], [c[keep] for c in key_cols]

    def _use_incremental(self) -> bool:
        """Struct keys (window structs) hash differently in the parquet
        snapshot than on the shuffle, and host-state aggregates (UDAF
        buffers, count_distinct multisets) are variable-length — both fall
        back to the full-snapshot global table."""
        if self._key_types is None:
            return False
        if any(s.host_state() is not None for s in self.specs):
            return False
        return not any(pa.types.is_struct(t) for t in self._key_types)

    def _delta_key_fields(self) -> tuple:
        return tuple(f"__k{i}" for i in range(len(self.key_cols)))

    def _build_delta_batch(self, bin_ts):
        """Delta thunk for dirty slots: keys/bins were captured at assign
        time (O(dirty)), the accumulator gather is *dispatched* now against
        the current device state, and the returned zero-arg callable
        materializes the RecordBatch (__ts = bin_ts(bin), __bin, __k*,
        __v*) on the flush path — so the device->host copy overlaps the
        next epoch's processing. The gather hands back its bucket's
        padded arrays; the slice to the slots' count is taken here,
        behind the copy, on the host."""
        if not self._dirty_chunks:
            return None
        slots, bins, key_cols = self._coalesce_dirty()
        self._dirty_chunks = []
        self._dirty_rows = self._dirty_base = 0
        # a count, no duration: the rows this capture's delta carries
        timeline.note("ckpt.delta", 0.0, n=len(slots))
        values = self.acc.snapshot(slots, materialize=False)

        def build() -> pa.RecordBatch:
            arrays = [pa.array(bin_ts(bins)), pa.array(bins)]
            names = ["__ts", "__bin"]
            for i, arr in enumerate(self.codec.delta_arrays(key_cols)):
                arrays.append(arr)
                names.append(f"__k{i}")
            for j, v in enumerate(values):
                arrays.append(pa.array(np.asarray(v)[: len(slots)]))
                names.append(f"__v{j}")
            return pa.RecordBatch.from_arrays(arrays, names=names)

        return build

    async def _checkpoint_window_state(self, ctx, inc_table: str,
                                       bin_ts) -> dict:
        """Stage the incremental delta (or legacy full snapshot when not
        eligible) and return the meta snap to extend + put."""
        if self._use_incremental():
            delta = self._build_delta_batch(bin_ts)
            if delta is not None:
                (await ctx.table(inc_table)).write_delta(delta)
            return {"bins": [], "keys": [], "values": []}
        return self._snapshot_rows()

    async def _restore_incremental(self, ctx, inc_table: str):
        """Rebuild directory+accumulator from incremental delta files.
        Later rows supersede earlier ones per (bin, key); the table manager
        already applied key-range and retention filters."""
        table = await ctx.table(inc_table)
        if self._key_types is None:
            return
        newest: Dict[tuple, list] = {}
        n_phys = len(self.acc.phys)
        for b in table.all_batches():
            names = b.schema.names
            bins = np.asarray(b.column(names.index("__bin")))
            key_cols = self.codec.columns_from_delta(b)
            vals = [
                np.asarray(b.column(names.index(f"__v{j}")))
                for j in range(n_phys)
            ]
            for r in range(b.num_rows):
                k = (int(bins[r]), tuple(c[r] for c in key_cols))
                newest[k] = [v[r] for v in vals]
        if not newest:
            return
        bins_l, keys_l = [], []
        cols: List[list] = [[] for _ in range(n_phys)]
        for (b_, key_t), vv in newest.items():
            bins_l.append(b_)
            keys_l.append(list(key_t))
            for j, v in enumerate(vv):
                cols[j].append(v)
        self._restore_rows(
            {"bins": bins_l, "keys": keys_l, "values": cols}, ctx
        )
        # conduit table: in-memory source of truth is the accumulator
        table.clear_batches()

    def _agg_input_cols(self, batch: pa.RecordBatch) -> Dict:
        """Column arrays for the accumulator. Numeric (device-phys) specs
        that actually read their column ('col'-sourced phys ops — count's
        phys reads the constant 1, never the column) claim plain keys with
        the cast the reduction needs; host-state specs (UDAF buffers,
        count_distinct multisets) always get the raw uncast values under
        ('raw', col) so strings survive and BIGINTs shared with a float
        spec don't collapse above 2^53."""
        cols: Dict = {}

        def claim(c: int):
            # cast by COLUMN type: float columns stay float64, everything
            # else (ints, bools, timestamps) flattens to int64 bit-friendly
            # values; derived sources (sq/prod) re-cast to float64 at use
            if c in cols:
                return
            arr = batch.column(c)
            if pa.types.is_floating(arr.type):
                cols[c] = np.asarray(
                    arr.to_numpy(zero_copy_only=False), dtype=np.float64
                )
            else:
                cols[c] = np.asarray(
                    arr.cast(pa.int64()).to_numpy(zero_copy_only=False)
                )

        for spec in self.specs:
            if spec.host_state() is not None:
                continue
            for _, _, src in spec.phys():
                if src in ("col", "sq", "prod"):
                    claim(spec.col)
                if src in ("col2", "sq2", "prod"):
                    claim(spec.col2)
        for spec in self.specs:
            if spec.col is None or spec.host_state() is None:
                continue
            for c in (spec.col, spec.col2):
                if c is not None and ("raw", c) not in cols:
                    cols[("raw", c)] = np.asarray(
                        batch.column(c).to_numpy(zero_copy_only=False)
                    )
        return cols

    def _build_output(
        self,
        keys: List[tuple],
        agg_cols: List[np.ndarray],
        start: int,
        end: int,
        ts_value: Optional[int] = None,
        key_arrays: Optional[List[np.ndarray]] = None,
        serve_stage: bool = True,
    ) -> pa.RecordBatch:
        """Build an output batch for one window [start, end). `key_arrays`
        (one int64 array per key word, raw directory bit-patterns; or the
        session table's stored columns) is the vectorized path of the
        native-directory emit and of sessions — no python tuple per key. start/end/ts_value may be scalars (one window) or
        per-row arrays (batched session emission)."""
        n = len(key_arrays[0]) if key_arrays else len(keys)

        def const_or_arr(v):
            if isinstance(v, np.ndarray):
                return v.astype(np.int64, copy=False)
            return np.full(n, v, dtype=np.int64)

        window_field = getattr(self, "window_field", None)
        arrays = []
        for f in self.out_schema.schema:
            if f.name == TIMESTAMP_FIELD:
                ts = ts_value if ts_value is not None else end - 1
                arrays.append(
                    pa.array(const_or_arr(ts)).cast(f.type)
                )
            elif f.name == window_field and pa.types.is_struct(f.type):
                s = pa.array(const_or_arr(start)).cast(f.type.field(0).type)
                e = pa.array(const_or_arr(end)).cast(f.type.field(1).type)
                arrays.append(
                    pa.StructArray.from_arrays(
                        [s, e], names=[f.type.field(0).name,
                                       f.type.field(1).name]
                    )
                )
            elif f.name == self.window_start_field:
                arrays.append(
                    pa.array(const_or_arr(start)).cast(f.type)
                )
            elif f.name == self.window_end_field:
                arrays.append(
                    pa.array(const_or_arr(end)).cast(f.type)
                )
            elif f.name in (self._key_names or []):
                ki = self._key_names.index(f.name)
                arrays.append(
                    self.codec.arrow_from_arrays(ki, key_arrays)
                    if key_arrays is not None
                    else self.codec.arrow_from_keys(ki, keys)
                )
            else:
                ai = next(
                    j for j, s in enumerate(self.specs) if s.name == f.name
                )
                col = agg_cols[ai]
                if pa.types.is_floating(f.type):
                    arrays.append(pa.array(col.astype(np.float64), type=f.type))
                elif pa.types.is_boolean(f.type):
                    arrays.append(pa.array(col.astype(bool)))
                elif pa.types.is_list(f.type):
                    arrays.append(pa.array(
                        [[_to_py(x) for x in v] for v in col], type=f.type
                    ))
                else:
                    arrays.append(pa.array(col.astype(np.int64), type=f.type))
        out = pa.RecordBatch.from_arrays(arrays, schema=self.out_schema.schema)
        if serve_stage and self._serve_view is not None:
            # StateServe: mirror the emitted window results into the
            # serve view's stage buffer (sealed at the next checkpoint
            # capture; reads see them once that epoch publishes).
            # serve_stage=False is the session-partial snapshot path,
            # which stages its batch itself with the partial flag set.
            from ..serve import stage_batch

            # a sub-step of the caller's close.build, in the ledger only
            with timeline.phase("close.build.stage", n=out.num_rows,
                                annotate=False):
                stage_batch(self._serve_view, out)
        return out

    # -- checkpoint form ----------------------------------------------------

    def _snapshot_rows(self) -> dict:
        """Directory + accumulator values as plain lists (checkpoint form).
        Interned key codes are resolved to their values: codes are
        process-local and must never leave the process."""
        bins, keys, slots = [], [], []
        for b, key, slot in self.dir.items():
            bins.append(int(b))
            keys.append(self.codec.values(key))
            slots.append(int(slot))
        slots_arr = np.asarray(slots, dtype=np.int64)
        values = self.acc.snapshot(slots_arr) if len(slots) else []
        return {"bins": bins, "keys": keys, "values": [v.tolist() for v in values]}

    def _restore_rows(self, snap: dict, ctx=None):
        """Rebuild directory+accumulator from a snapshot. Snapshots from ALL
        pre-restart subtasks are replayed; rows outside this subtask's key
        range are skipped, which makes rescaling a restore-time re-read
        (reference: key-range sharding, arroyo-types lib.rs:640)."""
        bins = snap["bins"]
        if not bins:
            return
        keys = snap["keys"]
        mask = self._range_mask(keys, ctx)
        if mask is not None:
            bins = [b for b, m in zip(bins, mask) if m]
            keys = [k for k, m in zip(keys, mask) if m]
            if not bins:
                return
        key_cols = self.codec.columns_from_values(keys)
        bins_arr = np.asarray(bins, dtype=np.int64)
        slots = self.dir.assign(bins_arr, key_cols)
        self._ensure_capacity()
        # trailing host-state columns (UDAF buffers / count-distinct
        # multisets) are per-slot variable-length lists: force 1-d object
        # arrays — np.asarray on ragged nested lists raises, and on
        # same-length lists it would silently build a 2-d numeric array
        n_phys = len(self.acc.phys)
        values = []
        for j, v in enumerate(snap["values"]):
            if j < n_phys:
                values.append(np.asarray(v))
            else:
                arr = np.empty(len(v), dtype=object)
                arr[:] = v
                values.append(arr)
        if mask is not None:
            marr = np.asarray(mask)
            values = [v[marr] for v in values]
        self.acc.restore(slots, values)
        # rows restored from a legacy full snapshot have no delta files;
        # mark them dirty so the first incremental checkpoint after restore
        # persists them (otherwise a later crash would lose every group not
        # touched since the format upgrade). Non-incremental operators
        # snapshot the whole directory anyway — marking would only grow
        # chunks nothing ever drains.
        if self._use_incremental():
            self._mark_dirty(slots, bins_arr, key_cols)

    def _range_mask(self, keys: List[list], ctx) -> Optional[List[bool]]:
        """True per row iff the key hashes into this subtask's range."""
        if ctx is None or ctx.task_info.parallelism <= 1 or not keys:
            return None
        if not self.key_cols:
            return None
        from ..types import hash_arrays, server_for_hash_array

        # dtypes must match what the shuffle hashed (schema.hash_keys)
        cols = self.codec.hash_columns(keys)
        owners = server_for_hash_array(
            hash_arrays(cols), ctx.task_info.parallelism
        )
        return list(owners == ctx.task_info.task_index)


def _snaps_for_me(table, ctx, keyed: bool):
    """Snapshots this subtask should replay: keyed state replays every
    subtask's snapshot (rows are filtered by key range inside
    _restore_rows); unkeyed state maps old subtask i onto new subtask
    i % parallelism so exactly one new subtask owns each old snapshot."""
    p = ctx.task_info.parallelism
    for snap in table.all_values():
        if snap is None:
            continue
        if keyed or snap.get("subtask", 0) % p == ctx.task_info.task_index:
            yield snap


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class TumblingWindowOperator(WindowOperatorBase):
    _mesh_ok = True

    """Fixed-width windows: bin = ts // width; emit at watermark >= end
    (reference tumbling_aggregating_window.rs:66-321).

    width_nanos == 0 is *instant* mode: rows group by their exact
    _timestamp — used to aggregate already-windowed streams
    (GROUP BY window), where every row of a window shares one timestamp."""

    def __init__(self, config: dict):
        super().__init__(config, "tumbling_window")
        self.width = int(config.get("width_nanos", 0))
        self.emitted_up_to: Optional[int] = None  # last emitted bin END

    def tables(self):
        from ..state.table_config import global_table, time_key_table

        # retention ties the delta rows' __ts (= bin end - 1, or the raw
        # instant timestamp) to the watermark: rows whose window already
        # emitted at the checkpointed watermark are pruned on restore.
        # Instant mode (width 0) emits at wm >= ts, hence retention -1
        # keeps exactly ts > wm.
        return {
            "t": global_table("t"),
            "ti": time_key_table(
                "ti",
                retention_nanos=0 if self.width else -1,
                timestamp_field="__ts",
                key_fields=self._delta_key_fields(),
            ),
        }

    def _delta_ts(self, bins: np.ndarray) -> np.ndarray:
        return (bins + 1) * self.width - 1 if self.width else bins

    async def on_start(self, ctx):
        self._capture_key_meta(ctx)
        if ctx.table_manager is not None:
            table = await ctx.table("t")
            for snap in _snaps_for_me(table, ctx, bool(self.key_cols)):
                if snap.get("emitted_up_to") is not None:
                    self.emitted_up_to = max(
                        self.emitted_up_to or 0, snap["emitted_up_to"]
                    )
                self._restore_rows(snap, ctx)
            await self._restore_incremental(ctx, "ti")

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            table = await ctx.table("t")
            snap = await self._checkpoint_window_state(
                ctx, "ti", self._delta_ts
            )
            snap["emitted_up_to"] = self.emitted_up_to
            snap["subtask"] = ctx.task_info.task_index
            table.put(ctx.task_info.task_index, snap)

    def _bin_of(self, ts: np.ndarray) -> np.ndarray:
        return ts // self.width if self.width else ts

    def _bin_end(self, b: int) -> int:
        return (b + 1) * self.width if self.width else b

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        with timeline.phase("win.keys", n=batch.num_rows):
            self._capture_key_meta(ctx)
            ts = ctx.in_schemas[0].timestamps(batch)
            bins = self._bin_of(ts)
            if self.emitted_up_to is not None:
                if self.width:
                    live = (bins + 1) * self.width > self.emitted_up_to
                else:
                    live = bins > self.emitted_up_to
                if not live.all():
                    if not live.any():
                        return
                    batch = batch.filter(pa.array(live))
                    bins = bins[live]
            keys = self.codec.columns(batch, self.key_cols)
        self._scatter(batch, bins, keys, ctx)

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME:
            return watermark
        t = watermark.timestamp
        limit = _ceil_div(t, self.width) if self.width else t + 1
        # mesh accumulators fuse gather+reset into one device program
        # (halves the per-wave emission dispatches); host-state drops
        # then happen after finalize has read the stores
        fused = getattr(self.acc, "gather_and_reset", None)
        # ONE device drain for the whole wave: per-bin slot sets of the
        # same watermark advance concatenate into a single gather/take
        # dispatch (the old per-bin loop launched one device program per
        # bin — ~30 near-empty mesh.take dispatches per wave on the q5
        # per-window-max stage), then outputs slice back out per bin
        wave = []  # (bin, end, keys, key_arrays, slots)
        due = [b for b in self.dir.bins_up_to(limit)
               if self._bin_end(b) <= t]
        if not due:
            return watermark
        # the leaves of a close, keyed by the last window's end
        last_end = self._bin_end(due[-1])
        with timeline.phase("close.take", key=last_end) as ph:
            for b in due:
                if self.codec.words:
                    # key columns stay numpy end-to-end
                    key_arrays, slots = self.dir.take_bin_arrays(b)
                    keys: List[tuple] = []
                else:
                    keys, slots = self.dir.take_bin(b)
                    key_arrays = None
                wave.append((b, self._bin_end(b), keys, key_arrays, slots))
            all_slots = (
                wave[0][4] if len(wave) == 1
                else np.concatenate([w[4] for w in wave])
            )
            ph.n = len(all_slots)
        with timeline.phase("close.combine", key=last_end,
                            n=len(all_slots)):
            gathered = (
                fused(all_slots) if fused is not None
                else self.acc.gather(all_slots)
            )
        with timeline.phase("close.finalize", key=last_end):
            agg_cols = self.acc.finalize(gathered)
        with timeline.phase("close.reset", key=last_end):
            if fused is not None:
                self.acc.drop_host_state(all_slots)
            else:
                self.acc.reset_slots(all_slots)
        off = 0
        for b, end, keys, key_arrays, slots in wave:
            n = len(slots)
            with timeline.phase("close.build", key=end, n=n):
                cols_b = [c[off:off + n] for c in agg_cols]
                off += n
                if self.width:
                    out = self._build_output(
                        keys, cols_b, b * self.width, end,
                        key_arrays=key_arrays)
                else:
                    # instant mode: preserve the window's timestamp exactly
                    out = self._build_output(keys, cols_b, b, b, ts_value=b,
                                             key_arrays=key_arrays)
            with timeline.phase("close.emit", key=end, n=out.num_rows,
                                annotate=False):
                await collector.collect(out)
            self.emitted_up_to = max(self.emitted_up_to or 0, end)
        return watermark


class SlidingWindowOperator(WindowOperatorBase):
    """Hop windows: slide-granularity partial bins; each emitted window
    merges width/slide bins (reference sliding_aggregating_window.rs:64-753).
    Requires width % slide == 0."""

    _mesh_ok = True

    def __init__(self, config: dict):
        super().__init__(config, "sliding_window")
        self.width = int(config["width_nanos"])
        self.slide = int(config["slide_nanos"])
        assert self.slide > 0 and self.width % self.slide == 0, (
            "window width must be a positive multiple of slide"
        )
        self.k = self.width // self.slide
        self.next_emit: Optional[int] = None
        self.last_freed_bin: Optional[int] = None

    def tables(self):
        from ..state.table_config import global_table, time_key_table

        # a slide-granularity bin stays live until it exits its last
        # window: freed <=> bin_end <= wm - width + slide, so retention
        # width - slide over __ts = bin_end - 1 prunes exactly freed bins
        return {
            "s": global_table("s"),
            "si": time_key_table(
                "si",
                retention_nanos=self.width - self.slide,
                timestamp_field="__ts",
                key_fields=self._delta_key_fields(),
            ),
        }

    def _delta_ts(self, bins: np.ndarray) -> np.ndarray:
        return (bins + 1) * self.slide - 1

    async def on_start(self, ctx):
        self._capture_key_meta(ctx)
        if ctx.table_manager is not None:
            table = await ctx.table("s")
            for snap in _snaps_for_me(table, ctx, bool(self.key_cols)):
                if snap.get("next_emit") is not None:
                    self.next_emit = (
                        snap["next_emit"] if self.next_emit is None
                        else min(self.next_emit, snap["next_emit"])
                    )
                if snap.get("last_freed_bin") is not None:
                    self.last_freed_bin = (
                        snap["last_freed_bin"] if self.last_freed_bin is None
                        else min(self.last_freed_bin, snap["last_freed_bin"])
                    )
                self._restore_rows(snap, ctx)
            await self._restore_incremental(ctx, "si")

    async def handle_checkpoint(self, barrier, ctx, collector):
        if ctx.table_manager is not None:
            table = await ctx.table("s")
            snap = await self._checkpoint_window_state(
                ctx, "si", self._delta_ts
            )
            snap["next_emit"] = self.next_emit
            snap["last_freed_bin"] = self.last_freed_bin
            snap["subtask"] = ctx.task_info.task_index
            table.put(ctx.task_info.task_index, snap)

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        with timeline.phase("win.keys", n=batch.num_rows):
            self._capture_key_meta(ctx)
            ts = ctx.in_schemas[0].timestamps(batch)
            bins = ts // self.slide
            if self.last_freed_bin is not None:
                live = bins > self.last_freed_bin
                if not live.all():
                    if not live.any():
                        return
                    batch = batch.filter(pa.array(live))
                    bins = bins[live]
            if self.next_emit is None and len(bins):
                self.next_emit = (int(bins.min()) + 1) * self.slide
            keys = self.codec.columns(batch, self.key_cols)
        self._scatter(batch, bins, keys, ctx)

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME:
            return watermark
        t = watermark.timestamp
        while self.next_emit is not None and self.next_emit <= t:
            await self._emit_window(self.next_emit, collector)
            if not self.dir.by_bin:
                self.next_emit = None  # drained; restart at next data
            else:
                self.next_emit += self.slide
        return watermark

    async def _emit_window(self, end: int, collector):
        end_bin = end // self.slide  # window covers bins [end_bin-k, end_bin)
        lo_bin = end_bin - self.k
        # merge per-key across participating bins (host merge: runs once per
        # slide period; the per-event scatter stays on device).
        # The bin exiting the window (lo_bin) is TAKEN from the directory
        # up front so its entries lead the union: the accumulator can then
        # gather the union and reset the freed bin in ONE fused device
        # dispatch (combine_for_segments_and_free) instead of a gather
        # followed by a separate reset program launch per wave.
        key_chunks = []
        slot_chunks = []
        with timeline.phase("close.take", key=end) as ph:
            if self.codec.words:
                fk_cols, freed = self.dir.take_bin_arrays(lo_bin)
                if len(freed):
                    key_chunks.append(np.stack(fk_cols, axis=1))
                    slot_chunks.append(freed)
                # ONE batched crossing covering every participating bin
                # (the merge unions keys across bins, so per-bin identity
                # is irrelevant) instead of k get_bin calls — k x shards
                # calls on the mesh facade
                kmat, slots_m = self.dir.bin_entries_multi(
                    np.arange(lo_bin + 1, end_bin, dtype=np.int64)
                )
                if len(slots_m):
                    key_chunks.append(kmat)
                    slot_chunks.append(slots_m)
            else:
                fk, freed = self.dir.take_bin(lo_bin)
                if len(freed):
                    key_chunks.append(fk)
                    slot_chunks.append(freed)
                for b in range(lo_bin + 1, end_bin):
                    keys_b, slots_b = self.dir.bin_entries(b)
                    if len(slots_b):
                        key_chunks.append(keys_b)
                        slot_chunks.append(slots_b)
            if slot_chunks:
                all_slots = np.concatenate(slot_chunks)
                ph.n = len(all_slots)
        if slot_chunks:
            with timeline.phase("close.union", key=end) as ph:
                seg_ids, out_keys, key_arrays, n_keys = self._key_union(
                    key_chunks, len(all_slots))
                ph.n = n_keys
            with timeline.phase("close.combine", key=end,
                                n=len(all_slots)):
                combined = self.acc.combine_for_segments_and_free(
                    all_slots, seg_ids, n_keys, free_n=len(freed)
                )
            with timeline.phase("close.finalize", key=end, n=n_keys):
                agg_cols = self.acc.finalize(combined)
            with timeline.phase("close.build", key=end, n=n_keys):
                out_batch = self._build_output(
                    out_keys, agg_cols, end - self.width, end,
                    key_arrays=key_arrays,
                )
            with timeline.phase("close.emit", key=end,
                                n=out_batch.num_rows, annotate=False):
                await collector.collect(out_batch)
        self.last_freed_bin = max(self.last_freed_bin or lo_bin, lo_bin)

    def _key_union(self, key_chunks: list, n_slots: int):
        """Union of the participating bins' keys: (segment id per slot,
        output keys as tuples, output key columns, distinct keys)."""
        key_arrays = None
        if self.codec.words:
            # vectorized key-union over int64 key matrices
            # (count, n_keycols); keys stay numpy end-to-end (no python
            # tuple per key)
            all_keys = np.concatenate(key_chunks)
            if all_keys.shape[1] == 1:
                # 1-D unique is markedly faster than axis=0
                u1, seg_ids = np.unique(
                    all_keys[:, 0], return_inverse=True
                )
                uniq = u1[:, None]
            else:
                uniq, seg_ids = np.unique(
                    all_keys, axis=0, return_inverse=True
                )
            seg_ids = np.asarray(seg_ids).ravel()
            if self.key_cols:
                out_keys = []
                # one column per flat key word (struct children ride
                # as separate words under the flat layout)
                key_arrays = [
                    uniq[:, j] for j in range(uniq.shape[1])
                ]
            else:
                out_keys = [() for _ in range(len(uniq))]
            return seg_ids, out_keys, key_arrays, len(uniq)
        index: Dict[tuple, int] = {}
        seg = np.empty(n_slots, dtype=np.int64)
        i = 0
        for chunk in key_chunks:
            for key in chunk:
                seg[i] = index.setdefault(key, len(index))
                i += 1
        return seg, list(index.keys()), key_arrays, len(index)


def _tolist(col) -> list:
    """Portable list view of one snapshot column slice (numpy scalar
    arrays or ragged host-state object arrays)."""
    if isinstance(col, np.ndarray):
        return col.tolist()
    return list(col)


def _batch_group_codes(key_cols: List[np.ndarray], n: int) -> np.ndarray:
    """Per-row group code over the key columns, local to ONE batch:
    non-integer columns factorize via pandas (no entry in the process-
    wide intern table — session keys expire, interning them would leak)."""
    if not key_cols:
        return np.zeros(n, dtype=np.int64)
    import pandas as pd

    norm = []
    for c in key_cols:
        c = np.asarray(c)
        if c.dtype.kind == "M":
            c = c.view("i8")
        elif c.dtype == np.uint64:
            c = c.view(np.int64)
        if c.dtype.kind not in "iub":
            try:
                c = pd.factorize(c)[0]
            except TypeError:   # unhashable values (a list column)
                c = pd.factorize(pd.Series(c).astype(str))[0]
        norm.append(c.astype(np.int64, copy=False))
    if len(norm) == 1:
        _, inverse = np.unique(norm[0], return_inverse=True)
        return inverse.ravel()
    _, inverse = np.unique(np.stack(norm, axis=1), axis=0,
                           return_inverse=True)
    return inverse.ravel()


class SessionWindowOperator(WindowOperatorBase):
    """Per-key gap-merged sessions
    (reference session_aggregating_window.rs:51-942). The open sessions
    are rows of arrays (`operators/session_table.py`): a batch is cut into
    segments (one key, rows less than the gap apart), the segments' keys
    are looked up together, hits extend their session and misses open one
    from a single `alloc_slots` call; only a key with several open sessions
    (rows far out of order, a row that bridges two) is placed one segment
    at a time. A watermark finds what it closes with one array expression
    over the open rows. The accumulator takes the tier every window
    operator takes (`make_accumulator`; sharded over the mesh in mesh
    mode): a scatter a batch, and a gather and a reset of the closed slots
    on every watermark that closes any (reference treats all window types
    uniformly)."""

    _mesh_ok = True
    _uses_assign = False

    def __init__(self, config: dict):
        super().__init__(config, "session_window")
        self.gap = int(config["gap_nanos"])
        assert self.gap > 0
        # made with the key types, which arrive with the first schema
        self._table: Optional[SessionTable] = None
        self._next_shard = 0

    def _capture_key_meta(self, ctx):
        super()._capture_key_meta(ctx)
        if self._table is None:
            self._table = SessionTable(
                self.gap,
                [self.codec.int_like(i) for i in range(len(self.key_cols))],
            )

    @property
    def sessions(self) -> Dict[tuple, List[List[int]]]:
        """{key: [[start, last, slot], ...]} of the open sessions, by
        start: a view for tests and debugging, built on demand."""
        t = self._table
        if t is None or not t.n_live:
            return {}
        rows = t.live_rows()
        cols = self.codec.value_lists([k[rows] for k in t.keys])
        out: Dict[tuple, List[List[int]]] = {}
        for i, r in enumerate(rows.tolist()):
            out.setdefault(tuple(c[i] for c in cols), []).append(
                [int(t.start[r]), int(t.last[r]), int(t.slot[r])])
        for v in out.values():
            v.sort()
        return out

    def _alloc(self, n: int) -> np.ndarray:
        """n accumulator slots from one directory call (the mesh facade
        deals them round-robin over the shards from `_next_shard`)."""
        slots = self.dir.alloc_slots(n, self._next_shard)
        self._next_shard += n
        return slots

    def tables(self):
        from ..state.table_config import global_table

        return {"sess": global_table("sess")}

    async def on_start(self, ctx):
        self._capture_key_meta(ctx)
        if ctx.table_manager is None:
            return
        table = await ctx.table("sess")
        if not self.key_cols:
            # unkeyed (window-global) sessions keep the legacy
            # per-subtask snapshot — there is no key to partition by
            for snap in _snaps_for_me(table, ctx, False):
                self._restore_snapshot(snap, ctx)
            return
        legacy, per_key = [], []
        for k, v in table.items():
            if isinstance(k, tuple) and k and k[0] == "sk":
                per_key.append((k, v))
            elif isinstance(v, dict) and "sessions" in v:
                legacy.append(v)
        for snap in legacy:
            self._restore_snapshot(snap, ctx)
        kept = self._restore_per_key(per_key, ctx)
        # each subtask's chain carries ONLY its own keys from here on:
        # out-of-range entries (and replayed legacy snaps) are owned and
        # re-persisted by their own subtasks this same epoch, so they are
        # pruned without tombstones — which keeps the cross-subtask union
        # free of stale replicated copies and lets rebase drop tombstones.
        # Everything restored is dirty, so it re-persists at the first
        # post-restore epoch (covers legacy-format upgrades and the
        # pruned replicas)
        table.retain(lambda k: isinstance(k, tuple) and k and k[0] == "sk"
                     and k in kept)

    async def handle_checkpoint(self, barrier, ctx, collector):
        t = self._table
        if t is None:
            return
        if self._serve_view is None:
            # no attached view consumes the serve flags
            t.restage[:t.top] = False
        if ctx.table_manager is None:
            return
        table = await ctx.table("sess")
        if not self.key_cols:
            snap = self._snapshot_sessions()
            snap["subtask"] = ctx.task_info.task_index
            table.put(ctx.task_info.task_index, snap)
            return
        # a key whose last session closed and that a barrier had written:
        # its entry goes (one that opened and closed between two barriers
        # was never written and leaves nothing)
        for cols in t.dead_stored:
            for key in zip(*self.codec.value_lists(cols)):
                table.delete(("sk", *key))
        t.dead_stored.clear()
        # every dirty key's entry anew, the values of all of them from ONE
        # gather; a key outside the sorted index writes all its sessions
        # when any of them changed
        alone = np.nonzero(t.dirty[:t.top] & ~t.shared[:t.top])[0]
        groups = [g for g in t.shared_keys() if t.dirty[g].any()]
        rows = np.concatenate(
            [alone] + [np.asarray(g, dtype=np.int64) for g in groups])
        if not len(rows):
            return
        slots = t.slot[rows]
        values = [_tolist(c) for c in self.acc.snapshot(slots)]
        spans = np.stack([t.start[rows], t.last[rows], slots], axis=1).tolist()
        keys = list(zip(*self.codec.value_lists([k[rows] for k in t.keys])))
        for i in range(len(alone)):
            table.put(("sk", *keys[i]), {
                "s": [spans[i]], "v": [[col[i]] for col in values]})
        i = len(alone)
        for g in groups:
            j = i + len(g)
            table.put(("sk", *keys[i]), {
                "s": spans[i:j], "v": [col[i:j] for col in values]})
            i = j
        t.stored[rows] = True
        t.dirty[rows] = False
        return len(rows)

    def _restore_per_key(self, items: list, ctx) -> set:
        """Replay per-key entries owned by this subtask; returns the set
        of table keys kept (for the retain() prune)."""
        if not items:
            return set()
        mask = self._range_mask([list(k[1:]) for k, _v in items], ctx)
        kept = set()
        entries, cols = [], None
        at = 0
        for i, (k, v) in enumerate(items):
            if mask is not None and not mask[i]:
                continue
            kept.add(k)
            entries.append(
                (list(k[1:]), [[s[0], s[1], at + j]
                               for j, s in enumerate(v["s"])]))
            at += len(v["s"])
            if cols is None:
                cols = [[] for _ in v["v"]]
            for c, col in zip(cols, v["v"]):
                c.extend(col)
        self._restore(entries, cols or [], stored=True)
        return kept

    def _snapshot_sessions(self) -> dict:
        """Every open session in the legacy form (`_restore_snapshot`)."""
        sessions = self.sessions
        slots = [s[2] for v in sessions.values() for s in v]
        values = self.acc.snapshot(
            np.asarray(slots, dtype=np.int64)) if slots else []
        return {
            "sessions": [[list(k), v] for k, v in sessions.items()],
            "slots": slots,
            "values": [_tolist(v) for v in values],
        }

    def _restore_snapshot(self, snap: dict, ctx=None):
        """Replay one pre-restart subtask's snapshot (the legacy form:
        {sessions: [[key values, [[start, last, old slot], ...]], ...],
        slots, values}), skipping keys outside this subtask's range."""
        entries = snap["sessions"]
        key_rows = [key_vals for key_vals, _ in entries]
        mask = self._range_mask(key_rows, ctx) if key_rows else None
        slot_pos = {s: i for i, s in enumerate(snap["slots"])}
        self._restore(
            [(k, [[s[0], s[1], slot_pos[s[2]]] for s in sess])
             for i, (k, sess) in enumerate(entries)
             if mask is None or mask[i]],
            snap["values"], stored=False)

    def _restore(self, entries: list, values: list, stored: bool):
        """Open the sessions of `entries` ([(key values, [[start, last,
        position in `values`], ...]), ...]) on fresh slots (old slot ids
        collide across subtasks), with one accumulator restore."""
        key_rows, spans = [], []
        for key_vals, sess in entries:
            for s in sess:
                key_rows.append(key_vals)
                spans.append(s)
        if not spans:
            return
        t = self._table
        start, last, pos = np.asarray(spans, dtype=np.int64).T
        key_cols = self.codec.value_columns_from_values(key_rows)
        slots = self._alloc(len(spans))
        self._ensure_capacity()
        # trailing host-state columns are ragged per-slot lists (same
        # object-array discipline as _restore_rows)
        n_phys = len(self.acc.phys)
        cols = []
        for j, v in enumerate(values):
            if j < n_phys:
                cols.append(np.asarray(v)[pos])
            else:
                arr = np.empty(len(v), dtype=object)
                arr[:] = v
                cols.append(arr[pos])
        self.acc.restore(slots, cols)
        t.load(t.codes_of(key_cols, len(spans)), key_cols, start, last,
               slots, stored)

    async def process_batch(self, batch, ctx, collector, input_index: int = 0):
        self._capture_key_meta(ctx)
        t = self._table
        with timeline.phase("sess.segment") as ph:
            ts = ctx.in_schemas[0].timestamps(batch)
            ph.padded = n = len(ts)
            wm = ctx.watermarks.current_nanos()
            keys = self.codec.value_columns(batch, self.key_cols)
            live = None
            if wm is not None:
                # a row whose session a watermark has closed is fully late
                live = ts + self.gap > wm
                if live.all():
                    live = None
                else:
                    ts = ts[live]
                    keys = [k[live] for k in keys]
                    n = len(ts)
            ph.n = n
            if not n:
                return
            # one segment per run of a key's rows less than the gap apart
            # (a distance of exactly the gap opens a new session)
            code = t.codes_of(keys, n)
            order = np.lexsort((ts, code))
            so_code, so_ts = code[order], ts[order]
            cut = np.ones(n, dtype=bool)
            cut[1:] = (so_code[1:] != so_code[:-1]) | (
                so_ts[1:] - so_ts[:-1] >= self.gap)
            if not t.exact:
                # two keys under one hash are two segments
                for k in keys:
                    so_k = k[order]
                    cut[1:] |= np.asarray(so_k[1:] != so_k[:-1], dtype=bool)
            first = np.nonzero(cut)[0]
            seg_of = np.cumsum(cut) - 1
            seg_hi = so_ts[np.r_[first[1:], n] - 1]
            at = order[first]
        with timeline.phase("sess.place", n=len(first)) as ph:
            rows = t.place(so_code[first], [k[at] for k in keys],
                           so_ts[first], seg_hi, self._alloc,
                           self._fold_slots)
            ph.padded = t.scalar
            # counts, no duration: sessions opened, sessions folded away
            timeline.note("sess.open", 0.0, n=t.opened)
            if t.merged:
                timeline.note("sess.merge", 0.0, n=t.merged)
            self._ensure_capacity()
            slots = np.empty(n, dtype=np.int64)
            slots[order] = t.slot[rows][seg_of]
        with timeline.phase("win.cols", n=n):
            cols = self._agg_input_cols(batch)
            if live is not None:
                cols = {c: v[live] for c, v in cols.items()}
        self.acc.update(slots, cols)

    def _emitted_keys(self, rows: np.ndarray) -> tuple:
        """`_build_output`'s (keys, key_arrays) of the sessions in `rows`:
        the stored key columns, or one empty key a row where sessions are
        window-global."""
        if not self.key_cols:
            return [()] * len(rows), None
        return [], [k[rows] for k in self._table.keys]

    def _fold_slots(self, dst: int, src: int):
        """Fold slot src's accumulator into dst's; free src (a row that
        bridges two open sessions: the table's scalar path)."""
        self._ensure_capacity()
        self.acc.merge_slot_into(dst, src)
        both = self.acc.gather(np.asarray([dst, src], dtype=np.int64))
        combined = []
        for (op, _dt, _, _), vals in zip(self.acc.phys, both):
            if op == "add":
                combined.append(np.asarray([vals[0] + vals[1]]))
            elif op == "min":
                combined.append(np.asarray([min(vals[0], vals[1])]))
            else:
                combined.append(np.asarray([max(vals[0], vals[1])]))
        self.acc.restore(np.asarray([dst], dtype=np.int64), combined)
        self.acc.reset_slots(np.asarray([src], dtype=np.int64))
        self.dir.free_slot(src)

    def serve_stage_snapshot(self, view) -> None:
        """Serve OPEN sessions as partials (ISSUE 20 satellite). Called
        by seal_op inside the checkpoint capture span. Delta-staged:
        only sessions that changed since the last capture — new events,
        merges, a neighbour's expiry: the table's `restage` flags — are
        re-gathered and re-staged flagged `partial: True` (end is the
        would-be close `last_ts + gap`), so point reads — worker- and
        follower-side alike — see in-flight sessions at the published
        epoch instead of a 404 until the gap closes. Unchanged partials
        persist in the cumulative view/mirror, keeping capture cost
        O(touched sessions) rather than O(live sessions) — the
        state-bloat flatness gate depends on this. Requires a
        side-effect-free `gather`; mesh-fused accumulators expose only
        gather_and_reset, so they skip partials (a documented known
        limit — finals are unaffected). A served partial needs no
        retraction: the watermark that closes a session stages its final
        (`_build_output`) in the same barrier interval, and the final is
        the key's newer row; a partial staged behind it belongs to a
        session the key has opened since."""
        gather = getattr(self.acc, "gather", None)
        t = self._table
        if gather is None or t is None:
            return
        rows = np.nonzero(t.restage[:t.top])[0]
        if not len(rows):
            return
        from ..serve import stage_batch

        # one row per session; staging overwrites per key, so a
        # multi-session key serves its latest (max-start) session
        rows = rows[np.argsort(t.start[rows], kind="stable")]
        agg_cols = self.acc.finalize(gather(t.slot[rows]))
        keys, key_arrays = self._emitted_keys(rows)
        out = self._build_output(
            keys, agg_cols, t.start[rows], t.last[rows] + self.gap,
            key_arrays=key_arrays, serve_stage=False,
        )
        stage_batch(view, out, partial=True)
        t.restage[rows] = False

    async def handle_watermark(self, watermark, ctx, collector):
        if watermark.kind != WatermarkKind.EVENT_TIME:
            return watermark
        t = self._table
        if t is None or not t.n_live:
            return watermark
        wm = watermark.timestamp
        rows = t.expired(wm)
        if not len(rows):
            return watermark
        # every expired session of this watermark together: one gather and
        # one reset of their slots (one fused program on a mesh), one
        # output batch with per-row window bounds. Ledger-only: it awaits
        with timeline.phase("sess.expire", n=len(rows), key=wm,
                            annotate=False) as ph:
            ph.padded = t.n_live
            slots = t.slot[rows]
            starts = t.start[rows]
            ends = t.last[rows] + self.gap
            keys, key_arrays = self._emitted_keys(rows)
            t.close(rows)
            fused = getattr(self.acc, "gather_and_reset", None)
            if fused is not None:
                agg_cols = self.acc.finalize(fused(slots))
                self.acc.drop_host_state(slots)
            else:
                agg_cols = self.acc.finalize(self.acc.gather(slots))
                self.acc.reset_slots(slots)
            self.dir.free_slots(slots)  # batch: one extend per shard
            with timeline.phase("close.build", key=wm, n=len(rows)):
                out = self._build_output(keys, agg_cols, starts, ends,
                                         key_arrays=key_arrays)
            await collector.collect(out)
        return watermark


@register_operator(OperatorName.TUMBLING_WINDOW_AGGREGATE)
def _make_tumbling(config: dict) -> Operator:
    return TumblingWindowOperator(config)


@register_operator(OperatorName.SLIDING_WINDOW_AGGREGATE)
def _make_sliding(config: dict) -> Operator:
    return SlidingWindowOperator(config)


@register_operator(OperatorName.SESSION_WINDOW_AGGREGATE)
def _make_session(config: dict) -> Operator:
    return SessionWindowOperator(config)
