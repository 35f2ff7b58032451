"""TableManager: per-(subtask, chain-op) state table ownership.

Capability parity with the reference's TableManager
(/root/reference/crates/arroyo-state/src/tables/table_manager.rs:37): owns
the operator's tables, restores them from the backend's restore manifest on
open, flushes dirty state on checkpoint barriers, and swaps file references
after compaction. Restore semantics per table kind:
  * global: union of ALL subtasks' blob chains (replication — rescale-aware
    operators re-filter by key range themselves). Each subtask's manifest
    entry carries a base+delta chain replayed in epoch order; entry stamps
    make the cross-subtask merge deterministic (tables.GlobalTable).
  * time_key: read every subtask's live files, filter rows to this
    subtask's key range and retention (rescale = overlap re-read,
    reference parquet.rs + expiring_time_key_map.rs)

Checkpointing is split into capture (synchronous at the barrier, O(dirty))
and flush (storage I/O, safe to overlap later epochs). A capture cuts WHAT
belongs to the epoch; what is a pure function of that cut may be staged
unresolved and is resolved by the flush, before anything is written: a
time-key delta's thunk (a device->host copy), a global table's blob whose
entries are `Deferred` (the serve view's merged segment as Arrow IPC bytes,
and the msgpack packing around it). The runner keeps up
to `state.max_inflight_flushes` epochs' flushes in flight, strictly
epoch-ordered per subtask, so flush N always lands before flush N+1 runs —
which is what lets flush-time bookkeeping (the cumulative time-key file
list) read `table.files` without racing a later capture.

Rebase policy: an incremental global table's chain is truncated with a
fresh base once it carries `state.rebase_epochs` deltas or its delta bytes
exceed `state.rebase_bytes_factor` x the base size (restore replays the
whole chain, so the chain length is a restore-time tax).
"""

from __future__ import annotations

import weakref
from typing import Dict, Optional

from .. import obs
from ..analysis.model.effects import protocol_effect
from ..config import config as get_config
from ..metrics import (
    STATE_BYTES,
    STATE_CHAIN_LEN,
    STATE_ROWS,
    STATE_SPILLED_BYTES,
)
from ..obs import timeline
from ..types import TaskInfo
from ..utils.logging import get_logger
from .backend import StateBackend
from .chain_cache import CACHE
from .table_config import TableConfig
from .tables import GlobalTable, TimeKeyTable

logger = get_logger("table_manager")


class TableManager:
    def __init__(self, backend: StateBackend, task_info: TaskInfo, op_idx: int):
        self.backend = backend
        self.task_info = task_info
        self.op_idx = op_idx
        self.tables: Dict[str, object] = {}
        self.configs: Dict[str, TableConfig] = {}
        # global tables' current blob chain: name -> [{"path", "bytes",
        # "epoch", "base"}]. Extended at CAPTURE time (paths are
        # deterministic) so pipelined flushes can't race the bookkeeping.
        self._chains: Dict[str, list] = {}
        # blobs whose packing a capture left to the flush: name -> path
        # -> [epoch, the nbytes of the staged inputs (what
        # `_should_rebase` reckons with while the flush is in flight),
        # len(blob) once it is written]. What lies before a flushed
        # chain's base goes
        self._late_blobs: Dict[str, Dict[str, list]] = {}
        # hot-standby tailing (ISSUE 17): highest manifest epoch whose
        # chain suffix has been replayed onto the open tables
        self._tailed_epoch = -1

    def _read_chain_blob(self, path: str, sp) -> Optional[bytes]:
        """One chain blob, preferring the task-local cache (same-worker
        restart / tail of a blob this process flushed) over storage."""
        blob = CACHE.get(self.backend.storage.url, path)
        if blob is not None:
            sp.event("cached_blob", path=path)
            return blob
        sp.event("read_blob", path=path)
        blob = self.backend.read_blob(path)
        if blob is not None:
            CACHE.put(self.backend.storage.url, path, blob)
        return blob

    async def open(self, configs: Dict[str, TableConfig]):
        self.configs = dict(configs)
        for name, cfg in self.configs.items():
            if cfg.kind == "global":
                table = GlobalTable(cfg)
            else:
                table = TimeKeyTable(cfg)
            self.tables[name] = table
        if self.backend.restore_manifest:
            self._restore()
        self._register_gauges()

    def _register_gauges(self):
        """Scrape-time state-size gauges (weakref pattern: a collected
        table unregisters its refresher instead of pinning stale values)."""
        jid, tid = self.task_info.job_id, self.task_info.task_id
        for name, table in self.tables.items():
            kind = self.configs[name].kind
            tref = weakref.ref(table)
            labels = dict(job=jid, task=tid, table=name, kind=kind)

            def _bytes(tref=tref):
                t = tref()
                if t is None:
                    return None
                if isinstance(t, GlobalTable):
                    return float(t.state_size()[0])
                mem, spilled, _r, _b = t.entry_stats()
                return float(mem + spilled)

            def _rows(tref=tref):
                t = tref()
                if t is None:
                    return None
                if isinstance(t, GlobalTable):
                    return float(t.state_size()[1])
                return float(t.entry_stats()[2])

            STATE_BYTES.labels(**labels).set_refresher(_bytes)
            STATE_ROWS.labels(**labels).set_refresher(_rows)
            if kind != "global":

                def _spilled(tref=tref):
                    t = tref()
                    if t is None:
                        return None
                    return float(t.entry_stats()[1])

                STATE_SPILLED_BYTES.labels(
                    job=jid, task=tid, table=name
                ).set_refresher(_spilled)
            if kind == "global":
                mref = weakref.ref(self)

                def _chain(mref=mref, name=name):
                    m = mref()
                    if m is None:
                        return None
                    return float(len(m._chains.get(name, ())))

                STATE_CHAIN_LEN.labels(job=jid, task=tid,
                                       table=name).set_refresher(_chain)

    def _restore(self):
        node_id = self.task_info.node_id
        # deterministic replay order: the cross-subtask union resolves
        # stale replicated copies by entry stamp, and ties by replay
        # order — sort so ties break the same way on every restore
        per_subtask = sorted(
            self.backend.tables_for(node_id, self.op_idx),
            key=lambda e: e["subtask"],
        )
        restore_wm = self.backend.restore_watermark(self.task_info.task_id)
        for name, table in self.tables.items():
            cfg = self.configs[name]
            # flight recorder: one span per restored table, staged events
            # per file — a restore failure (e.g. the process-scheduler
            # IndexError in ROADMAP open items) names its table, file and
            # stage in the trace dump instead of just a stack
            with obs.span(
                "state.restore_table", cat="storage", table=name,
                kind=cfg.kind, task=self.task_info.task_id,
                op_idx=self.op_idx,
            ) as sp:
                if cfg.kind == "global":
                    n_blobs = 0
                    for entry in per_subtask:
                        meta = entry["tables"].get(name)
                        if not meta:
                            continue
                        chain = meta.get("chain")
                        if chain is None and meta.get("path"):
                            chain = [{"path": meta["path"]}]
                        blobs = []
                        for f in chain or []:
                            blob = self._read_chain_blob(f["path"], sp)
                            if blob is not None:
                                blobs.append(blob)
                        if blobs:
                            table.load_chain(blobs)
                            n_blobs += len(blobs)
                    sp.set(blobs=n_blobs)
                else:
                    seen = set()
                    batches = []
                    for entry in per_subtask:
                        meta = entry["tables"].get(name)
                        for f in (meta or {}).get("files", []):
                            if f["path"] in seen:
                                continue
                            seen.add(f["path"])
                            sp.event("read_file", path=f["path"])
                            t = self.backend.read_parquet(f["path"])
                            if t is not None:
                                batches.extend(t.to_batches())
                            table.files.append(dict(f))
                    sp.set(files=len(seen), batches=len(batches))
                    sp.event("load_batches")
                    table.load_batches(
                        batches,
                        key_indices=None,
                        parallelism=self.task_info.parallelism,
                        task_index=self.task_info.task_index,
                    )
                    sp.event("filter_expired", watermark=restore_wm)
                    table.filter_expired(restore_wm)
        restored = self.backend.restore_epoch
        self._tailed_epoch = restored if restored is not None else -1

    @protocol_effect("state.tail_chains")
    def tail_chains(self) -> int:
        """Hot-standby tailing (ISSUE 17): replay the delta-chain SUFFIX of
        a newer published manifest onto the already-open tables instead of
        re-restoring from scratch. The caller points
        `backend.restore_manifest` at the new manifest first; only chain
        entries for epochs beyond `_tailed_epoch` are read and applied.

        Safe to re-apply overlapping entries: the cross-subtask global
        merge resolves replicated copies by entry stamp, so a rebase base
        that subsumes already-applied deltas loads idempotently. Time-key
        tables load only files not already referenced, then adopt the new
        manifest's file list. Returns the number of blobs/files applied."""
        target = self.backend.restore_epoch
        if target is None or target <= self._tailed_epoch:
            return 0
        node_id = self.task_info.node_id
        per_subtask = sorted(
            self.backend.tables_for(node_id, self.op_idx),
            key=lambda e: e["subtask"],
        )
        applied = 0
        with obs.span(
            "state.tail_chains", cat="storage",
            task=self.task_info.task_id, op_idx=self.op_idx,
            from_epoch=self._tailed_epoch, to_epoch=target,
        ) as sp:
            for name, table in self.tables.items():
                cfg = self.configs[name]
                if cfg.kind == "global":
                    floor = None
                    for entry in per_subtask:
                        meta = entry["tables"].get(name)
                        chain = (meta or {}).get("chain") or []
                        blobs = []
                        for f in chain:
                            e = f.get("epoch")
                            if e is not None and floor is not None:
                                floor = min(floor, e)
                            elif e is not None:
                                floor = e
                            if e is None or e <= self._tailed_epoch:
                                continue
                            blob = self._read_chain_blob(f["path"], sp)
                            if blob is not None:
                                blobs.append(blob)
                        if blobs:
                            table.load_chain(blobs)
                            applied += len(blobs)
                    if floor is not None and floor > 0:
                        # the chain floor moved (rebase/GC): cached blobs
                        # below it are unreferenced now
                        CACHE.invalidate_below(
                            self.task_info.job_id, floor
                        )
                else:
                    seen = {f["path"] for f in table.files}
                    batches = []
                    files = []
                    for entry in per_subtask:
                        meta = entry["tables"].get(name)
                        for f in (meta or {}).get("files", []):
                            if f["path"] in {x["path"] for x in files}:
                                continue
                            files.append(dict(f))
                            if f["path"] in seen:
                                continue
                            sp.event("read_file", path=f["path"])
                            t = self.backend.read_parquet(f["path"])
                            if t is not None:
                                batches.extend(t.to_batches())
                                applied += 1
                    if batches:
                        table.load_batches(
                            batches,
                            key_indices=None,
                            parallelism=self.task_info.parallelism,
                            task_index=self.task_info.task_index,
                        )
                    table.files = files
                    wm = self.backend.restore_watermark(
                        self.task_info.task_id
                    )
                    table.filter_expired(wm)
            sp.set(applied=applied)
        self._tailed_epoch = target
        return applied

    async def get_table(self, name: str):
        return self.tables[name]

    async def checkpoint(self, epoch: int, watermark: Optional[int]) -> Dict:
        """Flush dirty state; returns per-table metadata for the manifest.
        One-shot form of capture() + flush_captured()."""
        return self.flush_captured(epoch, self.capture(epoch, watermark))

    def _should_rebase(self, chain: list, name: str) -> bool:
        st = get_config().state
        if not chain:
            return True
        deltas = [f for f in chain if not f.get("base")]
        if len(deltas) >= st.rebase_epochs:
            return True
        late = self._late_blobs.get(name, {})

        def size(f: dict) -> int:
            # its own, or while its flush is in flight what the capture
            # knew of its inputs
            n = f.get("bytes", 0)
            if n is None:
                _epoch, staged, written = late.get(f["path"], (0, 0, 0))
                n = staged if written is None else written
            return n

        base_bytes = sum(size(f) for f in chain if f.get("base")) or 1
        delta_bytes = sum(size(f) for f in deltas)
        return delta_bytes > st.rebase_bytes_factor * base_bytes

    @protocol_effect("state.capture_tables")
    def capture(self, epoch: int, watermark: Optional[int]) -> Dict:
        """Synchronously stage this epoch's state at the barrier: global
        tables serialize only their dirty entries + tombstones (a base
        when the chain is empty or the rebase policy fires), time-key
        deltas are detached from the tables (possibly as unresolved
        thunks whose device->host copy completes later; a global table's
        blob as a `Deferred` where its entries are, its chain entry's
        `bytes` None until the flush knows them). After capture
        the operator may resume processing; flush_captured does the I/O."""
        staged: Dict[str, dict] = {}
        ti = self.task_info
        for name, table in self.tables.items():
            cfg = self.configs[name]
            if cfg.kind == "global":
                chain = self._chains.setdefault(name, [])
                blob, is_base = table.serialize_delta(
                    epoch, force_base=self._should_rebase(chain, name)
                )
                if blob is not None:
                    path = self.backend.global_blob_path(
                        epoch, ti.node_id, self.op_idx, name, ti.task_index
                    )
                    late = callable(blob)
                    if late:
                        self._late_blobs.setdefault(name, {})[path] = [
                            epoch, blob.nbytes, None]
                    meta = {"path": path,
                            "bytes": None if late else len(blob),
                            "epoch": epoch, "base": is_base}
                    if is_base:
                        chain[:] = [meta]
                    else:
                        chain.append(meta)
                staged[name] = {
                    "kind": "global", "blob": blob,
                    "chain": [dict(f) for f in chain],
                }
            else:
                dirty = table.take_dirty_staged()
                table.expire(watermark)
                staged[name] = {
                    "kind": "time_key",
                    "dirty": dirty,
                    "watermark": watermark,
                    "table": table,
                }
        return staged

    @protocol_effect("state.flush_tables")
    def flush_captured(self, epoch: int, staged: Dict) -> Dict:
        """Write captured state to storage; safe to run while the operator
        processes later epochs (captured data is immutable), as long as
        flushes stay epoch-ordered per subtask (the runner's flush queue
        guarantees it — time-key file bookkeeping reads `table.files`
        here, which epoch N must update before epoch N+1 flushes).
        Returns the manifest metadata."""
        meta: Dict[str, dict] = {}
        ti = self.task_info
        for name, st in staged.items():
            cfg = self.configs[name]
            if st["kind"] == "global":
                chain = st["chain"]
                blob = st["blob"]
                if callable(blob):
                    # beside the loop, on this storage thread: a wait to
                    # the loop's sums (`timeline.WAITS`), with this
                    # thread's own CPU
                    with timeline.phase("flush.resolve", task=ti.task_id,
                                        key=epoch, n=blob.rows,
                                        annotate=False):
                        blob = blob()
                    self._late_blobs[name][chain[-1]["path"]][2] = len(blob)
                self._fill_late_bytes(name, chain)
                if blob is not None:
                    self.backend.write_blob(chain[-1]["path"], blob)
                    # task-local recovery (ISSUE 17): a same-worker restart
                    # or tailing standby re-reads this exact blob; keep it
                    # in process memory so that read skips storage
                    CACHE.put(self.backend.storage.url, chain[-1]["path"],
                              blob)
                meta[name] = {
                    "kind": "global",
                    "chain": chain,
                    "bytes": sum(f.get("bytes", 0) for f in chain),
                }
            else:
                dirty = TimeKeyTable.resolve_staged(st["dirty"])
                table = st["table"]
                files = table.live_files(st["watermark"])
                if dirty is not None and dirty.num_rows:
                    f = self.backend.write_time_key_file(
                        epoch, ti.node_id, self.op_idx, name, ti.task_index,
                        dirty, timestamp_field=cfg.timestamp_field,
                    )
                    files = files + [f]
                table.files = files
                meta[name] = {"kind": "time_key", "files": files}
        return meta

    def _fill_late_bytes(self, name: str, chain: list) -> None:
        """Give the chain entries of blobs packed on the flush their
        `bytes`: in the staged copy the completion report carries (this
        epoch's, and earlier epochs' that were in flight at its capture:
        flushes are epoch-ordered, so theirs are written) and in
        `_chains`, then forget what lies before this chain's base."""
        late = self._late_blobs.get(name)
        if not late:
            return
        for f in chain + list(self._chains.get(name, ())):
            if f.get("bytes", 0) is None and f["path"] in late:
                f["bytes"] = late[f["path"]][2]
        floor = chain[0]["epoch"] if chain else 0
        # (the loop's capture may add a path meanwhile: a list, at once)
        for path, (epoch, _staged, written) in list(late.items()):
            if epoch < floor and written is not None:
                del late[path]

    async def load_compacted(self, table: str, paths):
        """Swap pre-compaction file references for the compacted file
        (reference ControlMessage::LoadCompacted). In-memory rows already
        hold the data; only restore bookkeeping changes."""
        t = self.tables.get(table)
        if t is None or not hasattr(t, "files"):
            return
        if isinstance(paths, list) and paths and isinstance(paths[0], dict):
            t.files = [dict(f) for f in paths]
