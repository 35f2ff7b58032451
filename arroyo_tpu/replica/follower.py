"""One follower: read-only serve-state mounts tailing published chains.

A follower owns, per mounted job, a generation-less StateBackend and
one TableManager per (node, op) whose manifest publishes a `__serve__`
table. Restore and tail both run the PR 17 machinery verbatim —
`TableManager.open` (with `restore_manifest` pointed at a published
manifest) unions ALL subtasks' chains because the follower's TaskInfo
claims parallelism 1, and `tail_chains` replays only the delta-chain
suffix per publish, at delta cost through the shared chain cache.

Views are rebuilt from the mirrored entries (one segment per sealed epoch
of a routable view, one entry per key otherwise: `serve/store.py`
`seed_from_mirror`) after every restore/tail and
stamped with the manifest epoch they reflect; `read` serves from them
without touching the compiled program, the workers, or the job's
generation. The `__serve_meta__` record carries the WORKER-side
describe() (true parallelism included), so the gateway can keep using
it for worker-ward fallback routing unchanged.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..analysis.model.effects import protocol_effect
from ..serve.store import (
    META_KEY,
    SERVE_TABLE,
    ServeView,
    seed_from_mirror,
)
from ..state import protocol
from ..state.backend import StateBackend
from ..state.table_config import global_table
from ..state.table_manager import TableManager
from ..types import TaskInfo
from ..utils.logging import get_logger

logger = get_logger("replica")


class _Mount:
    """One job's serve state mounted on this follower."""

    def __init__(self, backend: StateBackend):
        self.backend = backend
        # (node_id, op_idx) -> TableManager over that op's __serve__ chain
        self.tms: Dict[Tuple[int, int], TableManager] = {}
        self.views: Dict[str, ServeView] = {}
        self.meta: Dict[str, dict] = {}  # bare table -> worker describe()
        self.epoch = 0                   # manifest epoch currently served


class Follower:
    def __init__(self, index: int):
        self.index = index
        self.mounts: Dict[str, _Mount] = {}

    @protocol_effect("replica.subscribe")
    async def _subscribe(self, job_id: str, storage_url: str) -> bool:
        """Mount a job: full restore from the latest PUBLISHED manifest.
        Always re-resolves latest.json from storage — a reattach after
        death must never trust a controller-side epoch counter, which
        runs ahead of publication while a checkpoint is in flight (the
        follower_serves_unpublished_epoch mutant). Read-only by
        construction: the backend never claims a generation, so a
        follower can never fence the primary. False = nothing published
        yet (the manager backs off and retries)."""
        backend = StateBackend(storage_url, job_id)
        manifest = protocol.resolve_latest(backend.storage, backend.paths)
        if manifest is None:
            return False
        backend.restore_manifest = manifest
        mount = _Mount(backend)
        for node_id, op_idx in self._serve_ops(manifest):
            ti = TaskInfo(
                job_id=job_id, node_id=node_id, operator_name="replica",
                task_index=0, parallelism=1,
            )
            tm = TableManager(backend, ti, op_idx)
            await tm.open({SERVE_TABLE: global_table(SERVE_TABLE)})
            mount.tms[(node_id, op_idx)] = tm
        mount.epoch = int(manifest["epoch"])
        self._refresh_views(job_id, mount)
        self.mounts[job_id] = mount
        logger.info(
            "follower %d mounted %s at epoch %d (%d serve ops, %d views)",
            self.index, job_id, mount.epoch, len(mount.tms),
            len(mount.meta),
        )
        return True

    @protocol_effect("replica.tail")
    async def _tail(self, job_id: str, target: int) -> int:
        """Advance a mount by replaying the delta-chain SUFFIX of a
        newer published manifest (TableManager.tail_chains). The target
        manifest is read back from storage — a missing manifest file
        (retention raced the notification) degrades to re-resolving
        latest, never to trusting the in-memory target. Returns blobs
        applied (0 = already caught up)."""
        mount = self.mounts[job_id]
        backend = mount.backend
        manifest = protocol.load_manifest(backend.storage, backend.paths,
                                          target)
        if manifest is None:
            manifest = protocol.resolve_latest(backend.storage,
                                               backend.paths)
        if manifest is None or int(manifest["epoch"]) <= mount.epoch:
            return 0
        backend.restore_manifest = manifest
        applied = 0
        for tm in mount.tms.values():
            applied += tm.tail_chains()
        mount.epoch = int(manifest["epoch"])
        self._refresh_views(job_id, mount)
        return applied

    @protocol_effect("replica.serve")
    def read(self, job_id: str, table: str,
             key_values) -> Optional[dict]:
        """One key lookup from this follower's materialized view. None
        when the job/table is not mounted here (the gateway falls back
        worker-ward); otherwise {found, value, epoch} with epoch = the
        published manifest epoch the whole view reflects."""
        mount = self.mounts.get(job_id)
        if mount is None:
            return None
        view = self.view(job_id, table)
        if view is None:
            return None
        key = view.canon_key(tuple(key_values))
        found, value = view.read(key, mount.epoch)
        return {"found": found, "value": value, "epoch": mount.epoch}

    def view(self, job_id: str, table: str) -> Optional[ServeView]:
        mount = self.mounts.get(job_id)
        if mount is None:
            return None
        return (mount.views.get(table)
                or mount.views.get(str(table).split("@")[0]))

    # -- internals -----------------------------------------------------------

    @staticmethod
    def _serve_ops(manifest: dict):
        """Sorted (node_id, op_idx) pairs whose manifest entry carries a
        `__serve__` table."""
        pairs = set()
        for task in manifest.get("tasks", {}).values():
            for op_key, tables in (task.get("op_tables") or {}).items():
                if SERVE_TABLE in tables:
                    pairs.add((int(task["node_id"]), int(op_key[2:])))
        return sorted(pairs)

    def _refresh_views(self, job_id: str, mount: _Mount) -> None:
        """Rebuild the mount's ServeViews from the mirrored rows. The
        follower holds every subtask's rows in one table (parallelism-1
        restore unions the chains, the global merge resolving replicated
        copies by entry stamp), so the local view claims parallelism 1 —
        every key is owned — while `meta` keeps the worker describe()
        verbatim for the gateway's fallback routing."""
        views: Dict[str, ServeView] = {}
        meta: Dict[str, dict] = {}
        for (node_id, _op_idx), tm in mount.tms.items():
            table = tm.tables.get(SERVE_TABLE)
            if table is None:
                continue
            desc = table.get(META_KEY)
            if not isinstance(desc, dict):
                continue  # mirror chain predates its first seal
            name = desc["table"]
            view = ServeView(
                job_id=job_id, table=name, node_id=int(desc["node_id"]),
                task_index=0, parallelism=1,
                key_names=list(desc["key_fields"]),
                key_kinds=tuple(desc["key_kinds"]),
                value_names=list(desc["value_fields"]),
                kind=desc["kind"], live_mode=False,
            )
            seed_from_mirror(view, table)
            view.served_epoch = mount.epoch
            views[f"{name}@{node_id}"] = view
            if name in views:
                # bare-name collision across nodes: qualified names only
                views.pop(name, None)
            else:
                views[name] = view
            meta[name] = desc
        mount.views = views
        mount.meta = meta

    def stats(self) -> dict:
        return {
            "index": self.index,
            "mounts": {
                jid: {
                    "epoch": m.epoch,
                    "tables": {
                        name: v.stats()["keys"]
                        for name, v in m.views.items() if "@" not in name
                    },
                }
                for jid, m in self.mounts.items()
            },
        }
