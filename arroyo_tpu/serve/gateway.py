"""StateGateway: the controller-resident queryable-state router.

Request flow for one read (`read()`):

  1. resolve the job + tenant; only RUNNING jobs serve (anything else —
     scheduling, recovering, rescaling — answers a retriable error: the
     caller backs off exactly like it would for a worker that died);
  2. per-tenant admission: a token bucket caps sustained keys/second
     per tenant (`serve.tenant_qps`); tenants the PR 11 bottleneck
     doctor flagged noisy-neighbor get `serve.noisy_penalty` x the
     rate, so one hot tenant cannot starve the fleet's read path;
  3. the read-through cache answers keys whose entry matches BOTH the
     job's current published epoch and its schedule incarnation
     (epoch-based invalidation: a newly published checkpoint or a
     reschedule silently invalidates everything cached before it);
  4. remaining keys route key -> owning subtask via the engine's own
     hash ownership (`store.owner_subtask` == `owners_for`) and
     subtask -> worker via the job's assignment table (the SAME table
     rescale rewrites), then fan out as QueryState RPCs carrying the
     published epoch and the `{job}@{schedules}` namespace — a worker
     still running a torn-down incarnation fences the read instead of
     answering from a stale generation's state;
  5. a `stale_route` answer invalidates the routing cache and retries
     once; RPC failures/timeouts degrade those keys to retriable
     errors — never to a wrong value.

All serve metrics carry the job label (Registry.drop_job GCs them) and
read cost is billed to the job through the attribution pump like batch
cost (`arroyo_job_attributed_busy_seconds` et al.).
"""

from __future__ import annotations

import asyncio
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from ..config import config
from ..metrics import (
    REPLICA_LOOKUPS,
    SERVE_CACHE_HITS,
    SERVE_CACHE_MISSES,
    SERVE_KEYS,
    SERVE_REQUEST_SECONDS,
    SERVE_REQUESTS,
    SERVE_WORKER_RPCS,
)
from ..obs import attribution, timeline
from ..utils.logging import get_logger
from .store import owner_subtask

logger = get_logger("serve.gateway")


class _Bucket:
    """Token bucket: sustained `rate` keys/s, burst 2x rate."""

    __slots__ = ("rate", "tokens", "last")

    def __init__(self, rate: float):
        self.rate = rate
        self.tokens = 2.0 * rate
        self.last = time.monotonic()

    def take(self, n: int, rate: float) -> bool:
        now = time.monotonic()
        self.rate = rate
        self.tokens = min(2.0 * rate, self.tokens + (now - self.last) * rate)
        self.last = now
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False


class _Cache:
    """Byte-bounded LRU of (job, table, key) -> (epoch, schedules,
    value). Entries never expire by time — validity is checked against
    the job's CURRENT published epoch + incarnation at read."""

    def __init__(self):
        self.data: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.bytes = 0

    def _entry_bytes(self, key, value) -> int:
        return 64 + len(str(key)) + len(str(value))

    def get(self, key: tuple, epoch, schedules: int):
        ent = self.data.get(key)
        if ent is None:
            return None
        e_epoch, e_sched, value, _b = ent
        if e_epoch != epoch or e_sched != schedules:
            self._drop(key)
            return None
        self.data.move_to_end(key)
        return value

    def put(self, key: tuple, epoch, schedules: int, value,
            budget: int):
        if budget <= 0:
            return
        if key in self.data:
            self._drop(key)
        nb = self._entry_bytes(key, value)
        self.data[key] = (epoch, schedules, value, nb)
        self.bytes += nb
        while self.bytes > budget and self.data:
            _old, (_e, _s, _v, ob) = self.data.popitem(last=False)
            self.bytes -= ob

    def _drop(self, key: tuple):
        ent = self.data.pop(key, None)
        if ent is not None:
            self.bytes -= ent[3]

    def drop_job(self, job_id: str) -> int:
        stale = [k for k in self.data if k[0] == job_id]
        for k in stale:
            self._drop(k)
        return len(stale)


class StateGateway:
    def __init__(self, controller):
        self.controller = controller
        self.cache = _Cache()
        self._buckets: Dict[str, _Bucket] = {}
        # tenant -> monotonic expiry of the doctor's noisy-neighbor flag
        self._noisy: Dict[str, float] = {}
        # (job_id, schedules) -> {table: describe dict}
        self._tables: Dict[str, Tuple[int, Dict[str, dict]]] = {}
        # slow-read candidates over a decaying window (ISSUE 13): the
        # old single high-water-mark pinned one cold-start outlier into
        # /debug/serve forever. Bounded ring of per-second maxima
        # (monotonic second, entry) — the window's true slowest read
        # survives until it AGES OUT, at 1 s boundary resolution, and a
        # read flood cannot evict it early.
        self._slow: deque = deque(maxlen=512)

    # -- noisy-neighbor wiring (PR 11 doctor verdict) ------------------------

    def flag_noisy(self, tenant: str, ttl: float = 30.0) -> None:
        """Called when a doctor report names `tenant`'s job as the
        noisy-neighbor suspect: squeeze its read quota for `ttl`s."""
        self._noisy[tenant] = time.monotonic() + ttl
        logger.info("serve: tenant %s flagged noisy for %.0fs", tenant, ttl)

    def note_doctor_report(self, report: dict) -> None:
        """Wire a /doctor verdict into read admission: a noisy-neighbor
        verdict naming a suspect job flags that job's tenant."""
        v = (report or {}).get("verdict") or {}
        suspect = v.get("suspect")
        if v.get("cause") != "noisy-neighbor" or not suspect:
            return
        job = self.controller.jobs.get(suspect)
        if job is not None:
            self.flag_noisy(job.tenant)

    def _admit(self, tenant: str, n_keys: int) -> bool:
        rate = float(config().serve.tenant_qps or 0.0)
        if rate <= 0:
            return True
        penalty = float(config().serve.noisy_penalty)
        if self._noisy.get(tenant, 0.0) > time.monotonic():
            rate *= penalty
        admission = getattr(self.controller, "admission", None)
        if admission is not None and admission.tenant_at_quota(tenant):
            # admission-quota wiring: a tenant saturating its COMPUTE
            # slot quota does not get to dominate the read path too
            rate *= penalty
        b = self._buckets.get(tenant)
        if b is None:
            b = self._buckets[tenant] = _Bucket(rate)
        return b.take(n_keys, rate)

    # -- routing -------------------------------------------------------------

    def _published_epoch(self, job) -> Optional[int]:
        """The read snapshot level: the job's last PUBLISHED epoch (None
        for non-durable jobs — their views run live)."""
        if job.backend is None:
            return None
        return int(getattr(job, "published_epoch", 0))

    async def tables(self, job_id: str) -> Dict[str, dict]:
        """{table: describe} for one job, cached per schedule
        incarnation (a rescale/recovery re-fetches — parallelism and
        assignments changed)."""
        job = self.controller.jobs[job_id]
        cached = self._tables.get(job_id)
        if cached is not None and cached[0] == job.schedules:
            return cached[1]
        # follower replicas (ISSUE 20): a mounted durable job's listing
        # comes from the mirrored describe records — zero worker RPCs
        # (the mirror carries the WORKER's describe, true parallelism
        # included, so worker-ward fallback routing still works)
        replicas = getattr(self.controller, "replicas", None)
        if replicas is not None:
            meta = replicas.tables_meta(job_id)
            if meta:
                self._tables[job_id] = (job.schedules, meta)
                return meta
        out: Dict[str, dict] = {}
        for resp in await self._ask_workers(job, "tables"):
            for d in resp.get("tables", []):
                out.setdefault(d["table"], d)
        self._tables[job_id] = (job.schedules, out)
        return out

    async def _ask_workers(self, job, mode: str) -> List[dict]:
        """One QueryState of `mode` to each of the job's workers; a
        worker that fails to answer (it may be dying) is left out."""
        out = []
        for w in job.workers:
            try:
                SERVE_WORKER_RPCS.labels(job=job.job_id).inc()
                out.append(await self.controller._worker_call(
                    w, "WorkerGrpc", "QueryState",
                    {"job_id": job.job_id, "mode": mode,
                     "data_ns": f"{job.job_id}@{job.schedules}"},
                    timeout=float(config().serve.read_timeout),
                ))
            except Exception as e:  # noqa: BLE001 - worker may be dying
                logger.debug("serve %s from worker %s failed: %s",
                             mode, w.worker_id, e)
        return out

    async def view_stats(self, job_id: str) -> List[dict]:
        """`ServeView.stats()` of every view the job's workers host
        (`/debug/serve?job=`): occupancy, and how many rows the write
        side was handed against how many became Python objects."""
        return [v for resp in await self._ask_workers(
                    self.controller.jobs[job_id], "stats")
                for v in resp.get("views", [])]

    def _worker_for(self, job, node_id: int, subtask: int):
        wid = job.assignments.get((node_id, subtask))
        if wid is None:
            return None
        for w in job.workers:
            if w.worker_id == wid:
                return w
        return None

    # -- the read path -------------------------------------------------------

    async def read(self, job_id: str, table: str, keys: List) -> dict:
        """Bulk (or single — a 1-key bulk) read. Returns a dict ready
        for the REST layer: per-key results, the epoch served, cache
        stats, or a request-level error with `retriable`."""
        t0 = time.perf_counter()
        out = await self._read_inner(job_id, table, keys)
        dt = time.perf_counter() - t0
        SERVE_REQUEST_SECONDS.labels(job=job_id).observe(dt)
        # read cost is tenant-billed like batch cost: busy seconds under
        # the job's attribution context; the timeline note feeds BOTH
        # the Perfetto serve swimlane and the per-job phase rollup
        attribution.note(job=job_id, busy=dt)
        timeline.note("serve", dt, job=job_id, task=table)
        SERVE_REQUESTS.labels(
            job=job_id, tenant=out.pop("_tenant", ""),
            outcome=out.get("outcome", "error"),
        ).inc()
        self._note_slow(dt, job_id, table, len(keys),
                        out.get("outcome"))
        return out

    def _note_slow(self, dt: float, job_id: str, table: str,
                   n_keys: int, outcome) -> None:
        """Fold the read into its second's maximum (exact timestamps;
        second-granular dedupe keeps a read flood from evicting the
        window's true maximum out of the bounded ring)."""
        now = time.monotonic()
        ms = round(dt * 1e3, 3)
        entry = {"ms": ms, "job": job_id, "table": table,
                 "keys": n_keys, "outcome": outcome}
        if self._slow and int(self._slow[-1][0]) == int(now):
            if ms > self._slow[-1][1]["ms"]:
                self._slow[-1] = (now, entry)
        else:
            self._slow.append((now, entry))

    def slowest_read(self, now: Optional[float] = None) -> Optional[dict]:
        """Slowest read within serve.slow_read_window, or None."""
        now = time.monotonic() if now is None else now
        window = float(config().serve.slow_read_window)
        while self._slow and now - self._slow[0][0] > window:
            self._slow.popleft()
        if not self._slow:
            return None
        age, best = max(
            ((now - ts, e) for ts, e in self._slow),
            key=lambda p: p[1]["ms"],
        )
        return {**best, "age_s": round(age, 1)}

    def clear_slow(self) -> None:
        self._slow.clear()

    async def _read_inner(self, job_id: str, table: str,
                          keys: List) -> dict:
        if not config().serve.enabled:
            return {"error": "serving disabled", "retriable": False,
                    "outcome": "error", "status": 404}
        job = self.controller.jobs.get(job_id)
        if job is None:
            return {"error": "no such job", "retriable": False,
                    "outcome": "error", "status": 404}
        tenant = job.tenant
        if job.state.value != "Running":
            return {"error": f"job not running ({job.state.value})",
                    "retriable": True, "outcome": "error", "status": 409,
                    "_tenant": tenant}
        if len(keys) > int(config().serve.max_keys):
            return {"error": "too many keys", "retriable": False,
                    "outcome": "error", "status": 400, "_tenant": tenant}
        if not self._admit(tenant, max(1, len(keys))):
            return {"error": "tenant read quota exceeded",
                    "retriable": True, "outcome": "throttled",
                    "status": 429, "_tenant": tenant}
        out = await self._routed_read(job, table, keys)
        if out.get("outcome") == "stale_route":
            # one refresh + retry: the worker fenced a torn-down
            # incarnation's route — re-resolve against fresh assignments
            self._tables.pop(job_id, None)
            out = await self._routed_read(job, table, keys)
            if out.get("outcome") == "stale_route":
                out = {"error": "route kept fencing (rescale in flight)",
                       "retriable": True, "outcome": "error",
                       "status": 409}
        out["_tenant"] = tenant
        return out

    async def _routed_read(self, job, table: str, keys: List) -> dict:
        info = (await self.tables(job.job_id)).get(table)
        if info is None:
            return {"error": f"no such table {table!r}",
                    "retriable": False, "outcome": "error",
                    "status": 404}
        epoch = self._published_epoch(job)
        sched = job.schedules
        budget = int(config().serve.cache_bytes)
        kinds = tuple(info["key_kinds"])
        SERVE_KEYS.labels(job=job.job_id).inc(len(keys))
        # follower replicas (ISSUE 20): durable jobs route follower-
        # first when a caught-up mount exists; live jobs and lagging/
        # dead followers fall back to the worker fan-out below. The
        # cache keys on the SOURCE's epoch — the follower's served
        # epoch when follower-routed — so a lagging follower can never
        # serve a cache entry newer than its own epoch (and a worker-
        # cached entry at a newer published epoch never answers a
        # follower-routed read).
        replicas = getattr(self.controller, "replicas", None)
        follower = None
        if replicas is not None and epoch is not None:
            follower = replicas.route(job, table)
        src_epoch = follower.served_epoch if follower is not None else epoch
        results: List[Optional[dict]] = [None] * len(keys)
        misses: List[int] = []
        hits = 0
        for i, raw in enumerate(keys):
            ck = (job.job_id, table, str(raw))
            value = self.cache.get(ck, src_epoch, sched)
            if value is not None:
                results[i] = {"key": raw, "found": True, "value": value,
                              "cached": True}
                hits += 1
            else:
                misses.append(i)
        SERVE_CACHE_HITS.labels(job=job.job_id).inc(hits)
        SERVE_CACHE_MISSES.labels(job=job.job_id).inc(len(misses))
        stale = False
        if misses and follower is not None:
            REPLICA_LOOKUPS.labels(job=job.job_id).inc(len(misses))
            for i in misses:
                raw = keys[i]
                vals = raw if isinstance(raw, (list, tuple)) else [raw]
                if len(vals) != len(kinds):
                    results[i] = {"key": raw, "found": False,
                                  "error": "bad key", "retriable": False}
                    continue
                try:
                    resp = replicas.read_one(job.job_id, table,
                                             tuple(vals))
                except (TypeError, ValueError):
                    results[i] = {"key": raw, "found": False,
                                  "error": "bad key", "retriable": False}
                    continue
                if resp is None:
                    # follower died between route() and the read
                    results[i] = {"key": raw, "found": False,
                                  "error": "follower detached",
                                  "retriable": True}
                    continue
                results[i] = {"key": raw, "found": resp["found"]}
                if resp["found"]:
                    results[i]["value"] = resp["value"]
                    self.cache.put((job.job_id, table, str(raw)),
                                   src_epoch, sched, resp["value"],
                                   budget)
        elif misses:
            by_worker: Dict[int, List[int]] = {}
            broadcast = not info["routable"]
            for i in misses:
                raw = keys[i]
                vals = raw if isinstance(raw, (list, tuple)) else [raw]
                if not broadcast and len(vals) == len(kinds):
                    try:
                        sub = owner_subtask(
                            tuple(vals), kinds, int(info["parallelism"])
                        )
                    except (TypeError, ValueError):
                        results[i] = {"key": raw, "found": False,
                                      "error": "bad key",
                                      "retriable": False}
                        continue
                    w = self._worker_for(job, int(info["node_id"]), sub)
                    if w is None:
                        results[i] = {"key": raw, "found": False,
                                      "error": "owner unassigned",
                                      "retriable": True}
                        continue
                    by_worker.setdefault(w.worker_id, []).append(i)
                else:
                    for w in job.workers:
                        by_worker.setdefault(w.worker_id, []).append(i)
            stale = await self._fanout(job, table, epoch, keys, by_worker,
                                       results, broadcast)
            for i in misses:
                r = results[i]
                if r is not None and r.get("found"):
                    self.cache.put((job.job_id, table, str(keys[i])),
                                   epoch, sched, r["value"], budget)
        if stale:
            return {"outcome": "stale_route"}
        errors = sum(1 for r in results if r and r.get("error"))
        outcome = "ok" if errors == 0 else "partial"
        # every response reports its read staleness: published epoch
        # minus the epoch actually served. Worker-routed reads serve AT
        # publication (0); follower-routed reads lag by at most
        # replica.max_lag_epochs — one checkpoint interval (route()
        # refuses beyond that, falling back worker-ward).
        staleness = ((epoch - src_epoch)
                     if epoch is not None and src_epoch is not None else 0)
        return {
            "job": job.job_id, "table": table, "epoch": epoch,
            "served_epoch": src_epoch, "staleness": staleness,
            "source": "follower" if follower is not None else "worker",
            "results": [r or {"found": False} for r in results],
            "cache": {"hits": hits, "misses": len(misses)},
            "outcome": outcome, "status": 200,
        }

    async def _fanout(self, job, table: str, epoch, keys: List,
                      by_worker: Dict[int, List[int]],
                      results: List[Optional[dict]],
                      broadcast: bool) -> bool:
        """Fan QueryState legs out concurrently; returns True when any
        leg fenced (stale route). Failed legs degrade their keys to
        retriable errors."""
        ns = f"{job.job_id}@{job.schedules}"
        timeout = float(config().serve.read_timeout)
        handles = {w.worker_id: w for w in job.workers}
        stale = False

        async def leg(wid: int, idxs: List[int]):
            w = handles.get(wid)
            payload = {
                "job_id": job.job_id, "mode": "get", "table": table,
                "keys": [keys[i] for i in idxs], "epoch": epoch,
                "data_ns": ns,
            }
            try:
                SERVE_WORKER_RPCS.labels(job=job.job_id).inc()
                resp = await self.controller._worker_call(
                    w, "WorkerGrpc", "QueryState", payload,
                    timeout=timeout,
                )
            except Exception as e:  # noqa: BLE001 - dead/slow worker
                return idxs, {"error": f"worker {wid}: {e}",
                              "retriable": True}
            return idxs, resp

        legs = await asyncio.gather(
            *(leg(wid, idxs) for wid, idxs in by_worker.items())
        )
        for idxs, resp in legs:
            if resp.get("error"):
                if "stale_route" in str(resp.get("error")):
                    stale = True
                    continue
                for i in idxs:
                    if broadcast and results[i] and results[i].get("found"):
                        continue
                    results[i] = {"key": keys[i], "found": False,
                                  "error": resp["error"],
                                  "retriable": bool(
                                      resp.get("retriable", True))}
                continue
            for i, r in zip(idxs, resp.get("results", [])):
                if broadcast:
                    # merge: first found answer wins; errors only if
                    # nothing found anywhere
                    cur = results[i]
                    if cur is not None and cur.get("found"):
                        continue
                    if r.get("found") or cur is None:
                        results[i] = r
                else:
                    results[i] = r
        return stale

    # -- lifecycle / surfaces ------------------------------------------------

    def expunge_job(self, job_id: str) -> None:
        """Serving-tier GC, wired beside Registry.drop_job on the job
        release/StopJob expunge path: a stopped job leaves no cache
        entries or routing state behind (its arroyo_serve_* series are
        job-labeled and fall to drop_job itself)."""
        self.cache.drop_job(job_id)
        self._tables.pop(job_id, None)

    def status(self) -> dict:
        now = time.monotonic()
        return {
            "enabled": bool(config().serve.enabled),
            "cache": {"entries": len(self.cache.data),
                      "bytes": self.cache.bytes,
                      "budget": int(config().serve.cache_bytes)},
            "tenant_qps": float(config().serve.tenant_qps),
            "noisy_tenants": sorted(
                t for t, exp in self._noisy.items() if exp > now
            ),
            "routing_cached_jobs": sorted(self._tables),
            "slowest_read": self.slowest_read(now),
        }
