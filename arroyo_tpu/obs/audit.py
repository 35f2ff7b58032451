"""Conservation ledger (ISSUE 19): always-on exactly-once auditing.

Every data-plane edge accumulates an epoch-scoped ATTESTATION — a row
count plus an order-insensitive content digest — sealed at barrier
alignment on BOTH sides: the sender tap lives in the EdgeSender
(operators/collector.py, covering local queues AND the remote frame path,
since the remote sender pumps the very same tapped queue), the receiver
tap in the runner's input loop (operators/runner.py). The digest is a
commutative fold: each row's columns (struct children flattened in
order) combine linearly under per-column salts, one splitmix round mixes
the combined row, and the per-row hashes are summed mod 2^64 — invariant
to row order and batch slicing, so keyed shuffles and Arrow IPC
roundtrips do not perturb it, while any duplicated, lost, or torn frame
does.

Attestations ride the existing checkpoint reports
(CheckpointCompletedResp.audit) to a controller-resident Reconciler that
verifies, per epoch:

  (a) sender attestation == receiver attestation per edge at each
      manifest publish (catches dup/lost/torn delivery beyond TCP),
  (b) per-operator flow consistency — out-counts change only via the
      operator's declared selectivity class (Operator.flow_class),
      never silent duplication,
  (c) recovery conservation at report INTAKE: a re-emitted epoch at or
      behind the published epoch (rewind-behind-commit — the PR 15
      ``overlap_double_emission`` mutant class, live) and reports from a
      fenced data-plane generation (zombie append) are flagged with the
      exact (edge, epoch) culprit.

Breach records land in three places: the per-job reconciler (expunged
with the job, served by /debug/audit and GET /api/v1/jobs/{id}/audit),
the job-labeled arroyo_audit_* metric families (GC'd by
Registry.drop_job), and a small process-wide ring that deliberately
SURVIVES job expunge so chaos drills can assert audit silence after the
embedded controller tears the job down.

Rows emitted after the last sealed barrier (the trailing segment before
EndOfData) are unattested symmetrically on both sides — no attestation
is ever compared against a partial peer, so a clean run is audit-silent
by construction.

One fingerprint per batch object (ISSUE 27). `batch_fingerprint` is a pure
function of an immutable Arrow batch, so every tap that observes the SAME
OBJECT takes one computation: the edges of a fan-out, and both ends of an
in-process queue. The memo is keyed on the object's identity, never on its
content, schema or size: a batch that equals another but is another object
is computed. A receiver of a remote edge decodes a new object from the IPC
frame, so it always computes, and a torn, altered or duplicated frame is
seen as before. Every tap still books every observation: what an
in-process receiver no longer does is hash again the bytes of an object
that cannot have changed since its sender hashed them.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from . import timeline

logger = logging.getLogger(__name__)

_MOD = 1 << 64
# digest contribution of a row in a zero-column batch (never happens in
# practice — every schema carries _timestamp — but keeps the fold total)
_EMPTY_ROW = 0x9E3779B97F4A7C15

# per-job breach list cap and process-wide ring cap: breaches are
# exceptional; a run that produces hundreds has already failed loudly
_JOB_BREACH_CAP = 256
_RING_CAP = 512


def enabled() -> bool:
    """Auditing is on by default (config().audit.enabled); the bench's
    overhead child turns it off with ARROYO__AUDIT__ENABLED=0."""
    from ..config import config

    return bool(config().audit.enabled)


# ---------------------------------------------------------------------------
# attestation accumulation (task side)


_SALTS = np.empty(0, dtype=np.uint64)


def _col_salts(n: int) -> np.ndarray:
    """Distinct odd multipliers per column position (splitmix of the
    index), so the linear combine keeps column pairing: swapping values
    between columns within a row changes the row hash."""
    global _SALTS
    if len(_SALTS) < n:
        from ..types import _splitmix64

        _SALTS = _splitmix64(
            np.arange(1, n + 1, dtype=np.uint64)
        ) | np.uint64(1)
    return _SALTS


def _col_u64(col: pa.Array, tally: List[int]) -> np.ndarray:
    """Raw uint64 view of one column (nulls -> type sentinel, -0.0
    normalized) WITHOUT per-column mixing — the audit fold mixes once
    per row after the linear combine, which is what keeps the always-on
    tap cheap enough for every data-plane edge. A string column is the
    pandas hash of each row, taken once per DISTINCT value where those
    are few (`types.hash_string_array`: a hash depends on the value's
    bytes alone, so the rows' uint64s are the same bit for bit);
    `tally` counts [string columns hashed, of them through a
    dictionary]."""
    from ..schema import _null_sentinel, _to_numpy
    from ..types import hash_column, hash_string_array, is_string_type

    if is_string_type(col.type):
        hashes, via_dictionary = hash_string_array(col)
        tally[0] += 1
        tally[1] += via_dictionary
        return hashes
    if col.null_count:
        col = col.fill_null(_null_sentinel(col.type))
    arr = _to_numpy(col)
    kind = arr.dtype.kind
    if kind in ("i", "u", "b"):
        return arr.astype(np.uint64, copy=False)
    if kind == "f":
        arr = arr + 0.0  # normalize -0.0 == 0.0 before bit-viewing
        return (arr.view(np.uint64) if arr.dtype == np.float64
                else arr.astype(np.float64).view(np.uint64))
    if kind == "M":
        return arr.view("i8").astype(np.uint64)
    return hash_column(arr)  # binary and other objects: pandas hash


# extra odd salts for nested shapes: list length (so [a, b]+[] and
# [a]+[b] across adjacent rows differ) and the null-list sentinel (so a
# NULL list differs from an empty one)
_LIST_LEN_SALT = np.uint64(0xD6E8FEB86659FD93)
_NULL_LIST = np.uint64(0xA5A5A5A5A5A5A5A5)


def _row_u64(col: pa.Array, tally: List[int]) -> np.ndarray:
    """One uint64 per row for any column type, recursing into nested
    shapes: struct children combine linearly under the column salts
    (+ one mix), list elements get one mix each and sum within the row
    (order-insensitive, like the batch fold) with the length salted in.
    Flat columns stay on the raw-view fast path (`_col_u64`, which keeps
    `tally`)."""
    t = col.type
    from ..types import _splitmix64

    if pa.types.is_struct(t):
        kids = [_row_u64(col.field(j), tally)
                for j in range(t.num_fields)]
        salts = _col_salts(len(kids))
        with np.errstate(over="ignore"):
            acc = kids[0] * salts[0]
            for i in range(1, len(kids)):
                acc = acc + kids[i] * salts[i]
        return _splitmix64(acc)
    if pa.types.is_fixed_size_list(t):
        col, t = col.cast(pa.list_(t.value_type)), None
    if t is None or pa.types.is_list(t) or pa.types.is_large_list(t):
        import pyarrow.compute as pc

        lens = np.asarray(
            pc.list_value_length(col).fill_null(0), dtype=np.int64)
        h = _splitmix64(_row_u64(col.flatten(), tally))
        c = np.zeros(len(h) + 1, dtype=np.uint64)
        if len(h):
            np.cumsum(h, dtype=np.uint64, out=c[1:])  # wraps mod 2^64
        ends = np.cumsum(lens)
        with np.errstate(over="ignore"):
            rows = (c[ends] - c[ends - lens]
                    + _LIST_LEN_SALT * lens.astype(np.uint64))
        if col.null_count:
            rows = np.where(np.asarray(col.is_valid()), rows, _NULL_LIST)
        return rows
    return _col_u64(col, tally)


def batch_fingerprint(batch: pa.RecordBatch,
                      tally: Optional[List[int]] = None) -> Tuple[int, int]:
    """(rows, digest) of one batch. Every column (struct children
    flattened in order) contributes its raw uint64 view to a per-row
    linear combine under distinct per-column odd salts; ONE splitmix
    round then mixes each combined row, and the rows are folded
    commutatively by summing mod 2^64 — the digest of a multiset of rows
    is independent of row order and of how the rows are sliced into
    batches, while a duplicated, lost, or torn row perturbs it. A single
    mixing pass (instead of two per column) is what holds the always-on
    overhead down; the linear pre-combine admits only contrived
    cancellations, far below the accidental-corruption signal this
    ledger exists to catch.

    A string column's uint64s are its rows' pandas hashes, computed once
    per distinct value where those are few and row by row where they are
    not (`types.hash_string_array` decides from the column itself): the
    same bits by either route, so the digest does not depend on it. A
    caller's `tally` gets [string columns hashed, of them through a
    dictionary] added."""
    n = batch.num_rows
    if n == 0:
        return 0, 0
    if tally is None:
        tally = [0, 0]
    cols: List[np.ndarray] = []
    for col in batch.columns:
        if pa.types.is_struct(col.type):
            for j in range(col.type.num_fields):
                cols.append(_row_u64(col.field(j), tally))
            continue
        cols.append(_row_u64(col, tally))
    if not cols:
        return n, (n * _EMPTY_ROW) % _MOD
    from ..types import _splitmix64

    salts = _col_salts(len(cols))
    with np.errstate(over="ignore"):
        acc = cols[0] * salts[0]
        for i in range(1, len(cols)):
            acc = acc + cols[i] * salts[i]
        return n, int(_splitmix64(acc).sum(dtype=np.uint64))


# id(batch) -> (weakref to the batch, rows, digest): one entry per LIVE
# batch object a tap has observed. `pa.RecordBatch` takes weak references
# and is not hashable, so a WeakKeyDictionary will not do. The reference's
# callback drops the entry when the batch dies (before its id can be given
# to a new object), so the memo never outgrows the batches in the queues.
_FP_MEMO: Dict[int, Tuple[weakref.ref, int, int]] = {}
_FP_LOCK = threading.Lock()  # the totals; the memo needs none
_FP_OBSERVED = 0
_FP_COMPUTED = 0
# string columns the computed fingerprints hashed, and how many of them
# went through a dictionary (dictionary share = the second over the first)
_FP_STRINGS = 0
_FP_STRINGS_VIA_DICT = 0


def _forget(key: int, ref: weakref.ref, memo=_FP_MEMO) -> None:
    """A memoised batch died: drop its entry, unless the key is already
    another object's. (`memo` is bound here so that a batch dying while
    the interpreter shuts down still finds it.)"""
    cur = memo.get(key)
    if cur is not None and cur[0] is ref:
        del memo[key]


def _fingerprint_once(batch: pa.RecordBatch) -> Tuple[int, int]:
    """`batch_fingerprint(batch)`, computed once per batch OBJECT. A hit
    needs `ref() is batch`: an id can be reused once its object is gone.
    Each computation books the count `audit.fp` inside the caller's
    `audit.attest`: reuse share = 1 - count(audit.fp) / count(audit.attest).
    One that hashed string columns books `audit.fp.str` beside it, `n` of
    `padded` = through a dictionary of all: dictionary share = n / padded."""
    global _FP_OBSERVED, _FP_COMPUTED, _FP_STRINGS, _FP_STRINGS_VIA_DICT
    key = id(batch)
    hit = _FP_MEMO.get(key)
    computed = hit is None or hit[0]() is not batch
    strings = via_dict = 0
    if computed:
        tally = [0, 0]
        n, d = batch_fingerprint(batch, tally)
        strings, via_dict = tally
        _FP_MEMO[key] = (weakref.ref(batch, partial(_forget, key)), n, d)
        timeline.note("audit.fp", 0.0, n=n)
        if strings:
            timeline.note("audit.fp.str", 0.0, n=via_dict, padded=strings)
    else:
        _, n, d = hit
    with _FP_LOCK:
        _FP_OBSERVED += 1
        _FP_COMPUTED += computed
        _FP_STRINGS += strings
        _FP_STRINGS_VIA_DICT += via_dict
    return n, d


class EdgeTap:
    """Running attestation for ONE direction of ONE edge, sealed per
    epoch when the barrier passes. The sender seals every output tap at
    barrier broadcast; the receiver seals input i's tap the moment input
    i delivers the barrier (aligned inputs deliver no further rows for
    that epoch), so both sides cut the stream at the same causal point."""

    __slots__ = ("edge", "rows", "digest", "sealed")

    def __init__(self, edge: str):
        self.edge = edge
        self.rows = 0
        self.digest = 0
        self.sealed: Dict[int, Tuple[int, int]] = {}

    def observe(self, batch: pa.RecordBatch) -> None:
        n, d = _fingerprint_once(batch)
        if n:
            self.rows += n
            self.digest = (self.digest + d) % _MOD

    def seal(self, epoch: int) -> None:
        self.sealed[epoch] = (self.rows, self.digest)
        self.rows = 0
        self.digest = 0

    def drain(self, epoch: int) -> Optional[Tuple[int, int]]:
        return self.sealed.pop(epoch, None)


def edge_key(src: str, src_subtask: int, dst: str, dst_subtask: int) -> str:
    """Canonical edge name: one attestation pair per (src subtask, dst
    subtask) channel — exactly the quad the data plane routes on."""
    return f"{src}:{src_subtask}->{dst}:{dst_subtask}"


# ---------------------------------------------------------------------------
# breach ring (process-wide, survives job expunge — drill assertions)

_RING_LOCK = threading.Lock()
_RING: deque = deque(maxlen=_RING_CAP)
_SEQ = 0


def _ring_push(rec: dict) -> None:
    global _SEQ
    with _RING_LOCK:
        _SEQ += 1
        _RING.append(dict(rec, seq=_SEQ))


def breach_mark() -> int:
    """Current breach sequence number: drills snapshot it before a run
    and assert breaches_since(mark) == [] after."""
    with _RING_LOCK:
        return _SEQ


def breaches_since(mark: int, job_id: Optional[str] = None) -> List[dict]:
    with _RING_LOCK:
        out = [dict(r) for r in _RING if r["seq"] > mark]
    if job_id is not None:
        out = [r for r in out if r["job"] == job_id]
    return out


# ---------------------------------------------------------------------------
# reconciler (controller side)


class Reconciler:
    """Controller-resident per-job conservation reconciler. intake() runs
    the recovery-conservation checks the moment a checkpoint report
    lands; reconcile() joins sealed attestations across the epoch's task
    reports when the manifest publishes."""

    def __init__(self, job_id: str):
        self.job_id = job_id
        self.lock = threading.Lock()
        # highest data-plane incarnation ("job@N" suffix) seen in any
        # report: once a newer generation reports, an older generation
        # appending NEW epochs is a zombie past its fencing
        self.max_incarnation: Optional[int] = None
        self.epochs_reconciled = 0
        self.edges_verified = 0
        self.rows_attested = 0
        self.last_epoch: Optional[int] = None
        self.breaches: List[dict] = []
        # last verified attestation per edge, for the report surfaces
        self.edges: Dict[str, dict] = {}

    # -- breach plumbing ----------------------------------------------------

    def _breach(self, kind: str, edge: str, epoch: int, detail: str) -> None:
        from ..metrics import AUDIT_BREACHES

        rec = {
            "job": self.job_id,
            "kind": kind,
            "edge": edge,
            "epoch": epoch,
            "detail": detail,
            "ts": time.time(),
        }
        with self.lock:
            self.breaches.append(rec)
            if len(self.breaches) > _JOB_BREACH_CAP:
                del self.breaches[0]
        _ring_push(rec)
        AUDIT_BREACHES.labels(job=self.job_id, kind=kind).inc()
        logger.warning(
            "conservation breach [%s] job=%s edge=%s epoch=%s: %s",
            kind, self.job_id, edge, epoch, detail,
        )

    @staticmethod
    def _first_edge(audit: Optional[dict]) -> Optional[str]:
        for side in ("tx", "rx"):
            d = (audit or {}).get(side) or {}
            for edge in d:
                return edge
        return None

    # -- checks -------------------------------------------------------------

    @staticmethod
    def _incarnation(gen: Optional[str]) -> Optional[int]:
        """Parse the schedule incarnation out of a data-plane namespace
        ("<job_id>@<incarnation>"); None when unstamped/unparseable."""
        if not gen or "@" not in gen:
            return None
        try:
            return int(gen.rsplit("@", 1)[1])
        except ValueError:
            return None

    def intake(self, task_id: str, epoch: int, audit: Optional[dict],
               published_epoch: Optional[int]) -> bool:
        """Recovery-conservation checks (c) at report intake time.
        Returns True when the report must be FENCED (not folded into the
        epoch bookkeeping): any epoch at/behind the published epoch, and
        any report from a generation older than one already seen. Only
        strictly-stale epochs are flagged as rewind breaches — an exact
        redelivery of the just-published epoch (an rpc retry racing the
        publish) is fenced silently, a REWIND re-emits history."""
        if not audit:
            return False
        edge = self._first_edge(audit) or f"task:{task_id}"
        if published_epoch is not None and epoch <= published_epoch:
            if epoch < published_epoch:
                self._breach(
                    "rewind_behind_commit", edge, epoch,
                    f"re-emitted epoch {epoch} behind published epoch "
                    f"{published_epoch} — source rewind behind committed "
                    f"output",
                )
            return True
        inc = self._incarnation(audit.get("gen"))
        if inc is not None:
            with self.lock:
                if self.max_incarnation is None or inc > self.max_incarnation:
                    self.max_incarnation = inc
                behind = inc < self.max_incarnation
            if behind:
                self._breach(
                    "zombie_generation", edge, epoch,
                    f"report from fenced generation "
                    f"{audit.get('gen')!r} (newest incarnation "
                    f"{self.max_incarnation}) — append past fencing",
                )
                return True
        return False

    def reconcile(self, epoch: int,
                  audits: Dict[str, Optional[dict]]) -> None:
        """Checks (a) + (b) at manifest publish: join the epoch's sealed
        sender/receiver attestations per edge and verify each operator's
        flow against its declared selectivity class. One-sided edges
        (peer finished before this barrier, or its report carried no
        attestation) are skipped, never flagged."""
        from ..metrics import AUDIT_EDGES_VERIFIED, AUDIT_EPOCHS

        tx: Dict[str, Tuple[int, int]] = {}
        rx: Dict[str, Tuple[int, int]] = {}
        # one epoch's barriers originate in exactly one generation, so an
        # epoch assembled from MIXED generations means an old incarnation
        # appended into a fenced epoch (zombie write that slipped intake)
        gens = {
            a.get("gen") for a in audits.values() if a and a.get("gen")
        }
        if len(gens) > 1:
            incs = {g: self._incarnation(g) for g in gens}
            if all(v is not None for v in incs.values()):
                live = max(gens, key=lambda g: incs[g])
                for task_id, a in audits.items():
                    if a and a.get("gen") not in (None, live):
                        self._breach(
                            "zombie_generation",
                            self._first_edge(a) or f"task:{task_id}", epoch,
                            f"epoch assembled from mixed generations: "
                            f"{a.get('gen')!r} behind live {live!r}",
                        )
        for task_id, audit in audits.items():
            if not audit:
                continue
            for edge, v in (audit.get("tx") or {}).items():
                tx[edge] = (int(v[0]), int(v[1]))
            for edge, v in (audit.get("rx") or {}).items():
                rx[edge] = (int(v[0]), int(v[1]))
            flow = audit.get("flow") or {}
            for op, v in (audit.get("ops") or {}).items():
                cls = flow.get(op, "any")
                rows_in, rows_out = int(v[0]), int(v[1])
                if cls == "exact" and rows_out != rows_in:
                    self._breach(
                        "flow_violation", f"op:{task_id}/{op}", epoch,
                        f"declared exact selectivity but {rows_in} in != "
                        f"{rows_out} out",
                    )
                elif cls == "contracting" and rows_out > rows_in:
                    self._breach(
                        "flow_violation", f"op:{task_id}/{op}", epoch,
                        f"declared contracting selectivity but amplified "
                        f"{rows_in} in -> {rows_out} out",
                    )
        verified = 0
        rows = 0
        for edge, (t_rows, t_dig) in tx.items():
            r = rx.get(edge)
            if r is None:
                continue
            r_rows, r_dig = r
            if t_rows != r_rows:
                self._breach(
                    "count_mismatch", edge, epoch,
                    f"sender attested {t_rows} rows, receiver {r_rows}",
                )
            elif t_dig != r_dig:
                self._breach(
                    "digest_mismatch", edge, epoch,
                    f"sender digest {t_dig:#018x} != receiver {r_dig:#018x} "
                    f"over {t_rows} rows",
                )
            else:
                verified += 1
                rows += t_rows
            with self.lock:
                self.edges[edge] = {
                    "epoch": epoch,
                    "tx": [t_rows, t_dig],
                    "rx": [r_rows, r_dig],
                    "ok": t_rows == r_rows and t_dig == r_dig,
                }
        with self.lock:
            self.epochs_reconciled += 1
            self.edges_verified += verified
            self.rows_attested += rows
            self.last_epoch = epoch
        AUDIT_EPOCHS.labels(job=self.job_id).inc()
        if verified:
            AUDIT_EDGES_VERIFIED.labels(job=self.job_id).inc(verified)

    # -- surfaces -----------------------------------------------------------

    def status(self) -> dict:
        with self.lock:
            return {
                "job": self.job_id,
                "incarnation": self.max_incarnation,
                "epochs_reconciled": self.epochs_reconciled,
                "edges_verified": self.edges_verified,
                "rows_attested": self.rows_attested,
                "last_epoch": self.last_epoch,
                "breach_count": len(self.breaches),
                "breaches": [dict(b) for b in self.breaches],
                "edges": {e: dict(v) for e, v in self.edges.items()},
            }


# ---------------------------------------------------------------------------
# per-job reconciler registry

_REG_LOCK = threading.Lock()
_RECONCILERS: Dict[str, Reconciler] = {}


def reconciler(job_id: str) -> Reconciler:
    with _REG_LOCK:
        r = _RECONCILERS.get(job_id)
        if r is None:
            r = _RECONCILERS[job_id] = Reconciler(job_id)
        return r


def peek(job_id: str) -> Optional[Reconciler]:
    with _REG_LOCK:
        return _RECONCILERS.get(job_id)


def breach_count(job_id: str) -> Optional[float]:
    """The watchtower conservation signal: breaches recorded for a live
    job, None (abstain) when no reconciler exists yet."""
    r = peek(job_id)
    if r is None:
        return None
    with r.lock:
        return float(len(r.breaches))


def status(job_id: Optional[str] = None) -> dict:
    """/debug/audit payload: every live reconciler (or one job's)."""
    with _REG_LOCK:
        recs = dict(_RECONCILERS)
    if job_id is not None:
        r = recs.get(job_id)
        return r.status() if r is not None else {"job": job_id}
    with _FP_LOCK:
        observed, computed = _FP_OBSERVED, _FP_COMPUTED
        strings, via_dict = _FP_STRINGS, _FP_STRINGS_VIA_DICT
    return {
        "enabled": enabled(),
        # process-wide: observations of a batch by a tap, and how many of
        # them computed a fingerprint (the rest took the batch object's)
        "fingerprints_observed": observed,
        "fingerprints_computed": computed,
        # string columns those computations hashed, and how many of them
        # once per distinct value (through the column's dictionary)
        "string_columns_hashed": strings,
        "string_columns_via_dictionary": via_dict,
        "jobs": {jid: r.status() for jid, r in recs.items()},
    }


def expunge_job(job_id: str) -> None:
    """Job-scoped GC, same path as Registry.drop_job / obs.expunge_job.
    The process-wide breach ring is deliberately NOT touched — drills
    assert over it after the job is torn down."""
    with _REG_LOCK:
        _RECONCILERS.pop(job_id, None)


def reset() -> None:
    """Test hygiene: drop all reconcilers, the breach ring AND the
    fingerprint memo with its totals."""
    global _SEQ, _FP_OBSERVED, _FP_COMPUTED, _FP_STRINGS, _FP_STRINGS_VIA_DICT
    with _REG_LOCK:
        _RECONCILERS.clear()
    _FP_MEMO.clear()
    with _FP_LOCK:
        _FP_OBSERVED = _FP_COMPUTED = 0
        _FP_STRINGS = _FP_STRINGS_VIA_DICT = 0
    with _RING_LOCK:
        _RING.clear()
        _SEQ = 0
