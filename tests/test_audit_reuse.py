"""One fingerprint per batch object, through the engine (ISSUE 27).

A q7-shaped plan on the CPU: one source whose raw batches fan out to two
window subqueries that read different fields (between them every declared
column, the string too: a column nobody reads no longer crosses a fan-out,
ISSUE 31), joined per window; an
embedded cluster, several checkpoint epochs. The conservation ledger's
taps observe every batch at both ends of every edge, and compute a
fingerprint once per batch OBJECT: in one process the source's batch is
observed four times (two out edges, two receivers) and every other batch
twice; across two workers a receiver decodes a new object from its frame
and computes. Every observation is still booked: an in-process queue that
delivers one object twice shows as `count_mismatch` on its edge and
epoch."""

import collections
import json
import os

import pyarrow as pa
import pytest

from arroyo_tpu import obs
from arroyo_tpu.chaos.drill import _run_embedded
from arroyo_tpu.obs import audit, timeline
from arroyo_tpu.operators.queues import BatchQueue

N_ROWS = 1200
SOURCE_EDGES = ("1:0->2:0", "1:0->5:0")     # the fan-out of the raw batch
N_EDGES = 7


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()         # the phase ledger, and `audit.reset()` with it
    yield
    obs.reset()


def q7_shaped_sql(tmp_path):
    src = os.path.join(str(tmp_path), "in.json")
    with open(src, "w") as f:
        for i in range(N_ROWS):
            mins, secs = (i // 1200) % 60, (i // 20) % 60
            f.write(json.dumps({
                "k": i % 64, "v": (i * 37) % 1000 + 1, "note": f"n{i % 7}",
                "timestamp": f"2023-03-01T00:{mins:02d}:{secs:02d}."
                             f"{(i % 20) * 50:03d}Z",
            }) + "\n")
    return f"""
    CREATE TABLE src (
      timestamp TIMESTAMP, k BIGINT NOT NULL, v BIGINT NOT NULL, note TEXT
    ) WITH (connector = 'single_file', path = '{src}', format = 'json',
            type = 'source', throttle_per_sec = '1200',
            event_time_field = 'timestamp');
    CREATE TABLE out (k BIGINT, v BIGINT, c BIGINT) WITH (
      connector = 'single_file', path = '{tmp_path}/out.json',
      format = 'json', type = 'sink');
    INSERT INTO out
    SELECT W.k, W.v, W.c FROM (
      SELECT k, v, tumble(interval '10 second') as w, count(*) as c
      FROM src WHERE note IS NOT NULL GROUP BY 1, 2, w
    ) AS W JOIN (
      SELECT max(v) as maxv, tumble(interval '10 second') as w
      FROM src GROUP BY w
    ) AS M ON W.w = M.w AND W.v = M.maxv;
    """


class Watch:
    """Every observation of every tap, with its batch kept alive so that
    an id names one object for the whole run, and every reconciled epoch."""

    def __init__(self, monkeypatch):
        self.seen = []                  # (edge, batch) per observation
        self.sealed = collections.Counter()     # edge -> newest epoch sealed
        self.epochs = []
        self.reconciler = None
        watch = self
        observe, seal = audit.EdgeTap.observe, audit.EdgeTap.seal
        reconcile = audit.Reconciler.reconcile

        def observed(tap, batch):
            watch.seen.append((tap.edge, batch))
            observe(tap, batch)

        def sealed(tap, epoch):
            watch.sealed[tap.edge] = max(watch.sealed[tap.edge], epoch)
            seal(tap, epoch)

        def reconciled(rec, epoch, audits):
            watch.reconciler = rec
            watch.epochs.append(epoch)
            reconcile(rec, epoch, audits)

        monkeypatch.setattr(audit.EdgeTap, "observe", observed)
        monkeypatch.setattr(audit.EdgeTap, "seal", sealed)
        monkeypatch.setattr(audit.Reconciler, "reconcile", reconciled)

    def per_edge(self):
        """edge -> how often each object was observed on it."""
        out = collections.defaultdict(collections.Counter)
        for edge, batch in self.seen:
            out[edge][id(batch)] += 1
        return out

    def objects(self):
        return {id(batch) for _edge, batch in self.seen}


def run(tmp_path, job_id, n_workers=1):
    mark = audit.breach_mark()
    _run_embedded(
        q7_shaped_sql(tmp_path), job_id, os.path.join(str(tmp_path), "ck"),
        n_workers, 1, max_restarts=0, heartbeat_interval=0.1,
        heartbeat_timeout=30.0, checkpoint_interval=0.2, timeout=120.0)
    return audit.breaches_since(mark, job_id)


@pytest.mark.parametrize("n_workers", [1, 2], ids=["in-process", "remote"])
def test_a_fingerprint_is_computed_once_per_batch_object(
        tmp_path, monkeypatch, n_workers):
    watch = Watch(monkeypatch)
    breaches = run(tmp_path, f"reuse-{n_workers}", n_workers)
    assert breaches == []
    status = audit.status()
    observed, computed = (status["fingerprints_observed"],
                          status["fingerprints_computed"])
    per_edge = watch.per_edge()
    assert set(per_edge) >= set(SOURCE_EDGES) and len(per_edge) == N_EDGES
    objects = watch.objects()
    assert observed == len(watch.seen)
    assert computed == len(objects)             # once per object, no more
    # the raw batch goes to both subqueries as the same object
    raw = set(per_edge[SOURCE_EDGES[0]]) & set(per_edge[SOURCE_EDGES[1]])
    assert len(raw) > 100
    if n_workers == 1:
        # both ends of every edge see the sender's object: the source's
        # batches four times, every other batch twice
        assert all(n == 2 for c in per_edge.values() for n in c.values())
        assert raw == set(per_edge[SOURCE_EDGES[0]])
        assert observed == 4 * len(raw) + 2 * (len(objects) - len(raw))
        assert computed < 0.4 * observed
    else:
        # an edge that crosses the data plane hands its receiver new
        # objects, which are computed: no object is seen at both ends
        remote = [e for e, c in per_edge.items() if set(c.values()) == {1}]
        assert remote, "no edge crossed the data plane"
    # the reconciler verified every edge of every epoch
    assert len(watch.epochs) >= 2
    st = watch.reconciler.status()
    assert st["epochs_reconciled"] == len(watch.epochs)
    assert st["edges_verified"] == N_EDGES * len(watch.epochs)
    assert st["breach_count"] == 0
    # the ledger: every observation inside an `audit.attest`, every
    # computation a count inside one (no seconds of its own)
    t = timeline.phase_totals()
    assert t["audit.attest"]["count"] == observed
    assert t["audit.attest"]["n"] == sum(
        batch.num_rows for _edge, batch in watch.seen)
    assert t["audit.fp"]["count"] == computed
    assert t["audit.fp"]["total_s"] == 0.0
    # string columns are counted where they are hashed (ISSUE 29): once per
    # computed fingerprint of a batch that has one, never on a memo hit
    seen = {id(b): b for _edge, b in watch.seen}
    strings = sum(pa.types.is_string(f.type)
                  for b in seen.values() for f in b.schema)
    assert strings > 100
    assert status["string_columns_hashed"] == strings
    assert t["audit.fp.str"]["padded"] == strings
    assert (t["audit.fp.str"]["n"]
            == status["string_columns_via_dictionary"] <= strings)
    if n_workers == 1:
        # an in-process receiver never computes: the sink's task books its
        # `audit.attest` and no `audit.fp`; a task in the middle computes
        # what it sends, the source its raw batches once
        rx = timeline.totals(task="13-0")
        assert rx["audit.attest"]["count"] > 0 and "audit.fp" not in rx
        for task, out_edge in (("1-0", SOURCE_EDGES[0]),
                               ("2-0", "2:0->3:0")):
            assert timeline.totals(task=task)["audit.fp"]["count"] == len(
                per_edge[out_edge])


def test_an_in_process_double_delivery_is_a_count_mismatch(
        tmp_path, monkeypatch):
    """The queue of one of the source's out edges hands its 300th batch
    over twice: the same object, so the receiver computes nothing, and
    still books it twice."""
    watch = Watch(monkeypatch)
    edge = SOURCE_EDGES[0]
    push = BatchQueue._push
    state = {"n": 0}

    def push_twice(queue, item, nbytes):
        push(queue, item, nbytes)
        if getattr(queue, "audit_edge", None) == edge and isinstance(
                item, pa.RecordBatch):
            state["n"] += 1
            if state["n"] == 300:
                state["epoch"] = watch.sealed[edge] + 1
                push(queue, item, nbytes)

    monkeypatch.setattr(BatchQueue, "_push", push_twice)
    breaches = run(tmp_path, "reuse-dup")
    assert state.get("epoch", 0) >= 1, "the double delivery did not happen"
    assert [(b["kind"], b["edge"], b["epoch"]) for b in breaches] == [
        ("count_mismatch", edge, state["epoch"])]
    assert "receiver" in breaches[0]["detail"]
    dup = [key for key, n in watch.per_edge()[edge].items() if n == 3]
    assert len(dup) == 1                # sender once, receiver twice
    assert audit.status()["fingerprints_computed"] == len(watch.objects())
