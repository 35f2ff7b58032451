"""Conservation ledger (obs/audit.py): fingerprint algebra, edge taps,
reconciler intake/reconcile checks, the process-wide breach ring, and the
report surfaces (status payloads, watchtower rule, openapi route)."""

import pyarrow as pa
import pytest

from arroyo_tpu.obs import audit

MOD = 1 << 64


@pytest.fixture(autouse=True)
def _clean_ledger():
    audit.reset()
    yield
    audit.reset()


def _batch(vals, extra=None):
    arrays = [pa.array(vals, type=pa.int64())]
    names = ["v"]
    if extra is not None:
        arrays.append(extra)
        names.append("x")
    return pa.RecordBatch.from_arrays(arrays, names=names)


# -- batch fingerprint -------------------------------------------------------


def test_fingerprint_counts_rows_and_zero_rows():
    assert audit.batch_fingerprint(_batch([]))[0] == 0
    assert audit.batch_fingerprint(_batch([])) == (0, 0)
    n, d = audit.batch_fingerprint(_batch([1, 2, 3]))
    assert n == 3 and d != 0


def test_fingerprint_is_order_insensitive():
    a = audit.batch_fingerprint(_batch([1, 2, 3, 4]))
    b = audit.batch_fingerprint(_batch([4, 2, 1, 3]))
    assert a == b


def test_fingerprint_is_slicing_invariant():
    whole = _batch(list(range(100)))
    _, want = audit.batch_fingerprint(whole)
    total = 0
    for lo in range(0, 100, 7):
        _, d = audit.batch_fingerprint(whole.slice(lo, 7))
        total = (total + d) % MOD
    assert total == want


def test_fingerprint_sees_content_not_just_counts():
    _, a = audit.batch_fingerprint(_batch([1, 2, 3]))
    _, b = audit.batch_fingerprint(_batch([1, 2, 4]))
    assert a != b


def test_fingerprint_hashes_struct_children():
    def struct(vals):
        return pa.array([{"a": v, "b": v * 2} for v in vals])

    _, a = audit.batch_fingerprint(_batch([1, 2], struct([7, 8])))
    _, b = audit.batch_fingerprint(_batch([1, 2], struct([7, 9])))
    assert a != b
    # row-order invariance holds with struct columns too
    _, c = audit.batch_fingerprint(_batch([2, 1], struct([8, 7])))
    _, d = audit.batch_fingerprint(_batch([1, 2], struct([7, 8])))
    assert c == d


def test_fingerprint_handles_list_columns():
    """unnest / ARRAY_AGG shapes: list columns hash per-row (elements
    order-insensitive within the row, length + nullness salted in) and
    keep the slicing/ordering algebra of the flat fast path."""
    def lists(vals):
        return pa.array(vals, type=pa.list_(pa.int64()))

    whole = _batch([1, 2, 3, 4], lists([[1, 2], [], None, [3]]))
    n, want = audit.batch_fingerprint(whole)
    assert n == 4
    n1, d1 = audit.batch_fingerprint(whole.slice(0, 2))
    n2, d2 = audit.batch_fingerprint(whole.slice(2, 2))
    assert (n1 + n2, (d1 + d2) % MOD) == (n, want)
    # NULL list != empty list; element placement across rows matters
    _, a = audit.batch_fingerprint(
        _batch([1, 2, 3, 4], lists([[1, 2], [], [], [3]])))
    _, b = audit.batch_fingerprint(
        _batch([1, 2, 3, 4], lists([[1], [2], None, [3]])))
    assert len({want, a, b}) == 3


# -- edge taps ---------------------------------------------------------------


def test_edge_tap_seals_per_epoch_and_resets():
    tap = audit.EdgeTap("a:0->b:0")
    tap.observe(_batch([1, 2]))
    tap.observe(_batch([3]))
    tap.seal(1)
    tap.observe(_batch([9]))
    tap.seal(2)
    r1, d1 = tap.sealed[1]
    r2, d2 = tap.sealed[2]
    assert r1 == 3 and r2 == 1 and d1 != d2
    assert tap.drain(1) == (r1, d1)
    assert tap.drain(1) is None  # drained exactly once
    assert tap.drain(99) is None


def test_edge_tap_split_vs_whole_attestation_matches():
    """A keyed shuffle slices batches; the sum of the slices' attestation
    must equal the unsliced stream's (digest commutativity end-to-end)."""
    whole, split = audit.EdgeTap("e"), audit.EdgeTap("e")
    b = _batch(list(range(50)))
    whole.observe(b)
    for lo in range(0, 50, 11):
        split.observe(b.slice(lo, 11))
    whole.seal(1)
    split.seal(1)
    assert whole.sealed[1] == split.sealed[1]


# -- one fingerprint per batch object (ISSUE 27) -----------------------------

# (rows, digest) of fixed one-column batches and of all columns together,
# written here from the function as it stood before the memo: the memo must
# hand on the same numbers, and a later change of the hash shows here
_FIXED = {
    "ints": pa.array([1, -2, 3, 2**40, 0], pa.int64()),
    "floats": pa.array([1.5, -0.0, 0.0, 3.25e10, -7.125], pa.float64()),
    "strings": pa.array(["a", "", "bid", "channel-7", "a"], pa.string()),
    "nulls": pa.array([None, 4, None, 6, 7], pa.int64()),
    "null_strings": pa.array(["x", None, "y", None, ""], pa.string()),
    "struct": pa.array(
        [{"s": 1, "e": "p"}, {"s": 2, "e": None}, {"s": 3, "e": "q"},
         {"s": 4, "e": "r"}, {"s": 5, "e": "p"}],
        pa.struct([("s", pa.int64()), ("e", pa.string())])),
    "list": pa.array([[1, 2], [], None, [3], [2, 1]], pa.list_(pa.int64())),
}
_FIXED_DIGESTS = {
    "ints": 1191521663248509046,
    "floats": 7871750446412350719,
    "strings": 3236795247269411933,
    "nulls": 10014177305726986819,
    "null_strings": 9781650425886190462,
    "struct": 12137543665217278257,
    "list": 18358363235676902031,
    "all": 10000658056212722291,
}


def _fixed_batch(which):
    names = list(_FIXED) if which == "all" else [which]
    return pa.RecordBatch.from_arrays([_FIXED[n] for n in names], names=names)


def _fp_totals():
    s = audit.status()
    return s["fingerprints_observed"], s["fingerprints_computed"]


@pytest.mark.parametrize("which", sorted(_FIXED_DIGESTS))
def test_the_digest_of_a_fixed_batch_is_what_it_was(which):
    batch = _fixed_batch(which)
    assert audit.batch_fingerprint(batch) == (5, _FIXED_DIGESTS[which])
    tap = audit.EdgeTap("1:0->2:0")
    tap.observe(batch)          # computes
    tap.observe(batch)          # takes the object's
    assert (tap.rows, tap.digest) == (
        10, 2 * _FIXED_DIGESTS[which] % MOD)


def test_one_object_seen_by_two_taps_is_computed_once_and_booked_twice():
    batch = _fixed_batch("all")
    want = audit.batch_fingerprint(batch)
    tx, rx = audit.EdgeTap("1:0->2:0"), audit.EdgeTap("1:0->5:0")
    tx.observe(batch)
    rx.observe(batch)
    assert _fp_totals() == (2, 1)
    assert (tx.rows, tx.digest) == (rx.rows, rx.digest) == want
    # every observation is booked, whoever computed: a second delivery of
    # the same object doubles the receiver's attestation
    rx.observe(batch)
    assert _fp_totals() == (3, 1)
    assert (rx.rows, rx.digest) == (10, 2 * want[1] % MOD)
    assert (tx.rows, tx.digest) == want


def _built_again(batch):
    return pa.RecordBatch.from_arrays(batch.columns, schema=batch.schema)


def _ipc_round_trip(batch):
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return pa.ipc.open_stream(sink.getvalue()).read_next_batch()


@pytest.mark.parametrize("copy", [
    _built_again, lambda b: b.slice(0), _ipc_round_trip,
], ids=["from_arrays", "slice0", "ipc"])
def test_an_equal_batch_that_is_another_object_is_computed(copy):
    """Identity, not content: what a remote receiver decodes from a frame
    is a new object, and is hashed like any batch never seen."""
    batch = _fixed_batch("all")
    other = copy(batch)
    assert other is not batch and other.equals(batch)
    sender, receiver = audit.EdgeTap("e"), audit.EdgeTap("e")
    sender.observe(batch)
    receiver.observe(other)
    assert _fp_totals() == (2, 2)
    assert (receiver.rows, receiver.digest) == (sender.rows, sender.digest)


def test_a_memo_entry_lives_as_long_as_its_batch():
    tap = audit.EdgeTap("e")
    batch = _batch([1, 2, 3])
    rows, digest = audit.batch_fingerprint(batch)
    tap.observe(batch)
    assert list(audit._FP_MEMO) == [id(batch)]
    del batch
    assert audit._FP_MEMO == {}
    # 10,000 short-lived batches, each another object (ids ARE reused
    # here): every one is computed, none is kept
    for i in range(10_000):
        b = _batch([i, i + 1])
        n, d = audit.batch_fingerprint(b)
        rows, digest = rows + n, (digest + d) % MOD
        tap.observe(b)
        assert len(audit._FP_MEMO) == 1
    del b
    assert audit._FP_MEMO == {}
    assert _fp_totals() == (10_001, 10_001)
    assert (tap.rows, tap.digest) == (rows, digest)


def test_a_reused_id_does_not_hit_and_reset_empties_the_memo():
    tap = audit.EdgeTap("e")
    old, new = _batch([1, 2, 3]), _batch([7, 8])
    tap.observe(old)
    # as if `new` had been given the id of a batch the memo still holds:
    # the entry's reference is to another object
    audit._FP_MEMO[id(new)] = audit._FP_MEMO[id(old)]
    fresh = audit.EdgeTap("e")
    fresh.observe(new)
    assert (fresh.rows, fresh.digest) == audit.batch_fingerprint(new)
    assert _fp_totals() == (2, 2)
    assert audit._FP_MEMO[id(new)][0]() is new
    audit.reset()
    assert audit._FP_MEMO == {} and _fp_totals() == (0, 0)
    fresh.observe(new)                  # computed again after a reset
    assert _fp_totals() == (1, 1)


def test_taps_on_many_threads_share_the_memo_and_lose_no_count():
    """More threads than cores, a short switch interval: each thread's tap
    observes the same 20 long-lived batches and 200 short-lived ones of
    its own. Every tap holds exactly what fresh computations give, the
    totals lose no update, and only the live batches stay in the memo."""
    import sys
    import threading

    shared = [_batch([i, i + 1, i + 2]) for i in range(20)]
    want_rows = want_digest = 0
    for b in shared:
        n, d = audit.batch_fingerprint(b)
        want_rows, want_digest = want_rows + n, (want_digest + d) % MOD
    own_n, own_d = audit.batch_fingerprint(_batch([7, 7]))
    n_threads, n_own = 16, 200
    taps = [audit.EdgeTap(f"e{i}") for i in range(n_threads)]
    start = threading.Barrier(n_threads)

    def work(tap):
        start.wait(timeout=30)
        for i in range(n_own):
            tap.observe(_batch([7, 7]))
            tap.observe(shared[i % len(shared)])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in taps]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    reps = n_own // len(shared)
    for tap in taps:
        assert tap.rows == n_own * own_n + reps * want_rows
        assert tap.digest == (n_own * own_d + reps * want_digest) % MOD
    observed, computed = _fp_totals()
    assert observed == n_threads * n_own * 2
    # every short-lived batch is computed; a shared one at least once and
    # at most once per thread (two threads may meet on its first sight)
    own = n_threads * n_own
    assert own + len(shared) <= computed <= own + n_threads * len(shared)
    assert set(audit._FP_MEMO) == {id(b) for b in shared}


def test_edge_key_shape():
    assert audit.edge_key("3", 0, "5", 1) == "3:0->5:1"


# -- reconciler: intake (recovery conservation) ------------------------------


def _att(rows=5, dig=0xAB, edge="1:0->2:0", gen="j@1"):
    return {"tx": {edge: [rows, dig]}, "rx": {}, "ops": {}, "flow": {},
            "gen": gen}


def test_intake_accepts_fresh_epochs():
    r = audit.Reconciler("j")
    assert r.intake("t1", 1, _att(), None) is False
    assert r.intake("t1", 5, _att(), 4) is False
    assert r.breaches == []


def test_intake_fences_republished_epoch_silently():
    """Redelivery of exactly the published epoch is an rpc retry racing
    the publish: fenced, never flagged."""
    r = audit.Reconciler("j")
    assert r.intake("t1", 4, _att(), 4) is True
    assert r.breaches == []


def test_intake_flags_strictly_stale_epoch_as_rewind():
    r = audit.Reconciler("j")
    assert r.intake("t1", 2, _att(edge="7:1->9:0"), 5) is True
    (b,) = r.breaches
    assert b["kind"] == "rewind_behind_commit"
    assert b["edge"] == "7:1->9:0" and b["epoch"] == 2


def test_intake_flags_fenced_generation_as_zombie():
    r = audit.Reconciler("j")
    assert r.intake("t1", 3, _att(gen="j@2"), None) is False
    assert r.max_incarnation == 2
    assert r.intake("t2", 4, _att(gen="j@1", edge="1:0->2:1"), None) is True
    (b,) = r.breaches
    assert b["kind"] == "zombie_generation"
    assert b["edge"] == "1:0->2:1" and b["epoch"] == 4
    # the live generation keeps reporting unhindered
    assert r.intake("t1", 4, _att(gen="j@2"), None) is False


def test_intake_ignores_reports_without_attestation():
    r = audit.Reconciler("j")
    assert r.intake("t1", 1, None, 5) is False
    assert r.intake("t1", 1, {}, 5) is False
    assert r.breaches == []


def test_incarnation_parsing():
    r = audit.Reconciler
    assert r._incarnation("job@3") == 3
    assert r._incarnation("a@b@12") == 12
    assert r._incarnation("no-suffix") is None
    assert r._incarnation("job@x") is None
    assert r._incarnation(None) is None


# -- reconciler: reconcile (edge joins + flow) -------------------------------


def test_reconcile_verifies_matching_edges():
    r = audit.Reconciler("j")
    r.reconcile(3, {
        "t1": {"tx": {"1:0->2:0": [10, 77]}, "rx": {}, "ops": {}, "flow": {}},
        "t2": {"tx": {}, "rx": {"1:0->2:0": [10, 77]}, "ops": {}, "flow": {}},
    })
    assert r.breaches == []
    assert r.epochs_reconciled == 1
    assert r.edges_verified == 1
    assert r.rows_attested == 10
    assert r.last_epoch == 3
    assert r.edges["1:0->2:0"]["ok"] is True


def test_reconcile_flags_count_then_digest_mismatch():
    r = audit.Reconciler("j")
    r.reconcile(2, {
        "t1": {"tx": {"a": [10, 1], "b": [5, 2]}, "rx": {}, "ops": {},
               "flow": {}},
        "t2": {"tx": {}, "rx": {"a": [9, 1], "b": [5, 3]}, "ops": {},
               "flow": {}},
    })
    kinds = {b["edge"]: b["kind"] for b in r.breaches}
    assert kinds == {"a": "count_mismatch", "b": "digest_mismatch"}
    assert all(b["epoch"] == 2 for b in r.breaches)
    assert r.edges["a"]["ok"] is False and r.edges["b"]["ok"] is False


def test_reconcile_skips_one_sided_edges():
    """A peer that finished before this barrier contributes no attestation;
    one-sided edges are skipped, never flagged."""
    r = audit.Reconciler("j")
    r.reconcile(1, {
        "t1": {"tx": {"a": [10, 1]}, "rx": {}, "ops": {}, "flow": {}},
        "t2": None,
    })
    assert r.breaches == [] and r.edges_verified == 0


def test_reconcile_checks_declared_flow_classes():
    r = audit.Reconciler("j")
    r.reconcile(1, {
        "t1": {
            "tx": {}, "rx": {},
            "ops": {"0:filter": [10, 12], "1:map": [12, 11],
                    "2:window": [11, 2], "3:udf": [2, 9]},
            "flow": {"0:filter": "contracting", "1:map": "exact",
                     "2:window": "buffering", "3:udf": "any"},
        },
    })
    kinds = {b["edge"]: b["kind"] for b in r.breaches}
    # contracting amplified + exact lossy flagged; buffering/any never
    assert kinds == {"op:t1/0:filter": "flow_violation",
                     "op:t1/1:map": "flow_violation"}


def test_reconcile_flags_mixed_generation_epoch():
    r = audit.Reconciler("j")
    r.reconcile(6, {
        "t1": dict(_att(gen="j@2"), rx={}),
        "t2": dict(_att(gen="j@1", edge="4:0->5:0"), rx={}),
    })
    zombies = [b for b in r.breaches if b["kind"] == "zombie_generation"]
    (b,) = zombies
    assert b["edge"] == "4:0->5:0" and b["epoch"] == 6


# -- breach ring + registry --------------------------------------------------


def test_ring_mark_since_and_job_filter():
    mark = audit.breach_mark()
    audit.reconciler("j1").intake("t", 1, _att(gen="j1@1"), 3)
    audit.reconciler("j2").intake("t", 2, _att(gen="j2@1"), 9)
    assert [b["job"] for b in audit.breaches_since(mark)] == ["j1", "j2"]
    assert [b["epoch"] for b in audit.breaches_since(mark, "j2")] == [2]
    mark2 = audit.breach_mark()
    assert audit.breaches_since(mark2) == []


def test_ring_survives_job_expunge():
    """Drills assert audit silence AFTER the embedded controller tears
    the job down; the ring must outlive the reconciler."""
    mark = audit.breach_mark()
    audit.reconciler("j").intake("t", 1, _att(), 3)
    assert audit.peek("j") is not None
    audit.expunge_job("j")
    assert audit.peek("j") is None
    assert len(audit.breaches_since(mark, "j")) == 1


def test_breach_count_abstains_without_reconciler():
    assert audit.breach_count("nope") is None
    audit.reconciler("j")
    assert audit.breach_count("j") == 0.0
    audit.reconciler("j").intake("t", 1, _att(), 3)
    assert audit.breach_count("j") == 1.0


def test_status_shapes():
    audit.reconciler("j1").reconcile(1, {
        "t": {"tx": {"a": [1, 2]}, "rx": {"a": [1, 2]}, "ops": {},
              "flow": {}},
    })
    all_status = audit.status()
    assert all_status["enabled"] is True
    assert set(all_status["jobs"]) == {"j1"}
    one = audit.status("j1")
    assert one["job"] == "j1" and one["edges_verified"] == 1
    assert one["breach_count"] == 0 and one["incarnation"] is None
    assert audit.status("ghost") == {"job": "ghost"}


# -- surfaces ----------------------------------------------------------------


def test_watchtower_has_conservation_rule():
    from arroyo_tpu.obs.watchtower import build_rules

    rules = {r.name: r for r in build_rules()}
    assert "conservation" in rules
    rule = rules["conservation"]
    assert rule.kind == "above"
    assert rule.threshold == 0.5  # watch.conservation_breaches default


def test_openapi_exposes_audit_route_and_schema():
    from arroyo_tpu.api.openapi import build_spec

    s = build_spec()
    assert "/api/v1/jobs/{job_id}/audit" in s["paths"]
    schemas = s["components"]["schemas"]
    assert "AuditReport" in schemas and "AuditBreach" in schemas
    assert "kind" in schemas["AuditBreach"]["properties"]


def test_audit_disabled_via_config_env():
    from arroyo_tpu.config import update

    assert audit.enabled() is True
    with update(audit={"enabled": False}):
        assert audit.enabled() is False
    assert audit.enabled() is True
