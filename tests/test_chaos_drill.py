"""The full exactly-once acceptance drills (ISSUE 2): worker SIGKILL
mid-window + data-plane drop + manifest CAS loss across three goldens
(windowed aggregate, join, updating query), plus the transactional-kafka
drill. Slow: each kill costs a heartbeat-timeout detection wait; the
default suite runs the fast smoke drill in test_chaos.py instead."""

import pytest

from arroyo_tpu import chaos
from arroyo_tpu.chaos import drill

pytestmark = pytest.mark.slow


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    chaos.clear()
    yield
    chaos.clear()


@pytest.mark.parametrize("query", drill.DEFAULT_DRILL_QUERIES)
def test_standard_drill(query, tmp_path):
    """(a) SIGKILL a worker mid-window, (b) drop a data-plane connection,
    (c) fail a manifest CAS write — output identical to the fault-free
    run, every scheduled fault fired."""
    res = drill.run_drill(query, seed=20260804, workdir=str(tmp_path))
    assert res.passed, f"{query}: {res.error}\nfired: {res.fired}"
    assert res.restarts >= 2  # kill + at least one of drop/CAS recovered
    assert res.comparable_log == res.expected_log


def test_same_seed_reproduces_fired_log(tmp_path):
    """The acceptance reproducibility clause, run for real: two faulted
    runs under the same chaos seed produce the same comparable
    fired-fault log."""
    a = drill.run_drill(
        drill.DEFAULT_DRILL_QUERIES[0], seed=777,
        workdir=str(tmp_path / "a"),
    )
    b = drill.run_drill(
        drill.DEFAULT_DRILL_QUERIES[0], seed=777,
        workdir=str(tmp_path / "b"),
    )
    assert a.passed, a.error
    assert b.passed, b.error
    assert a.comparable_log == b.comparable_log
    # and a different seed schedules a different log
    assert (
        drill.standard_plan(777).expected_log()
        != drill.standard_plan(778).expected_log()
    )


def test_rescale_drill_exactly_once(tmp_path):
    """ISSUE 5 satellite: a worker SIGKILL lands mid-autoscaler-triggered
    rescale (the stop checkpoint fails, the job recovers, the autoscaler
    re-decides) and a later rescale fails between its durable stop
    checkpoint and the reschedule (recovery must come back at the NEW
    parallelism) — canonical output byte-identical to the fault-free run,
    every scheduled rescale.* fault fired, decision audit log written."""
    res = drill.run_rescale_drill(seed=20260804, workdir=str(tmp_path))
    assert res.passed, f"{res.error}\nfired: {res.fired}"
    assert res.restarts >= 1  # the mid-rescale kill forced a recovery
    fired_points = {f["point"] for f in res.fired}
    assert {"rescale.stop_delay", "rescale.reschedule_fail",
            "worker.kill"} <= fired_points
    assert (tmp_path / "autoscale_decisions.json").exists()


def test_pipeline_drill_staged_batches_survive_kill(tmp_path):
    """A fused stateless segment on small batches takes a worker SIGKILL
    and a dropped connection mid-stream — canonical output byte-identical
    to the UNFUSED fault-free run (no event lost or duplicated), every
    fault fired, the conservation audit silent."""
    res = drill.run_pipeline_drill(seed=20260804, workdir=str(tmp_path))
    assert res.passed, f"{res.error}\nfired: {res.fired}"
    assert res.restarts >= 1
    assert not res.unfired and not res.audit_breaches


def test_state_bloat_drill_flat_checkpoints(tmp_path):
    """ISSUE 8 acceptance (ROADMAP item 4): session state grows ~10x
    during the run, a worker is SIGKILLed mid-upload with storage
    latency widening the in-flight flush window — output byte-identical
    to the fault-free run AND checkpoint capture time + per-epoch delta
    bytes stay ~flat as state grows (a full-snapshot design shows ~10x
    growth on both)."""
    res = drill.run_state_bloat_drill(seed=20260804, workdir=str(tmp_path))
    assert res.passed, f"{res.error}\nextras: {res.extras}"
    assert res.restarts >= 1  # the mid-upload SIGKILL forced a recovery
    assert res.extras["epochs_measured"] >= 6, res.extras
    assert (
        res.extras["capture_ms_late_median"]
        <= 2.0 * res.extras["capture_ms_early_median"] + 2.0
    ), res.extras


def test_kafka_exactly_once_drill(tmp_path):
    """VERDICT r5 item 8 wiring: the protocol-shaped kafka fake (fenced
    producer epochs, abortable transactions) driven through the embedded
    cluster under worker kill + manifest CAS loss — the transactional
    sink's read-committed output carries every row exactly once."""
    res = drill.run_kafka_drill(seed=20260804, workdir=str(tmp_path))
    assert res.passed, f"{res.error}\nfired: {res.fired}"
    assert res.restarts >= 1
