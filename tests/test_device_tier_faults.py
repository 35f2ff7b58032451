"""Device execution tiers under faults (VERDICT r5 item 2, scoped slice):
a representative golden subset — ≥6 queries including one session window
and one updating query — with `tpu.require_accelerator` forced OFF (device
kernels engage on the CPU-jax backend) over the native slot directory,
which is what the chip runs, plus one checkpoint/kill/restore cycle
through the device-tier paths.

Gated behind ARROYO_DEVICE_TIER_FAULTS=1 (or `-m device_tier` after
setting it): the XLA compiles make this subset too heavy for tier-1, and
the device tiers are exercised compile-free elsewhere in the suite.

    ARROYO_DEVICE_TIER_FAULTS=1 python -m pytest tests/test_device_tier_faults.py -q
"""

import asyncio
import os

import pytest

from arroyo_tpu import chaos
from arroyo_tpu.chaos import drill
from arroyo_tpu.config import update
from arroyo_tpu.engine import Engine
from arroyo_tpu.sql import plan_query

pytestmark = [
    pytest.mark.device_tier,
    pytest.mark.skipif(
        not os.environ.get("ARROYO_DEVICE_TIER_FAULTS"),
        reason="set ARROYO_DEVICE_TIER_FAULTS=1 to run device-tier fault "
        "coverage (XLA-compile heavy)",
    ),
]

# ≥6 goldens: windowed aggregates (tumble/hop), one SESSION window, one
# UPDATING query, a join, and a distinct aggregate — the surfaces the
# device kernels (scatter-reduce accumulators, device join probe)
# actually specialize
DEVICE_TIER_QUERIES = (
    "hourly_by_event_type",    # tumbling window aggregate
    "sliding_window_end",      # hopping window
    "session_window",          # session window (required by the issue)
    "updating_aggregate",      # updating query (required by the issue)
    "offset_impulse_join",     # windowed join
    "distinct_aggregates",     # distinct accumulator path
    "grouped_aggregates",      # updating debezium aggregate
)

DEVICE_TIER_CONFIG = {
    "enabled": True,
    "require_accelerator": False,  # engage device kernels on CPU-jax
}


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    chaos.clear()
    yield
    chaos.clear()


def _golden(name):
    return os.path.join(drill.DEFAULT_GOLDEN_DIR, "queries", f"{name}.sql")


@pytest.mark.parametrize("name", DEVICE_TIER_QUERIES)
def test_device_tier_golden(name, tmp_path):
    """Each golden must match its committed output with the device tiers
    forced on — identical semantics to the host paths."""
    query_path = _golden(name)
    headers = drill.query_headers(query_path)
    drill.register_query_udfs(headers, drill.DEFAULT_GOLDEN_DIR)
    out = str(tmp_path / "out.json")
    sql = drill.load_query(query_path, out, drill.DEFAULT_GOLDEN_DIR)

    async def go():
        eng = Engine(plan_query(sql, parallelism=2).graph).start()
        await eng.join(120)

    with update(tpu=DEVICE_TIER_CONFIG):
        asyncio.run(go())
    got = drill.canonicalize_output(out, sql, headers)
    golden_file = os.path.join(
        drill.DEFAULT_GOLDEN_DIR, "golden_outputs", f"{name}.json"
    )
    want = [line.strip() for line in open(golden_file)]
    assert got == want, f"{name}: device-tier output diverged from golden"


def test_device_tier_checkpoint_kill_restore(tmp_path):
    """One checkpoint/kill/restore cycle with the device tiers on: a
    worker SIGKILL mid-window through the embedded cluster, restore from
    the durable checkpoint, output identical to the fault-free run —
    device accumulator state must round-trip through checkpoints."""

    def kill_plan(seed):
        from arroyo_tpu.chaos import FaultPlan

        return FaultPlan(seed).add("worker.kill", at_hits=(10,))

    with update(tpu=DEVICE_TIER_CONFIG):
        res = drill.run_drill(
            "hourly_by_event_type", seed=99, workdir=str(tmp_path),
            plan_factory=kill_plan,
        )
    assert res.passed, res.error
    assert res.restarts >= 1
