"""The phase ledger's two counts that carry no time (ISSUE 26): `dir.new`
(slots `_scatter`'s `dir.assign` call created) and `ckpt.delta` (rows a
capture's incremental delta carries), over seeded runs of the tumbling and
the hop operator through the engine."""

import asyncio
import json

import numpy as np
import pytest

from arroyo_tpu import obs
from arroyo_tpu.engine import Engine
from arroyo_tpu.obs import timeline
from arroyo_tpu.operators import windows
from arroyo_tpu.sql import plan_query

T0_MS = 1_677_628_800_000           # 2023-03-01T00:00:00Z
WINDOWS = {
    # name: (the SQL's window, the bin's width in ms)
    "tumble": ("tumble(interval '10 second')", 10_000),
    "hop": ("hop(interval '2 second', interval '10 second')", 2_000),
}


@pytest.fixture(autouse=True)
def _fresh_ledger():
    obs.reset()
    yield
    obs.reset()


def seeded_rows(seed, n=6000, span_ms=40_000):
    """In order over 40 s of event time; some rows repeat a (a, b) of
    their own bin, the others open a new one."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, span_ms, n))
    a = rng.integers(0, 40, n)
    b = rng.integers(0, 25, n)
    return ts, a, b


def run_query(tmp_path, window, rows, checkpoints=0):
    ts, a, b = rows
    src = str(tmp_path / "in.json")
    with open(src, "w") as f:
        for t, x, y in zip(ts.tolist(), a.tolist(), b.tolist()):
            stamp = np.datetime64(T0_MS + t, "ms").astype(str) + "Z"
            f.write(json.dumps({"a": x, "b": y, "timestamp": stamp}) + "\n")
    sql = f"""
    CREATE TABLE src (
      timestamp TIMESTAMP, a BIGINT NOT NULL, b BIGINT NOT NULL
    ) WITH (connector = 'single_file', path = '{src}', format = 'json',
            type = 'source', throttle_per_sec = '3000',
            event_time_field = 'timestamp');
    CREATE TABLE out (a BIGINT, b BIGINT, cnt BIGINT) WITH (
      connector = 'single_file', path = '{tmp_path}/out.json',
      format = 'json', type = 'sink');
    INSERT INTO out
    SELECT a, b, cnt FROM (
      SELECT a, b, count(*) as cnt, {window} as w
      FROM src GROUP BY 1, 2, w);
    """

    async def run():
        plan = plan_query(sql, parallelism=1)
        eng = Engine(plan.graph, job_id="counts",
                     storage_url=str(tmp_path / "ckpt")).start()
        win = next(s for s in eng.program.subtasks
                   if not s.node.is_source
                   and "window" in s.node.description)
        recv = win.runner._batches_recv
        seen = recv.get()       # the registry's counter outlives a job
        for _ in range(checkpoints):
            # each capture after at least one new batch: a delta each
            for _ in range(3000):
                if recv.get() > seen:
                    break
                await asyncio.sleep(0.01)
            seen = recv.get()
            await eng.checkpoint_and_wait()
        await eng.join(120)             # the bounded source runs out

    asyncio.run(run())
    return timeline.phase_totals("counts")


@pytest.mark.parametrize("kind", sorted(WINDOWS))
def test_dir_new_counts_the_distinct_bin_key_pairs(tmp_path, kind):
    window, width_ms = WINDOWS[kind]
    ts, a, b = rows = seeded_rows(seed=26 + len(kind))
    t = run_query(tmp_path, window, rows)
    pairs = {(int((T0_MS + t_) // width_ms), int(x), int(y))
             for t_, x, y in zip(ts, a, b)}
    assert t["dir.assign"]["n"] == len(ts)
    # one `dir.new` beside every `dir.assign`, a count and no time
    assert t["dir.new"]["count"] == t["dir.assign"]["count"]
    assert t["dir.new"]["total_s"] == 0.0
    assert t["dir.new"]["n"] == len(pairs)
    assert 0 < len(pairs) < len(ts)


def test_ckpt_delta_counts_the_rows_of_each_delta_batch(
        tmp_path, monkeypatch):
    built = []
    real = windows.WindowOperatorBase._build_delta_batch

    def counted(self, bin_ts):
        build = real(self, bin_ts)
        if build is None:
            return None

        batch = build()         # now, not on the flush path: a job
        built.append(batch.num_rows)    # that ends may never flush it
        return lambda: batch

    monkeypatch.setattr(
        windows.WindowOperatorBase, "_build_delta_batch", counted)
    t = run_query(tmp_path, WINDOWS["tumble"][0], seeded_rows(seed=7),
                  checkpoints=2)
    assert len(built) >= 2 and min(built) > 0
    assert t["ckpt.delta"]["count"] == len(built)
    assert t["ckpt.delta"]["n"] == sum(built)
    assert t["ckpt.delta"]["total_s"] == 0.0
    # booked inside the capture: the capture's phase is there to set it
    # against
    assert t["ckpt.capture"]["count"] >= len(built)
