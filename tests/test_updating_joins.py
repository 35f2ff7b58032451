"""Updating (non-windowed) joins with retractions — mirrors the reference's
updating_{inner,left,right,full}_join.sql queries."""

import asyncio
import json

import pytest

from arroyo_tpu.engine import Engine
from arroyo_tpu.sql import plan_query
from arroyo_tpu.sql.lexer import SqlError

IMPULSE = """
CREATE TABLE impulse WITH (
  connector = 'impulse', event_rate = '1000000',
  message_count = '40', start_time = '0'
);
CREATE VIEW impulse_odd AS (
  SELECT counter FROM impulse WHERE counter % 2 == 1
);
"""


def run_to_debezium(sql, tmp_path, parallelism=1):
    out = tmp_path / "out.json"
    plan = plan_query(
        sql.replace("$out", str(out)), parallelism=parallelism
    )

    async def go():
        eng = Engine(plan.graph).start()
        await eng.join(60)

    asyncio.run(go())
    state = {}
    ops = {"c": 0, "d": 0}
    with open(out) as f:
        for line in f:
            if not line.strip():
                continue
            env = json.loads(line)
            ops[env["op"]] = ops.get(env["op"], 0) + 1
            row = env["before"] if env["op"] == "d" else env["after"]
            k = json.dumps(row, sort_keys=True)
            if env["op"] == "d":
                state[k] = state.get(k, 0) - 1
            else:
                state[k] = state.get(k, 0) + 1
    final = [json.loads(k) for k, v in state.items() if v > 0 for _ in range(v)]
    return final, ops


def test_updating_inner_join(tmp_path):
    """reference updating_inner_join.sql: impulse ⋈ odd-only view."""
    final, ops = run_to_debezium(
        IMPULSE
        + """
        CREATE TABLE output (left_count BIGINT, right_count BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.counter, B.counter
        FROM impulse A
        JOIN impulse_odd B ON A.counter = B.counter;
        """,
        tmp_path,
    )
    got = sorted(r["left_count"] for r in final)
    assert got == list(range(1, 40, 2))  # odds only
    assert all(r["left_count"] == r["right_count"] for r in final)


def test_updating_left_join(tmp_path):
    # separate sources: the left table lands instantly, the right side is
    # realtime-paced, so the left side's null-padded rows DETERMINISTICALLY
    # precede their matches (a shared fanned-out source makes side arrival
    # order scheduler-dependent, and either order is legal join semantics)
    final, ops = run_to_debezium(
        """
        CREATE TABLE lsrc WITH (
          connector = 'impulse', event_rate = '100000', realtime = 'true',
          message_count = '40'
        );
        CREATE TABLE rsrc WITH (
          connector = 'impulse', event_rate = '150', realtime = 'true',
          message_count = '40'
        );
        CREATE TABLE output (l BIGINT, r BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.counter, B.counter
        FROM lsrc A
        LEFT JOIN (
          SELECT counter FROM rsrc WHERE counter % 2 == 1
        ) B ON A.counter = B.counter;
        """,
        tmp_path,
    )
    # every left row survives; evens keep a null right side
    assert sorted(r["l"] for r in final) == list(range(40))
    nulls = [r for r in final if r["r"] is None]
    assert sorted(r["l"] for r in nulls) == list(range(0, 40, 2))
    # the odd rows' null-padded versions were retracted as matches arrived
    assert ops["d"] >= 1


def test_updating_right_join(tmp_path):
    final, _ = run_to_debezium(
        IMPULSE
        + """
        CREATE TABLE output (l BIGINT, r BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.counter, B.counter
        FROM impulse_odd A
        RIGHT JOIN impulse B ON A.counter = B.counter;
        """,
        tmp_path,
    )
    assert sorted(r["r"] for r in final) == list(range(40))
    assert sorted(r["r"] for r in final if r["l"] is None) == list(
        range(0, 40, 2)
    )


def test_updating_full_join_with_updating_inputs(tmp_path):
    """reference updating_full_join.sql shape: full join of two updating
    aggregates (retraction-consuming join)."""
    from arroyo_tpu.config import update

    with update(pipeline={"update_aggregate_flush_interval": 0.05}):
        final, ops = run_to_debezium(
            """
            CREATE TABLE impulse WITH (
              connector = 'impulse', event_rate = '8000', realtime = 'true',
              message_count = '3000', start_time = '0'
            );
            CREATE TABLE output (k BIGINT, lc BIGINT, rc BIGINT) WITH (
              connector = 'single_file', path = '$out',
              format = 'debezium_json', type = 'sink'
            );
            INSERT INTO output
            SELECT coalesce(A.k, B.k), A.cnt, B.cnt FROM (
              SELECT counter % 4 as k, count(*) as cnt FROM impulse
              WHERE counter % 2 = 0 GROUP BY 1
            ) A
            FULL JOIN (
              SELECT counter % 4 as k, count(*) as cnt FROM impulse
              WHERE counter % 4 = 1 GROUP BY 1
            ) B ON A.k = B.k;
            """,
            tmp_path,
        )
    # exact final multiset: every intermediate count was retracted
    assert len(final) == 3, final
    got = {r["k"]: (r["lc"], r["rc"]) for r in final}
    # evens: k=0 and k=2 get 750 each; k%4==1 side: k=1 gets 750
    assert got == {0: (750, None), 2: (750, None), 1: (None, 750)}
    assert ops["d"] > 0  # incremental counts retracted along the way


def test_updating_join_requires_debezium_sink(tmp_path):
    with pytest.raises(SqlError, match="debezium"):
        plan_query(
            IMPULSE
            + f"""
            CREATE TABLE output (l BIGINT, r BIGINT) WITH (
              connector = 'single_file', path = '{tmp_path}/x.json',
              format = 'json', type = 'sink'
            );
            INSERT INTO output
            SELECT A.counter, B.counter FROM impulse A
            JOIN impulse_odd B ON A.counter = B.counter;
            """
        )


def test_updating_join_checkpoint_restore(tmp_path):
    sql = """
    CREATE TABLE impulse WITH (
      connector = 'impulse', event_rate = '15000', realtime = 'true',
      message_count = '4000', start_time = '0'
    );
    CREATE VIEW odd AS (SELECT counter FROM impulse WHERE counter % 2 == 1);
    CREATE TABLE output (l BIGINT, r BIGINT) WITH (
      connector = 'single_file', path = '$OUT',
      format = 'debezium_json', type = 'sink'
    );
    INSERT INTO output
    SELECT A.counter, B.counter FROM impulse A
    LEFT JOIN odd B ON A.counter = B.counter;
    """.replace("$OUT", str(tmp_path / "out.json"))
    url = str(tmp_path / "ck")

    async def phase1():
        plan = plan_query(sql, parallelism=2)
        eng = Engine(plan.graph, job_id="uj", storage_url=url).start()
        await asyncio.sleep(0.12)
        await eng.checkpoint_and_wait(then_stop=True)
        await eng.join(60)

    asyncio.run(phase1())

    async def phase2():
        plan = plan_query(sql, parallelism=2)
        eng = Engine(plan.graph, job_id="uj", storage_url=url).start()
        await eng.join(60)

    asyncio.run(phase2())

    state = {}
    with open(tmp_path / "out.json") as f:
        for line in f:
            if line.strip():
                env = json.loads(line)
                row = env["before"] if env["op"] == "d" else env["after"]
                k = json.dumps(row, sort_keys=True)
                state[k] = state.get(k, 0) + (-1 if env["op"] == "d" else 1)
    final = [json.loads(k) for k, v in state.items() if v > 0 for _ in range(v)]
    assert sorted(r["l"] for r in final) == list(range(4000))
    assert sorted(r["l"] for r in final if r["r"] is None) == list(
        range(0, 4000, 2)
    )


def _count_bulk_hits(monkeypatch):
    """Patch UpdatingJoinOperator._inner_bulk to count engagements so a
    silent fallback to the per-row path can't pass the bulk tests
    vacuously."""
    import arroyo_tpu.operators.updating_join as uj

    hits = {"bulk": 0, "slow": 0}
    orig = uj.UpdatingJoinOperator._inner_bulk

    def spy(self, batch, side, ts):
        r = orig(self, batch, side, ts)
        hits["bulk" if r is not None else "slow"] += 1
        return r

    monkeypatch.setattr(uj.UpdatingJoinOperator, "_inner_bulk", spy)
    return hits


def test_updating_inner_join_bulk_probe_path(tmp_path, monkeypatch):
    """The device-probe bulk path (inner, append-only batches) must
    produce the same net debezium state as the per-row path (VERDICT r3
    item 4: updating join inner core rides the merge-join probe)."""
    from arroyo_tpu.config import update

    hits = _count_bulk_hits(monkeypatch)
    sql = (
        IMPULSE
        + """
        CREATE TABLE output (left_count BIGINT, right_count BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.counter, B.counter
        FROM impulse A
        JOIN impulse_odd B ON A.counter = B.counter;
        """
    )
    with update(tpu={"enabled": True, "require_accelerator": False,
                     "device_join_min_rows": 0}):
        final, ops = run_to_debezium(sql, tmp_path)
    got = sorted(r["left_count"] for r in final)
    assert got == list(range(1, 40, 2))
    assert all(r["left_count"] == r["right_count"] for r in final)
    assert hits["bulk"] > 0 and hits["slow"] == 0


def test_updating_join_bulk_falls_back_on_retracts(tmp_path, monkeypatch):
    """A retract-carrying input (updating aggregate upstream, so batches
    carry __updating_meta) must take the per-row path and still produce
    the correct net state with the force flag on."""
    from arroyo_tpu.config import update

    hits = _count_bulk_hits(monkeypatch)
    sql = (
        IMPULSE
        + """
        CREATE VIEW agg AS (
          SELECT counter % 4 AS g, count(*) AS c FROM impulse GROUP BY 1
        );
        CREATE TABLE output (g BIGINT, c BIGINT, counter BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.g, A.c, B.counter
        FROM agg A
        JOIN impulse B ON A.g = B.counter;
        """
    )
    baseline, _ = run_to_debezium(sql, tmp_path / "base")
    with update(tpu={"enabled": True, "require_accelerator": False,
                     "device_join_min_rows": 0}):
        final, _ = run_to_debezium(sql, tmp_path / "bulk")
    key = lambda rows: sorted(json.dumps(r, sort_keys=True) for r in rows)
    assert key(final) == key(baseline)
    assert len(final) > 0


def test_updating_inner_join_bulk_probe_strings(tmp_path, monkeypatch):
    """Bulk path with string join keys (joint-dictionary probe) against
    larger per-key fan-out; net state must match the per-row run."""
    from arroyo_tpu.config import update

    hits = _count_bulk_hits(monkeypatch)
    sql = (
        IMPULSE
        + """
        CREATE VIEW lab AS (
          SELECT counter, concat('k', counter % 5) AS tag FROM impulse
        );
        CREATE TABLE output (lc BIGINT, rc BIGINT) WITH (
          connector = 'single_file', path = '$out',
          format = 'debezium_json', type = 'sink'
        );
        INSERT INTO output
        SELECT A.counter, B.counter
        FROM lab A
        JOIN lab B ON A.tag = B.tag;
        """
    )
    baseline, _ = run_to_debezium(sql, tmp_path / "base")
    with update(tpu={"enabled": True, "require_accelerator": False,
                     "device_join_min_rows": 0}):
        bulk, _ = run_to_debezium(sql, tmp_path / "bulk")
    key = lambda rows: sorted(json.dumps(r, sort_keys=True) for r in rows)
    assert key(bulk) == key(baseline)
    assert len(baseline) == 40 * 8  # 5 tags x 8 rows each -> 8x8 pairs x 5
    assert hits["bulk"] > 0
