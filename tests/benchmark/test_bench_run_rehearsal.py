"""`benchmark/run.py` end to end, as the driver runs it: a process of its
own, one result line. Each rehearsal feeds more than 20,000 seeded events
through the engine's device tier (on XLA's CPU backend) and holds the
complete result of every closed window against the plain reference.

All of the benchmark's tests that start such a process are in this one
file, so that one test worker runs them one after another: tier-1 has
tests that stall when the machine is busy (heartbeats under chaos)."""

import json
import os
import shutil

import pytest

from bench_helpers import HERE, REPO, listed, rehearse, run_cell

# entries for the files that no cell of BENCHMARK.json uses yet (the paced
# mode, the four-chip configuration and q7: PERF.md sections 4 and 7)
FUTURE = ("--benchmark-file", os.path.join(HERE, "data", "future_cells.json"))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = {w["name"] for w in BENCH["workloads"]}


def entries(cell):
    """Where the cell's entries are: BENCHMARK.json itself, or FUTURE."""
    return () if cell in CELLS else FUTURE


def test_without_a_tpu_and_without_rehearsal_it_exits_nonzero():
    out = run_cell("--workload", "q5.catchup", "--seed", "1", "--seconds",
                   "5", "--trace", "0")
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU (platform=cpu)" in out.stderr


def test_an_unknown_cell_exits_nonzero():
    out = run_cell("--workload", "nope", "--seed", "1", "--seconds", "5",
                   "--trace", "0", "--rehearsal")
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_in_a_directory_with_only_the_benchmark_it_exits_nonzero(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cell("--workload", "q5.catchup", "--seed", "1", "--seconds",
                   "5", "--trace", "0", "--rehearsal",
                   script=str(tmp_path / "benchmark" / "run.py"),
                   cwd=str(tmp_path))
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_a_catchup_cell_end_to_end_with_a_large_seed():
    line, said = rehearse("q5.catchup", seed=2**31 + 12345)
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 10
    assert {"setup_s", "events_per_s"} <= set(
        line["metrics"]) == listed(BENCH, "q5.catchup", "end_to_end")
    assert line["metrics"]["events_per_s"]["unit"] == "events/s"
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1 and "kind" in line["device"]
    assert "memory_peak_bytes" in line["device"]
    compared = next(s for s in said if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    assert any("cadence: checkpoints_in_window=" in s for s in said)
    booked = [s for s in said if "conservation:" in s]
    assert len(booked) == 2 and all(
        "off by 0 (limit 0)" in s for s in booked)


def test_the_other_query_end_to_end_traced():
    line, _said = rehearse("q7.catchup", *entries("q7.catchup"), seed=8,
                           trace=1)
    assert line["correct"] is True and line["failed"] == 0
    # a CPU run gives counts, never a device number
    assert set(line["metrics"]) == {
        "host_cpu_cores", "dispatches_per_mevent",
        "compiles_in_window.catchup"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_paced_mode_end_to_end():
    line, said = rehearse("q5.steady", *FUTURE, seed=9, seconds=8)
    assert line["correct"] is True and line["attempted"] == 4
    assert set(line["metrics"]) == {"setup_s", "result_delay_p50_ms"}
    assert line["metrics"]["result_delay_p50_ms"]["value"] > 0
    assert any("late limit 2000 ms" in s for s in said)


def test_the_paced_modes_layer_metrics():
    line, _said = rehearse("q5.steady", *FUTURE, seed=10, seconds=8, trace=1)
    assert line["correct"] is True
    assert {"generator_late_p95_ms", "compiles_in_window.steady",
            "result_delay_p90_ms.layer"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window.steady"]["value"] >= 0


def test_the_mesh_configuration_end_to_end():
    """The four-chip configuration on four of the test environment's
    virtual CPU devices: sharded state, the device exchange and its
    padding counter."""
    line, _said = rehearse("q5-mesh4.catchup", *FUTURE, seed=21, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] >= 4
    pad = line["metrics"]["exchange_padding_pct"]
    assert pad["unit"] == "%" and 0 <= pad["value"] < 100
    assert line["metrics"]["dispatches_per_mevent"]["value"] > 0


# The comparison that decides `correct`, shown to fail: a control run breaks
# a guarantee the configurations state (one bid drawn from the seed is lost
# or repeated at the source; barriers go out at a quarter of the stated
# cadence), and a run whose timed path is broken underneath miscounts. Each
# goes through the whole harness and must come out not correct.


@pytest.mark.parametrize("cell,fault", [
    ("q5.catchup", "drop"), ("q5.catchup", "dup"),
    ("q7.catchup", "drop"), ("q7.catchup", "dup")])
def test_a_lost_or_repeated_bid_comes_out_not_correct(cell, fault):
    line, said = rehearse(cell, *entries(cell), "--control", fault, seed=14)
    assert line["correct"] is False
    assert any(s.split("] ")[1].startswith(f"control: {fault} event")
               for s in said)
    booked = [s for s in said if "conservation:" in s]
    assert booked and not any("off by 0 " in s for s in booked[:1])


def test_slow_barriers_come_out_not_correct():
    line, said = rehearse("q5.catchup", "--control", "cadence", seed=14,
                          seconds=10)
    assert line["correct"] is False
    compared = next(s for s in said if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    slow = next(s for s in said if "cadence:" in s)
    assert "(at least 2)" in slow and "(at most 4:" in slow
    assert all("off by 0 " in s for s in said if "conservation:" in s)


def test_the_same_seed_without_the_fault_is_correct():
    line, _said = rehearse("q5.catchup", seed=14, seconds=6)
    assert line["correct"] is True


def test_an_answer_altered_where_it_is_produced_comes_out_not_correct():
    line, said = rehearse("answer", "--workload", "q5.catchup", seed=15,
                          script=os.path.join(HERE, "broken_path.py"))
    assert line["correct"] is False
    assert not any("wrong=0 " in s for s in said if "compared:" in s)


def test_a_row_left_out_inside_an_operator_comes_out_not_correct():
    # every answer is the sound one: the operator's own books show it
    line, said = rehearse("rows", "--workload", "q7.catchup",
                          *entries("q7.catchup"), seed=15,
                          script=os.path.join(HERE, "broken_path.py"))
    assert line["correct"] is False
    compared = next(s for s in said if "compared:" in s)
    assert "wrong=0 (limit 0) missing=0 (limit 0)" in compared
    assert any("off by (0, -1) (limit 0)" in s for s in said)
