"""The four readings that split `engine_unnamed_pct` from inside the
program (ISSUE 38: `loop_idle_pct`, `engine_offcore_pct`,
`host_leaf_offcore_pct`, `engine_unnamed_cpu_pct`; readers under
`benchmark/layer_metrics/`). ISSUE 40 appended their entries to
BENCHMARK.json, word for word as `data/owed_entries.json` held them; ONE
traced rehearsal of `q5.catchup` on XLA's CPU backend reads them from
BENCHMARK.json itself, with the ledger printed behind the line
(`ledger_dump.py`). A CPU run gives host times, never a device number; the
tests hold them to what must be true anywhere. The rehearsal runs 12 s:
the ledger answers for the quarter-second buckets that start inside the
window, up to half a second more or less than it, which is 12 % of a 4 s
window and more than the tenth `test_every_item_...` allows."""

import importlib
import json
import os
import types

import pytest

from bench_helpers import HERE, REPO, run_cell

NAMES = ["loop_idle_pct", "engine_offcore_pct", "host_leaf_offcore_pct",
         "engine_unnamed_cpu_pct"]
with open(os.path.join(HERE, "data", "owed_entries.json")) as _f:
    OWED = json.load(_f)
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.fixture(scope="module")
def traced():
    """(the result line, the ledger behind it) of one traced rehearsal."""
    out = run_cell("--workload", "q5.catchup", "--seed", str(2**31 + 38),
                   "--seconds", "12", "--trace", "1", "--rehearsal",
                   script=os.path.join(HERE, "ledger_dump.py"))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(next(ln for ln in lines if ln.startswith('{"correct"')))
    ledger = json.loads(lines[-1].removeprefix("LEDGER "))
    assert line["correct"] is True and line["rehearsal"] is True
    return line, ledger


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}")


@pytest.fixture
def by_hand(monkeypatch):
    """A run as a reader sees it, over a ledger booked by hand."""
    import ledger_window

    monkeypatch.setattr(ledger_window, "totals", lambda run: run.hand_booked)
    return lambda totals: types.SimpleNamespace(
        window_s=10.0, hand_booked=totals)


def cell(total, cpu=0.0, self_s=None, self_cpu=None):
    return {"count": 1, "total_s": total, "self_s": total if self_s is None
            else self_s, "max_s": total, "n": 0, "padded": 0, "cpu_s": cpu,
            "self_cpu_s": cpu if self_cpu is None else self_cpu}


def test_the_owed_entries_are_the_issues_word_for_word():
    """Entered (ISSUE 40): the four stand behind the twenty-two the
    benchmark held, before `rank_close_ms`, each as it was owed; what a
    later PR appends comes after them. `owed_entries.json` still
    lists them, for `tests/test_timeline.py` reads their names there, and
    names what is still owed: two of PR 39's, with no reader yet."""
    entered = BENCH["per_layer"][22:26]
    assert [m["name"] for m in entered] == NAMES
    cells = ["q5.catchup", "q7.catchup-25k", "q5-mesh4.catchup",
             "top5-hop60.catchup"]
    assert [w["name"] for w in BENCH["workloads"]][:4] == cells
    # as owed, word for word; a later cell is appended to their cells
    assert [{**m, "workloads": m["workloads"][:4]} for m in entered] == [
        {"name": name, "unit": "%", "better": "lower",
         "source": "program_span", "layer": "entry + control",
         "moves": "events_per_s", "workloads": cells}
        for name in NAMES] == OWED["per_layer"]
    assert len(OWED["still_owed"]) == 2 and all(
        "reader" in e["needs"] for e in OWED["still_owed"])


@pytest.mark.parametrize("name", NAMES)
def test_each_reading_is_in_the_traced_line_with_its_unit(traced, name):
    line, _ledger = traced
    got = line["metrics"][name]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0


def test_the_line_holds_what_the_cell_held_and_the_four(traced):
    line, _ledger = traced
    held = {m["name"] for m in BENCH["per_layer"]
            if "q5.catchup" in m.get("workloads", ["q5.catchup"])
            and m["source"] != "device_trace"}
    got = set(line["metrics"])
    assert set(NAMES) <= got and got - set(NAMES) <= held
    assert len(got - set(NAMES)) >= 10      # a memory peak a CPU has none of


def test_idle_and_running_time_add_up_to_the_window(traced):
    _line, ledger = traced
    t = ledger["window"]
    assert t["loop.run"]["count"] > 5 and "loop.idle" in ledger["whole_run"]
    # a catch-up window may hold no `select` that blocked at all
    idle_s = t.get("loop.idle", {"total_s": 0.0})["total_s"]
    assert idle_s + t["loop.run"]["total_s"] == pytest.approx(
        ledger["window_s"], abs=0.6)
    # the loop thread's CPU is inside the process's (all threads)
    assert 0 < t["loop.run"]["cpu_s"] <= ledger["cpu_s"] + 0.3


def test_no_phase_has_more_cpu_than_wall(traced):
    """Entry by entry where the thread CPU clock is cheap enough to be read
    at every edge (this sandbox); where it is read on a grid (the chip's
    host) only sums over many phases mean anything."""
    _line, ledger = traced
    t = ledger["whole_run"]
    if ledger["cpu_every_s"] == 0:
        for name, v in t.items():
            assert v["cpu_s"] <= 1.01 * v["total_s"] + 2e-3, (name, v)
            assert v["self_cpu_s"] <= 1.01 * v["self_s"] + 2e-3, (name, v)
    named = [v for p, v in t.items() if not p.startswith("loop.")
             and p != "queue.wait"]
    assert sum(v["self_cpu_s"] for v in named) <= 1.01 * sum(
        v["self_s"] for v in named)
    # and inside the window the loop thread's true total holds them all
    # (a wait's CPU is the other tasks', who book their own)
    from arroyo_tpu.obs import timeline

    w = ledger["window"]
    assert sum(v["self_cpu_s"] for p, v in w.items()
               if p != "loop.run" and p not in timeline.WAITS) <= (
        w["loop.run"]["cpu_s"] + 0.05 * w["loop.run"]["total_s"])


def test_what_is_unnamed_is_idle_off_core_or_unnamed_cpu(traced):
    """Nothing is booked twice: `engine_unnamed_pct`, which skips the
    enclosures and the waits, is the three new shares of the window that
    lie outside named phases."""
    from arroyo_tpu.obs import timeline

    line, ledger = traced
    t, window_s = ledger["window"], ledger["window_s"]
    skip = set(timeline.ENCLOSING) | set(timeline.WAITS)
    named_offcore = sum(v["self_s"] - v["self_cpu_s"]
                        for p, v in t.items() if p not in skip)
    value = {n: line["metrics"][n]["value"] for n in line["metrics"]}
    parts = (value["loop_idle_pct"] + value["engine_unnamed_cpu_pct"]
             + value["engine_offcore_pct"] - 100 * named_offcore / window_s)
    assert value["engine_unnamed_pct"] == pytest.approx(
        parts, abs=100 * 0.6 / window_s)


def test_every_item_of_a_task_is_inside_its_enclosure(traced):
    """The source's batches are under `process` with its task id, an
    operator task's too, each watermark signal under `watermark`; what the
    leaves name lies inside them."""
    _line, ledger = traced
    by_task = {task: t for task, t in ledger["tasks"].items() if t}
    sources = [t for t in by_task.values()
               if "process" in t and "watermark" not in t]
    assert len(sources) == 1        # q5's one source task: no input item
    # by each batch's END: what is in flight at an edge falls either way
    assert sources[0]["process"]["n"] == pytest.approx(
        ledger["events"], rel=0.1)
    operators = [t for t in by_task.values() if "watermark" in t]
    assert operators
    for t in by_task.values():
        enclosed = t["process"]["total_s"] + t.get(
            "watermark", {"total_s": 0.0})["total_s"]
        inside = sum(v["self_s"] for p, v in t.items()
                     if p.split(".")[0] in ("win", "dir", "agg", "audit"))
        assert inside <= enclosed + 1e-3


def test_the_device_waits_are_phases_the_cells_book(traced):
    """Every name of `timeline.DEVICE_WAITS` is a phase the program books
    by that name, and this rehearsal books all but the device probe's (its
    join takes the host path: `rehearsal_settings`)."""
    import re
    import subprocess

    from arroyo_tpu.obs import timeline

    _line, ledger = traced
    assert not set(timeline.DEVICE_WAITS) & (
        set(timeline.ENCLOSING) | set(timeline.WAITS))
    booked = set(ledger["whole_run"])
    for name in timeline.DEVICE_WAITS:
        assert name in booked or name.startswith("join.probe."), name
        sites = subprocess.run(
            ["grep", "-rlE", rf'(phase|note)\(\s*"{re.escape(name)}"',
             os.path.join(REPO, "arroyo_tpu")],
            capture_output=True, text=True).stdout.split()
        assert sites, name


def test_the_readers_arithmetic_on_a_ledger_booked_by_hand(by_hand):
    run = by_hand({
        "loop.idle": cell(2.0), "loop.run": cell(8.0, cpu=6.0),
        "process": cell(5.0, cpu=4.0, self_s=1.0, self_cpu=0.5),
        "queue.wait": cell(3.0, cpu=2.5),
        "win.keys": cell(2.0, cpu=1.5), "agg.pack": cell(1.0, cpu=1.0),
        "agg.read": cell(1.0, cpu=0.1)})
    assert reader("loop_idle_pct").read(run) == pytest.approx(20.0)
    assert reader("engine_offcore_pct").read(run) == pytest.approx(20.0)
    # win.keys and agg.pack: 3 s of self, 2.5 of it on a core; agg.read
    # waits for the device and the enclosure names nothing
    assert reader("host_leaf_offcore_pct").read(run) == pytest.approx(
        100 * 0.5 / 3.0)
    # 6 s of the loop thread's CPU less 1.5 + 1.0 + 0.1 in named phases
    assert reader("engine_unnamed_cpu_pct").read(run) == pytest.approx(34.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_loops_clock_gives_none(by_hand, name):
    parent = {"process": cell(5.0), "win.keys": cell(2.0)}
    for v in parent.values():
        del v["cpu_s"], v["self_cpu_s"]     # the parent's ledger has none
    assert reader(name).read(by_hand(parent)) is None
    assert reader(name).read(by_hand(None)) is None
