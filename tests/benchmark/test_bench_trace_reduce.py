"""The reduction from a profiler trace to numbers, on a trace recorded on
a TPU v5e: a 5.3 s slice of `q5.catchup` (PR 23's first traced run; a 20 s
window, seed 103). In it the device ran one window close and 25 batch
updates inside one second and nothing in the other four."""

import os

import pytest

import roofline
import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "q5_catchup_slice.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE)


def test_the_slice_is_the_profilers_own_start_to_stop(summary):
    # the device's events span 0.99 s; the slice was 5.35 s
    assert summary.window_s == pytest.approx(5.3456, abs=1e-3)


def test_busy_is_the_union_of_the_devices_operation_intervals(summary):
    assert list(summary.busy_by_device) == ["/device:TPU:0"]
    assert summary.busy_s == pytest.approx(0.58104, abs=1e-4)
    idle_pct = 100 * (1 - summary.busy_s / summary.window_s)
    assert idle_pct == pytest.approx(89.13, abs=0.05)


def test_programs_are_found_by_their_module_names(summary):
    m = summary.modules
    assert m["agg.update"]["calls"] == 25
    assert m["join.phase1"]["calls"] == 1 and m["join.phase2"]["calls"] == 1
    assert m["join.phase2"]["seconds"] == pytest.approx(0.3550, abs=1e-3)
    # 24 calls on the 1,048,576-slot state at 1.14 ms, one on the
    # 4,096-slot state (the window max over 0.5M counts) at 36 ms
    assert m["agg.update"]["seconds"] == pytest.approx(0.06378, abs=1e-4)
    top = summary.breakdown()["device_ops"]
    assert [n for n, _ in top[:3]] == [
        "join.phase2", "join.phase1", "agg.update"]
    assert len(top) <= 10


def test_the_longest_gaps_are_the_engines_and_include_the_slices_edges(
        summary):
    gaps = summary.breakdown()["idle_gaps"]
    assert len(gaps) <= 10
    assert gaps[0][0] == "engine" and gaps[0][1] == pytest.approx(
        2.8816, abs=1e-3)         # from the slice's start to the first op
    assert gaps[1][1] == pytest.approx(1.4759, abs=1e-3)   # ... to its end
    assert any(name == "engine:np.asarray(jax.Array)" for name, _ in gaps)
    assert sum(s for _, s in gaps) <= summary.window_s - summary.busy_s


def test_union_merges_overlapping_and_touching_intervals():
    total, merged = trace_reduce.union_ns(
        [(0, 10), (5, 12), (12, 13), (20, 30), (22, 25)])
    assert total == 23 and merged == [(0, 13), (20, 30)]


def test_gaps_are_attributed_to_what_the_benchmark_saw():
    busy = [(0, 0), (100, 200), (1200, 1300), (2300, 2400), (3000, 3000)]
    marks = [(trace_reduce.WAIT_BEGIN, 210, 210),
             (trace_reduce.WAIT_END, 1190, 1190),
             (trace_reduce.SINK, 1310, 2290)]
    calls = [("np.asarray(jax.Array)", 2410, 2990)]
    gaps = trace_reduce.attribute_gaps(busy, marks, calls)
    assert gaps[:3] == [("bench.source.wait", 1e-6), ("bench.sink", 1e-6),
                        ("engine:np.asarray(jax.Array)", 6e-7)]
    assert gaps[3] == ("engine", 1e-7)


def test_a_trace_without_a_device_plane_is_an_error(tmp_path):
    with pytest.raises(Exception):
        trace_reduce.reduce(str(tmp_path / "missing.xplane.pb"))


def test_module_names_lose_their_fingerprint():
    assert trace_reduce.module_program("jit_update(928901)") == "agg.update"
    assert trace_reduce.module_program("jit_gather(17)") == "jit_gather"


def test_agg_update_bytes_and_the_roofline_share():
    # 16,384 rows, one int64 accumulator: 16 B read per row, and the
    # touched slot read and written: 32 B a row
    assert roofline.agg_update_bytes(16_384) == 16_384 * 32
    peaks = {"hbm_bytes_per_s": 819e9}
    # PR 23's reading: 1.144 ms a call -> 0.056 % of the memory roofline
    pct = roofline.roofline_pct(roofline.agg_update_bytes(16_384),
                                1.144e-3, peaks)
    assert pct == pytest.approx(0.0560, abs=1e-3)
