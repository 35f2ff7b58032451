"""BENCHMARK.json against the contract's limits, and the rule that the
harness is driven by data: `run.py` names no cell, configuration, traffic
parameter or metric."""

import json
import os
import re

import pytest

from bench_helpers import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# the entries kept for the files no cell uses yet (the paced mode, the
# four-chip configuration) are held to the same limits
FILES = ["BENCHMARK.json", "tests/benchmark/data/future_cells.json"]


@pytest.fixture(scope="module", params=FILES)
def bench(request):
    with open(os.path.join(REPO, request.param)) as f:
        return json.load(f)


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_the_file_has_exactly_the_contracts_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    assert all(line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))


def test_every_name_and_unit_is_within_the_allowed_characters(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
        names.append(w["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert line(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))


def test_cells_configs_and_metrics_hang_together(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert 1 <= len(configs) <= 24 and 1 <= len(cells) <= 24
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    assert four <= max(len(cells) // 2, 1)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.25

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in bench["end_to_end"] + bench["per_layer"]:
        assert cells_of(m) <= set(cells), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        # each of its cells reports the end-to-end metric it should move
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
    for name in cells:
        mine = [m for m in bench["end_to_end"] if name in cells_of(m)]
        assert len(mine) >= 2          # setup_s and at least one other
        assert any(name in cells_of(m) for m in bench["per_layer"])


def test_everything_a_cell_names_is_a_file_found_by_that_name(bench):
    paths = tuple(p + "/" for p in bench["paths"])
    for c in bench["configs"]:
        assert c["file"].startswith(paths)
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert "guarantees" in cfg and "assumed" in cfg
        d = os.path.dirname(os.path.join(REPO, c["file"]))
        assert os.path.exists(os.path.join(d, cfg["sql"]))
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "reference", cfg["reference"] + ".py"))
        chips = {w["chips"] for w in bench["workloads"]
                 if w["config"] == c["name"]}
        assert chips == {cfg["chips"]}
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    for group, d in (("end_to_end", "end_to_end"),
                     ("per_layer", "layer_metrics")):
        for m in bench[group]:
            assert os.path.exists(os.path.join(
                REPO, "benchmark", d, m["name"] + ".py")), m["name"]
    for root, _dirs, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if "__pycache__" not in root:
                assert PATH.match(f), f


def test_run_py_names_no_cell_configuration_traffic_or_metric(bench):
    with open(os.path.join(REPO, "benchmark", "run.py")) as f:
        text = f.read()
    named = [c["name"] for c in bench["configs"]]
    named += [w["name"] for w in bench["workloads"]]
    named += [w["traffic"] for w in bench["workloads"]]
    named += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if m["name"] != "setup_s"]     # the window's start records it
    named += ["nominal_rate", "chunk_seconds", "batch_rows", "first_event",
              "q5", "q7", "nexmark", "mesh_devices"]
    for name in named:
        assert name not in text, name


def test_no_file_of_these_tests_reaches_twenty_tests():
    """Tier-1 hands test files to its workers largest first. The seed has
    a test that fails in a worker that ran `tests/test_obs.py` before
    `tests/test_device_obs.py` (20 tests); a larger file of these tests
    among the seed's first dozen changes who gets which (PERF.md section
    7). More tests here go into a new file."""
    import collections
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "tests/benchmark"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    files = collections.Counter(
        ln.split("::")[0] for ln in out.stdout.splitlines() if "::" in ln)
    assert len(files) >= 9, out.stdout[-2000:] + out.stderr[-2000:]
    assert {f: n for f, n in files.items() if n >= 20} == {}
