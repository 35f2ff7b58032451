"""`benchmark/run.py` with a reference found beside these tests: a
configuration whose `reference` names a module under `data/` gets that one
(`data/persons_sellers.py`, a stub over persons and auctions). Used by
test_bench_whole_stream.py through `--benchmark-file data/stub_cells.json`;
never by the benchmark, whose references live under `benchmark/reference/`.

    stub_cell.py --workload persons-sellers.catchup --benchmark-file ... \\
        --seed 7 --seconds 12 --trace 0 --rehearsal
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "benchmark"))

import run  # noqa: E402

sound_load = run.load_module


def load_module(kind, name):
    path = os.path.join(HERE, "data", f"{name}.py")
    if kind != "reference" or not os.path.exists(path):
        return sound_load(kind, name)
    spec = importlib.util.spec_from_file_location(f"stub_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


run.load_module = load_module
run.entry(sys.argv[1:])
