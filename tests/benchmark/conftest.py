"""Tests of the benchmark's own files (`benchmark/`): they import its
modules by the names `run.py` itself uses."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
