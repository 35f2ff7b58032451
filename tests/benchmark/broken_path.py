"""Drives a rehearsal run of the harness with the timed path broken
underneath. `correct` must come out false. Used by
test_bench_run_rehearsal.py; never by the benchmark.

    broken_path.py answer ...   the counts the accumulator hands to its
                                first emission are one too high (an answer
                                altered where it is produced)
    broken_path.py rows ...     each tumbling window operator leaves out
                                one row of its 20th batch (a part of the
                                batch left out: no answer need change)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "benchmark"))

import run  # noqa: E402

from arroyo_tpu.operators.windows import TumblingWindowOperator  # noqa: E402
from arroyo_tpu.ops.aggregates import Accumulator  # noqa: E402

kind = sys.argv[1]
sound_gather = Accumulator.gather
sound_batch = TumblingWindowOperator.process_batch
seen = []
batches = {}


def gather(self, slots, materialize=True):
    outs = sound_gather(self, slots, materialize)
    if seen or not len(outs) or not len(slots):
        return outs
    seen.append(True)
    return [outs[0] + 1] + list(outs[1:])


async def process_batch(self, batch, ctx, collector, input_index=0):
    batches[id(self)] = batches.get(id(self), 0) + 1
    if batches[id(self)] == 20 and batch.num_rows > 1:
        batch = batch.slice(1)
    return await sound_batch(self, batch, ctx, collector, input_index)


if kind == "answer":
    Accumulator.gather = gather
elif kind == "rows":
    TumblingWindowOperator.process_batch = process_batch
else:
    raise SystemExit(f"broken_path.py: {kind!r}")
run.entry(sys.argv[2:])
