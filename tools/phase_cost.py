#!/usr/bin/env python
"""What one `obs.timeline.phase` costs on this machine's CPU, in ns per
enter and exit: before jax is imported, with jax and no profiler session
(the state of every `--trace 0` run), and inside a profiler session with
the options the benchmark's tracer uses (host tracer on, python tracer
off). Multiply by the entries a window books (the sum of `count` over
`timeline.totals`) for the ledger's share of the window's host time.

    JAX_PLATFORMS=cpu python tools/phase_cost.py
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from arroyo_tpu.obs import timeline  # noqa: E402

N = 200_000


def bench(label, body) -> None:
    best = min(_timed(body) for _ in range(5))
    print(f"{label}: {best / N * 1e9:.0f} ns")


def _timed(body) -> float:
    t0 = time.perf_counter()
    body()
    return time.perf_counter() - t0


def leaves() -> None:
    for _ in range(N):
        with timeline.phase("x.y", n=1):
            pass


def nested() -> None:
    with timeline.phase("process", task="1-0", annotate=False):
        leaves()


def notes() -> None:
    for _ in range(N):
        timeline.note("x.y", 1e-6)


def main() -> None:
    bench("phase, jax not imported", leaves)
    bench("note, jax not imported", notes)
    assert "jax" not in sys.modules
    import jax

    bench("phase, no profiler session", leaves)
    bench("phase inside an enclosing phase, no session", nested)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            bench("phase, profiler session on", leaves)
        finally:
            jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
