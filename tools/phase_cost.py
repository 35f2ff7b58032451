#!/usr/bin/env python
"""What one `obs.timeline.phase` costs on this machine's CPU, in ns per
enter and exit: before jax is imported, with jax and no profiler session
(the state of every `--trace 0` run), and inside a profiler session with
the options the benchmark's tracer uses (host tracer on, python tracer
off). Since ISSUE 38 a phase reads the thread's CPU clock beside the wall
clock, at every edge where a reading is cheap and on a grid where it is not
(`timeline.cpu_every_s`, printed): the two clocks alone are printed first,
then a `note` with and without `cpu_s`, and a `select` of the loop's timed
selector that may block (one ready handle, so it never does) against the
plain selector's.
Multiply by the entries a window books (the sum of `count` over
`timeline.totals`) for the ledger's share of the window's host time.

    JAX_PLATFORMS=cpu python tools/phase_cost.py
"""

import os
import selectors
import socket
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


def notes_with_cpu() -> None:
    for _ in range(N):
        t0 = time.perf_counter()
        c0 = timeline.thread_cpu(t0)
        t1 = time.perf_counter()
        timeline.note("x.y", t1 - t0, cpu_s=timeline.thread_cpu(t1) - c0)


def wall_clock() -> None:
    for _ in range(N):
        time.perf_counter()


def cpu_clock() -> None:
    for _ in range(N):
        time.thread_time()


def selects(selector):
    """`N` selects with a timeout and one handle always ready: what each
    turn of a loop with nothing else to run costs."""
    a, b = socket.socketpair()
    b.send(b"x")
    selector.register(a, selectors.EVENT_READ)

    def body() -> None:
        for _ in range(N):
            selector.select(0.01)

    try:
        yield body
    finally:
        selector.close()
        a.close()
        b.close()


def main() -> None:
    bench("perf_counter", wall_clock)
    bench("thread_time", cpu_clock)
    bench("phase, jax not imported", leaves)
    bench("note, jax not imported", notes)
    bench("note with cpu_s (its clock readings included)", notes_with_cpu)
    print(f"the thread CPU clock is read every {timeline.cpu_every_s() * 1e3:.2f}"
          " ms of wall at most (0 = at every edge)")
    for label, selector in (
            ("select, plain selector", selectors.DefaultSelector()),
            ("select, timed selector (books loop.idle)",
             timeline.TimedSelector())):
        for body in selects(selector):
            bench(label, body)
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
