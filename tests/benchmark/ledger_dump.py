"""`benchmark/run.py` with the program's phase ledger printed behind its
result line: `LEDGER {json}` holds `timeline.totals` over the window (all
tasks, and one entry per task of the job), over the whole run, and the
window's length. Used by test_bench_loop_clock.py, and by a builder who
wants PERF.md section 5's tables from a chip run; never by the benchmark.

    ledger_dump.py --workload q5.catchup --seed 7 --seconds 45 --trace 1
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "benchmark"))

import check  # noqa: E402
import run as bench_run  # noqa: E402

seen = {}
sound_judge = check.judge


def judge(run, *args, **kwargs):
    seen["run"] = run
    return sound_judge(run, *args, **kwargs)


def main(argv) -> int:
    check.judge = judge
    code = bench_run.main(argv)
    from arroyo_tpu.obs import timeline

    run = seen["run"]
    edges = (run.start["t_ns"] / 1e3, run.end["t_ns"] / 1e3)
    print("LEDGER " + json.dumps({
        "window_s": run.window_s, "events": run.events_in_window,
        "closes": len(run.closes), "starved_polls": run.feed.starved,
        "cpu_s": run.end["cpu_s"] - run.start["cpu_s"],
        # 0 = the thread CPU clock is read at every edge of a phase
        "cpu_every_s": getattr(timeline, "cpu_every_s", lambda: None)(),
        "window": timeline.totals(*edges),
        "tasks": {task: timeline.totals(*edges, task=task)
                  for task in run.flow},
        "whole_run": timeline.totals()}), flush=True)
    return code


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)
