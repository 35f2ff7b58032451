"""CPU seconds of this process (all threads, the benchmark's producer
among them) per second of window: 1.0 is one saturated thread. Not a share
of a peak."""


def read(run):
    return (run.end["cpu_s"] - run.start["cpu_s"]) / run.window_s
