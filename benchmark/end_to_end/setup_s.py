"""Process start to the start of the timed window: imports, the native
build, planning, the job's start, the warm-up feed and every compile."""


def read(run):
    return run.setup_s
