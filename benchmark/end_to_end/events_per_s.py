"""Events the pipeline accepted from the generator inside the window,
over the window's measured length. Completeness is `correct`'s business."""


def read(run):
    return run.events_in_window / run.window_s
