"""Real rows per exchange step inside the window (MESH_STATS rows_sent
over dispatches): how many rows one packed host-to-device transfer and
one sharded program carry. None on a run without a mesh."""

import mesh_counts


def read(run):
    sent = mesh_counts.delta(run, "rows_sent")
    steps = mesh_counts.delta(run, "dispatches")
    if not steps or sent is None:
        return None
    return sent / steps
