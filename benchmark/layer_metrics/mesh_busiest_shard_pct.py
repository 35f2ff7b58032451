"""Of the real rows the mesh exchange shipped inside the window, the share
owned by the busiest destination shard of their step (MESH_STATS
rows_busiest over rows_sent): 100 / shards where keys spread evenly, near
100 where one shard owns nearly every row. None where the program does
not count it."""

import mesh_counts


def read(run):
    sent = mesh_counts.delta(run, "rows_sent")
    busiest = mesh_counts.delta(run, "rows_busiest")
    if not sent or busiest is None:
        return None
    return 100.0 * busiest / sent
