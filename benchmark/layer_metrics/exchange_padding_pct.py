"""Of the rows the mesh exchange shipped inside the window, the share that
was padding (MESH_STATS rows_padded over rows_sent + rows_padded)."""


def read(run):
    sent = run.end["mesh"]["rows_sent"] - run.start["mesh"]["rows_sent"]
    padded = run.end["mesh"]["rows_padded"] - run.start["mesh"]["rows_padded"]
    if sent + padded == 0:
        return None
    return 100.0 * padded / (sent + padded)
