"""Table rows added plus rows fetched a second, counted at the client,
each operation ended by its reply (host plane) or ``block_until_ready``
(device plane), over the wall time of the window."""


def read(run):
    w = run.window
    return w["rows"] / w["wall_s"] if "rows" in w else None
