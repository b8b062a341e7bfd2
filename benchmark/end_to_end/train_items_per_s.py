"""Training examples a second, in the unit the reference application logs
(the cell's file states the item), over the wall time of the timed
``train()`` call, which ends when the last loss is fetched and every
update is in the tables."""


def read(run):
    w = run.window
    return w["items"] / w["wall_s"] if "items" in w else None
