"""The pooled device-plane verbs' share of their memory roofline: the
least bytes the traced window's verbs must move through HBM, over the
trace's device-busy seconds times the chip's peak HBM bandwidth
(``harness/peaks.json``). ``row_plane_roofline``'s counterpart for a
server that pools; the verbs are bound by bytes.

``least_bytes`` counts on *distinct* rows and on *bags*, whatever program
does the work: a pooled fetch must read each distinct row once and write
one row a bag; a pooled apply must read one gradient row a bag and read
and write each distinct row of the table and of each further array of
updater state (``state`` arrays in all: 2 under AdaGrad, the row and its
history). Nothing else is counted: not the ids and maps, not the row a
position a program gathers before it sums, not the gradient spread to the
positions. So the share cannot pass 100 % unless the device was busy for
less time than the trace says. The busy seconds are all the device's (the
runner's gradient program among them: one pass over a row a bag). Nothing
to read where the runner's record has no ``pooled_verbs``. Layer: row ops
and kernels. Moves ``table_rows_per_s``."""

from benchmark.harness import device


def least_bytes(pooled_verbs) -> int:
    """``pooled_verbs``: dicts a table with ``positions``, ``bags`` and
    ``unique`` (distinct rows) summed over the window's steps, each step
    one pooled fetch and one pooled apply of them, ``row_bytes`` and
    ``state``."""
    total = 0
    for v in pooled_verbs:
        fetch = v["unique"] + v["bags"]
        apply = v["bags"] + 2 * v["state"] * v["unique"]
        total += (fetch + apply) * v["row_bytes"]
    return total


def read(run):
    s = run.trace_summary()
    verbs = run.window.get("pooled_verbs")
    if s is None or not verbs or run.rehearsal:
        return None     # a rehearsal has no chip whose peak to take
    busy_s = max(d["busy_s"] for d in s["devices"])
    if not busy_s:
        return None
    peak = device.peaks(run.devices[0].device_kind)["hbm_gb_per_s"] * 1e9
    return 100.0 * least_bytes(verbs) / (busy_s * peak)
