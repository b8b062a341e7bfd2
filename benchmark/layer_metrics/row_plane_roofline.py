"""The device-plane row verbs' share of their memory roofline: the least
bytes the traced window's verbs must move through HBM, over the trace's
device-busy seconds times the chip's peak HBM bandwidth
(``harness/peaks.json``). The verbs are bound by bytes: they do a handful of
operations an element.

``least_bytes`` counts on *distinct* rows, whatever the verb was handed: a
fetch must read each distinct row once and write one row per position; an
apply must read one delta row per position and read and write each distinct
row of the table and of each further array of updater state (``state``
arrays in all: 2 under AdaGrad, the row and its history). Nothing else is
counted: not the ids, not a gather's second pass, not a copy the program
makes. So the share cannot pass 100 % unless the device was busy for less
time than the trace says. Layer: row ops and kernels. Moves
``table_rows_per_s``."""

from benchmark.harness import device


def least_bytes(row_verbs) -> int:
    """``row_verbs``: dicts with ``verb`` (``fetch`` or ``apply``),
    ``positions`` (rows handed to the verb, repeats counted), ``unique``
    (distinct rows among them), ``row_bytes`` and ``state`` (arrays an
    apply reads and writes a row: the table's and its updater state's)."""
    total = 0
    for v in row_verbs:
        if v["verb"] == "fetch":
            rows = v["unique"] + v["positions"]
        else:
            rows = v["positions"] + 2 * v["state"] * v["unique"]
        total += rows * v["row_bytes"]
    return total


def read(run):
    s = run.trace_summary()
    verbs = run.window.get("row_verbs")
    if s is None or not verbs or run.rehearsal:
        return None     # a rehearsal has no chip whose peak to take
    busy_s = max(d["busy_s"] for d in s["devices"])
    if not busy_s:
        return None
    peak = device.peaks(run.devices[0].device_kind)["hbm_gb_per_s"] * 1e9
    return 100.0 * least_bytes(verbs) / (busy_s * peak)
