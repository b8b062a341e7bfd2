"""1 minus the union of device-operation intervals over the traced
window, averaged over the chips (the largest is on an earlier line of the
run). Layer: device. Moves the cell's throughput."""


def read(run):
    s = run.trace_summary()
    if s is None or not s["window_s"]:
        return None
    busy = sum(d["busy_s"] for d in s["devices"]) / len(s["devices"])
    return 100.0 * (1.0 - busy / s["window_s"])
