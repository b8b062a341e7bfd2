"""Seconds ``prepare()`` spent building the Huffman tree and every word's
path arrays: the gauge ``we.prepare.huffman_s``, as it stands after the
window (set-up ends before the first snapshot). Nothing to read where the
program has no such gauge. Layer: entry points. Moves ``setup_s``."""


def read(run):
    gauge = run.counters_after.get("we.prepare.huffman_s")
    return None if gauge is None else float(gauge["value"])
