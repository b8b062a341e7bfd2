"""Bytes of the Huffman path tables the trainer keeps on a chip (a node id
a lane, a word's turns as bits, its path length), in GB (1e9 bytes): the
gauge ``we.hs.table_bytes``. A capacity record beside ``hbm_peak_gb`` and
listed under ``setup_s`` as that is: making and placing them is set-up
work. Nothing to read where the program has no such gauge. Layer: device."""


def read(run):
    gauge = run.counters_after.get("we.hs.table_bytes")
    return None if gauge is None else float(gauge["value"]) / 1e9
