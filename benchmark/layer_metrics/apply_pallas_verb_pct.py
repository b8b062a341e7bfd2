"""Share of the window's ``device_apply_rows`` verbs whose row program
writes through the Pallas row kernel behind the dense-run test: the
program's counter ``table.device_apply.pallas_verbs`` over its sum with
``.xla_verbs`` (XLA's scatter: an id vector over the kernel's SMEM budget,
or a row shape Mosaic does not compile) and ``.small_table_verbs`` (the id
bucket is not under the table's rows: the general branch alone). The
counters step on the host, from static shapes, where the verb picks its
program; a kind no verb of the process took has no counter and counts 0.
Nothing to read where the program has none of them. Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import program

KINDS = ("pallas", "xla", "small_table")


def read(run):
    moved = [program.counter_delta(
        run.counters_before, run.counters_after,
        f"table.device_apply.{kind}_verbs") for kind in KINDS]
    moved = [m or 0.0 for m in moved]
    if not sum(moved):
        return None
    return 100.0 * moved[0] / sum(moved)
