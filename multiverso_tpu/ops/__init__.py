"""TPU kernels for the parameter-server hot path.

The reference's hot loops are the server's per-row updater application and
the serialize/memcpy path (reference src/updater/updater.cpp:21-29 OpenMP
loops; src/net/mpi_net.h:300-349 serialize memcpys). Here they are device
programs: XLA's gather for row reads (one slice for a run of consecutive
rows), and, on TPU, a Pallas row-DMA scatter for row writes (only touched
rows move), with XLA's scatter everywhere else (``rows.py`` holds the one
decision).
"""

from multiverso_tpu.ops.rows import (dedup_rows, gather_rows, padded_cols,
                                     row_write, scatter_set_rows, slice_rows,
                                     update_gather_rows, update_rows,
                                     update_rows_with_state, use_pallas)

__all__ = ["dedup_rows", "gather_rows", "padded_cols", "row_write",
           "scatter_set_rows", "slice_rows", "update_gather_rows",
           "update_rows", "update_rows_with_state", "use_pallas"]
