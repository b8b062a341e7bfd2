"""The three things a table does to the device, one helper each.

A verb of the table layer crosses into the row-ops layer in three ways:
it copies host arrays in (:func:`place`), it launches a program
(``with`` :func:`call`: the jitted row programs and the eager ``jnp``
operations around them alike — a slice of a device array is a launch of
its own) and it copies a device array back (:func:`take`). Each crossing
is a counter step whatever the flags, and with ``-trace`` on a leaf span
named after the span it runs in (``ttrace.child``): ``<verb's
span>.place``, ``.call``, ``.take`` and, before a ``.take``, ``.wait``.

=================  ==========================  ===========================
crossing           counters                    span suffix
=================  ==========================  ===========================
host to device     ``table.device.h2d_copies`` ``.place``
                   (host arrays),
                   ``table.device.h2d_bytes``
                   (their bytes, once,
                   whatever the sharding
                   replicates)
a program's call   ``table.device.calls``      ``.call`` (``args``: the
                                               program's name)
device to host     ``table.device.d2h_copies`` ``.wait`` (``-trace`` only),
                   ``table.device.d2h_bytes``  then ``.take``
                   (what crossed: a Get's
                   bucket with its pad, see
                   below)
=================  ==========================  ===========================

Where a gather's pad is dropped. A row program returns its bucket, and a
host-plane Get wants the first ``n`` rows of it on the host. The bucket is
copied back whole and the caller takes ``[:n]`` of the host array, a view:
no slice program, so a Get is ONE ``.call`` and the pad's bytes (48 KB of
2 MB for 10,000 ids under the rung 10,240) are counted in ``d2h_bytes``.
A launch costs the host more than those bytes do; only a pad over
``matrix_table._HOST_CUT_PAD_BYTES`` and over a quarter of the rows asked
for is cut on the device first (a second ``.call``, program ``slice``,
to one of the bucket's eighths: never a program a row count).
``table.get.host_cuts`` and ``table.get.device_cuts`` count the Gets of
either kind. Rows that stay in HBM (``device_fetch_rows``) are always cut
on the device.

How a host delta's pad is made. A row Add's delta crosses exact-size and
the device pads it to its bucket (program ``_pad_row_batch``, one a
distinct batch size) while the pad is under the same constant. Over it
the delta crosses in pieces of an eighth of the bucket, views of the
sender's array (``matrix_table._place_rows``: one ``.place`` of that
many host arrays, no copy on the host), and ``_join_row_pieces`` lays
them out at the bucket: one program a piece count.
``table.add.host_pieces`` counts the pieces.

With ``-trace`` off a helper adds one flag read a span and one counter
step a crossing to what the verb did before: no ``block_until_ready``, no
copy, no program. ``.wait`` exists only while a trace runs: it blocks on
the array about to be copied so that ``.take`` is the copy alone; the
copy would have waited as long.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace


def place(host, put: Callable = jnp.asarray):
    """``put(host)``: a host array, or a pytree of them in one ``put``,
    onto the device."""
    with ttrace.child(".place"):
        out = put(host)
    leaves = (host,) if isinstance(host, np.ndarray) else jax.tree.leaves(host)
    tmetrics.counter("table.device.h2d_copies").inc(len(leaves))
    tmetrics.counter("table.device.h2d_bytes").inc(
        sum(leaf.nbytes for leaf in leaves))
    return out


def call(program: str):
    """One launch of the program so named: ``with crossing.call(name):``
    around the call alone, its operands made before. A context manager
    and not a wrapper of the call: a frame of ours between a verb and a
    jitted row program while it is traced cost ``rec_bag_steps`` 5 to 7 s
    of set-up (PERF.md section 6, PR 35)."""
    tmetrics.counter("table.device.calls").inc()
    if not ttrace.enabled():
        return ttrace.NULL_SPAN
    return ttrace.child(".call", {"program": program})


def take(arr, fetch: Callable = np.asarray,
         also: Optional[str] = None) -> np.ndarray:
    """``fetch(arr)``: a device array onto the host. ``also`` names a
    second counter that takes the same bytes (a verb's own, such as
    ``table.device_apply.d2h_bytes``)."""
    if ttrace.enabled():
        with ttrace.child(".wait"):
            jax.block_until_ready(arr)
    with ttrace.child(".take"):
        host = fetch(arr)
    tmetrics.counter("table.device.d2h_copies").inc()
    tmetrics.counter("table.device.d2h_bytes").inc(host.nbytes)
    if also is not None:
        tmetrics.counter(also).inc(host.nbytes)
    return host
