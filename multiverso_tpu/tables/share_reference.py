"""Plain arithmetic of a MatrixTable block-sharded over servers, and the
replay of a small world of such tables.

numpy only: no tables, no engine, no jax. A MatrixTable of ``num_rows``
rows over ``servers`` servers gives every server ``ceil(num_rows /
servers)`` consecutive rows (``parallel/mesh.py`` ``ceil_block_rows``; the
last servers hold what is left, perhaps nothing), so server ``s`` holds
the logical rows ``share_bounds(num_rows, servers, s)`` and knows them by
their offset from its first. One server's share of a deployment (the
benchmark's ``criteo1tb-mh-26t-128-share32``: server 0 of 32) is a world
of tables of ``share_rows`` rows each.

``replay`` applies a world's Adds table by table with the float32 rules of
``updaters/reference.py``; ``replay_share`` does so for one server's rows
alone, from the same Adds: it keeps the ids that fall on the server, moves
them to its offsets and applies their deltas. ``tests/test_bag_tables.py``
holds a table the system sharded over four devices to it, shard by shard.
"""

from __future__ import annotations

import numpy as np

from multiverso_tpu.updaters import reference


def block_rows(num_rows: int, servers: int) -> int:
    """Rows a server's block spans: ``ceil(num_rows / servers)``."""
    return -(-int(num_rows) // int(servers))


def share_bounds(num_rows: int, servers: int, server: int):
    """(first, past-the-last) logical row of ``server``'s block."""
    block = block_rows(num_rows, servers)
    return (min(server * block, num_rows),
            min((server + 1) * block, num_rows))


def share_rows(num_rows: int, servers: int, server: int) -> int:
    """How many rows ``server`` holds; over the servers they add up to
    ``num_rows``."""
    first, past = share_bounds(num_rows, servers, server)
    return past - first


def replay(tables, adds, updater: str = "adagrad", **option):
    """A world after its Adds. ``tables``: the initial (rows, cols) array
    of each table; ``adds``: (table number, ids, deltas) in the order they
    were applied; ``option``: ``AddOption``'s fields. Returns a reference
    state (``data`` and the rule's own arrays) a table."""
    states = [reference.new_state(t, updater) for t in tables]
    for table, ids, deltas in adds:
        reference.apply_rows(updater, states[table], ids, deltas, **option)
    return states


def replay_share(tables, adds, servers: int, server: int,
                 updater: str = "adagrad", **option):
    """What ``server`` of ``servers`` holds of the same world after the
    same Adds: its block of each table under its own offsets, advanced by
    the ids of each Add that fall in the block (an Add none of whose ids
    do leaves the share alone)."""
    bounds = [share_bounds(len(t), servers, server) for t in tables]
    states = [reference.new_state(np.asarray(t)[a:b], updater)
              for t, (a, b) in zip(tables, bounds)]
    for table, ids, deltas in adds:
        first, past = bounds[table]
        ids = np.asarray(ids, np.int64).ravel()
        mine = (ids >= first) & (ids < past)
        if mine.any():
            deltas = np.asarray(deltas, np.float32).reshape(len(ids), -1)
            reference.apply_rows(updater, states[table], ids[mine] - first,
                                 deltas[mine], **option)
    return states
