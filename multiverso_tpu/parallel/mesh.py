"""Device mesh construction and shard placement.

The TPU-native replacement for the reference's node/rank fabric: instead of
N MPI processes each hosting a parameter shard in its heap
(reference src/zoo.cpp, src/net/mpi_net.h), a ``jax.sharding.Mesh`` with a
``server`` axis hosts every table shard in HBM. ``num_servers`` is the mesh
size along that axis; worker identity is a host-side concept (threads in one
process, processes across hosts via ``jax.distributed``).

``partition_offsets`` preserves the reference's contiguous-shard math —
each server takes ``size // num_servers`` elements and the last takes the
remainder (reference src/table/array_table.cpp:10-19, 101-105) — used by
host-side partition logic and by parity unit tests
(reference Test/unittests/test_array.cpp:47-66 tests Partition as a pure
function).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SERVER_AXIS = "server"

def partition_offsets(size: int, num_servers: int) -> List[Tuple[int, int]]:
    """[(offset, count)] per server; last server takes the remainder.

    Mirrors reference array_table.cpp:101-105 (server_offsets_ construction).
    """
    if num_servers <= 0:
        raise ValueError("num_servers must be positive")
    base = size // num_servers
    out = []
    for s in range(num_servers):
        offset = base * s
        count = base if s < num_servers - 1 else size - base * (num_servers - 1)
        out.append((offset, count))
    return out


def row_partition_server(row: int, num_rows: int, num_servers: int) -> int:
    """Reference-parity row→server math: ``row / (num_row / num_server)``
    with the tail clamped to the last server (reference
    matrix_table.cpp:24-46). Kept as the parity-tested pure function; the
    actual TPU storage ownership is ``storage_partition_server`` (equal-size
    shards — jax shards must be uniform, so the remainder spreads by ceil
    blocks instead of piling on the last server). The two agree whenever
    ``num_servers`` divides ``num_rows``."""
    base = num_rows // num_servers
    if base == 0:
        return 0
    return min(row // base, num_servers - 1)


def ceil_block_rows(num_rows: int, num_servers: int) -> int:
    """Rows per server shard in the interleaved TPU layout — the ONE place
    the ceil-block ownership law lives (matrix_table.py storage and its
    shard-local id math both derive from this)."""
    return -(-num_rows // num_servers)


def storage_partition_server(row: int, num_rows: int, num_servers: int) -> int:
    """Which server shard actually owns a row in the interleaved TPU layout
    (matrix_table.py): ceil-based equal blocks."""
    block = ceil_block_rows(num_rows, num_servers)
    return min(row // block, num_servers - 1)


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def next_bucket(n: int, min_bucket: int = 8) -> int:
    """Smallest bucket size >= n (and >= min_bucket). The table layer pads
    dynamic id batches to these buckets so XLA compiles a handful of shapes
    instead of one per batch size.

    Ladder: powers of two up to 256, then quarter-octave steps (b/2 x
    {1.25, 1.5, 1.75, 2}) — pad waste drops from <=100% to <=25% of the
    batch (wasted lanes are real DMAs on the row hot path) for ~4x the
    shape count, and every rung above 256 stays a multiple of 64, the
    Pallas row-kernel chunk."""
    b = min_bucket
    while b < n:
        b <<= 1
    if b <= 256:
        return b
    half = b >> 1
    for num in (5, 6, 7):          # half * 1.25 / 1.5 / 1.75
        cand = (half * num) // 4   # half >= 256 -> exact and 64-aligned
        if cand >= n:
            return cand
    return b


def local_device_count(mesh: Mesh) -> int:
    """Devices of ``mesh`` owned by THIS process (>=1)."""
    import jax as _jax
    pid = _jax.process_index()
    return max(1, sum(1 for d in mesh.devices.flat
                      if d.process_index == pid))


def parts_bucket(n: int, local_dev: int) -> int:
    """Per-process bucket for a batch-sharded parts array: the next_bucket
    rung rounded up to a multiple of this process's device count, so the
    global (nproc * bucket) batch always shards evenly over the mesh."""
    return pad_to_multiple(next_bucket(n), local_dev)


def place_parts(mesh: Mesh, local, nproc: int) -> jax.Array:
    """THIS process's local block -> a batch-sharded GLOBAL array whose
    axis 0 stacks every process's block in process order (global shape
    ``(nproc * local.shape[0], ...)``, sharded P(SERVER_AXIS) on axis 0).

    The one placement primitive behind every table's multi-process
    device-plane verbs. Host arrays ride
    ``make_array_from_process_local_data``; device-resident arrays stay
    in HBM — the block is split across this process's mesh devices with
    on-device slices (no host round-trip), falling back to the host path
    only if the sharding's device-to-index map doesn't line up with
    process-contiguous blocks (it does for the process-grouped meshes
    build_mesh constructs)."""
    import jax as _jax
    spec = P(SERVER_AXIS, *([None] * (local.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    global_shape = (nproc * local.shape[0],) + tuple(local.shape[1:])
    if isinstance(local, jax.Array) and local.is_fully_addressable:
        pid = _jax.process_index()
        offset = pid * local.shape[0]
        pieces, ok = [], True
        for dev, idx in sharding.devices_indices_map(global_shape).items():
            if dev.process_index != pid:
                continue
            lo = (idx[0].start or 0) - offset
            hi = (idx[0].stop if idx[0].stop is not None
                  else global_shape[0]) - offset
            if lo < 0 or hi > local.shape[0]:
                ok = False   # non-contiguous process blocks: host fallback
                break
            pieces.append((lo, hi, dev))
        if ok:
            arrs = [_jax.device_put(local[lo:hi], dev)
                    for lo, hi, dev in pieces]
            return _jax.make_array_from_single_device_arrays(
                global_shape, sharding, arrs)
        local = np.asarray(local)
    return _jax.make_array_from_process_local_data(
        sharding, np.asarray(local), global_shape)


def build_mesh(devices: Optional[Sequence[jax.Device]] = None,
               axis_name: str = SERVER_AXIS) -> Mesh:
    """1-D mesh over all (or given) devices along the server axis."""
    if devices is None:
        devices = jax.devices()
    dev_array = np.asarray(devices)
    return Mesh(dev_array, (axis_name,))


@dataclass
class MeshContext:
    """Owns the mesh and canonical shardings for the table layer."""

    mesh: Mesh

    @classmethod
    def create(cls, devices: Optional[Sequence[jax.Device]] = None) -> "MeshContext":
        return cls(mesh=build_mesh(devices))

    @property
    def num_servers(self) -> int:
        return self.mesh.shape[SERVER_AXIS]

    def sharding_1d(self) -> NamedSharding:
        """Contiguous range shards of a 1-D array (ArrayTable layout)."""
        return NamedSharding(self.mesh, P(SERVER_AXIS))

    def sharding_rows(self) -> NamedSharding:
        """Row shards of a 2-D array (MatrixTable layout)."""
        return NamedSharding(self.mesh, P(SERVER_AXIS, None))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def place(self, array, sharding: NamedSharding):
        """Host -> HBM placement with an explicit layout."""
        return jax.device_put(array, sharding)

    def fetch(self, arr) -> np.ndarray:
        """Device -> host of a possibly globally-sharded array.

        Single-process (and fully-replicated) arrays fetch directly. In a
        multi-process job a shard-spanning array lives partly on
        non-addressable devices — reassemble the global value by
        allgathering every process's local shards. That makes this a
        COLLECTIVE in multihost mode, which the table layer's collective
        contract already guarantees (parallel/multihost.py docstring)."""
        if not isinstance(arr, jax.Array):
            return np.asarray(arr)
        if arr.is_fully_addressable or arr.is_fully_replicated:
            return np.asarray(arr)
        from jax.experimental import multihost_utils

        from multiverso_tpu.parallel import multihost
        multihost.note_collective()
        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
