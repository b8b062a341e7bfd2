"""Same-host shared-memory wire for the windowed engine's exchange.

The reference treats transports as pluggable — its allreduce engine
picks between a ZMQ socket wire and MPI collectives per deployment
(PAPER.md L2, allreduce_engine.cpp). The TPU build's equivalent split:
``multihost.capped_exchange`` is the engine's one host-byte collective,
and gloo (a socket allgather) is its only implementation — measured at
~410 MB/s between two processes of the SAME machine (a CPU-backend
measurement of round 9, not a chip number), i.e. the window wire pays
socket-stack prices for what is physically a memcpy. This module is
the same-host transport: every rank owns one POSIX shared-memory
segment per (channel, rank) and an exchange round is N-1 memcpys in,
N-1 memcpys out.

Protocol (per channel — channels are INDEPENDENT exchange streams, one
per engine shard, so sharded engines exchange concurrently without
sharing a collective order):

* A segment is ``header | consumed[nprocs] | data[cap]``. The writer
  (the owning rank) publishes frames as one or more chunks of at most
  ``cap`` bytes; the header carries ``(seq, round, total, chunk_off,
  chunk_len, crc32)`` and is finalized by the ``seq`` store — readers
  accept a chunk only once ``seq`` reaches the value they expect, so a
  torn frame is never consumed (x86-TSO store order; the CRC trailer
  is the backstop).
* ``seq`` counts chunks monotonically per segment; ``round`` counts
  exchanges per channel. Both sides advance them in lockstep (the
  exchange IS collective), so a rank re-entering an exchange alone
  surfaces as a loud ``round`` mismatch (WireCorruption) instead of
  silently pairing different windows — the same SEQ-stamp posture as
  the engine's window blobs.
* Flow control: ``consumed[j]`` (written by reader j into the writer's
  segment) is the last chunk seq rank j fully consumed. The writer
  overwrites the single data area only after every reader consumed the
  previous chunk. Readers and the writer interleave inside one
  exchange call (everybody writes chunk 0 first, then drains peers
  while draining their own backpressure), so multi-chunk frames cannot
  deadlock.
* ``crc32`` covers the WHOLE blob and is verified after reassembly —
  a mismatch (or a ``total`` that the chunks never reach — truncation)
  raises ``WireCorruption``, counted in ``shm_wire.crc_failures``.

Waits honour ``-mv_deadline_s`` (``failsafe.deadline.timeout_or_none``)
directly — a dead peer raises ``DeadlineExceeded`` from the spin
itself, so an abandoned exchange never leaves a hot-spinning thread
behind. With the flag unset the wait blocks exactly like the gloo
collective would, backing off to short sleeps.

Selection lives in ``multihost.maybe_install_wire``: ``-mv_wire=auto``
installs this wire when every rank of the boot world reports the same
hostname (one gloo rendezvous exchanges hostnames + rank 0's session
token), verified by a smoke exchange; any setup failure falls back to
gloo loudly. Elastic epochs (> 0) ride the coordinator relay as
before — the group transport takes precedence over this wire.
"""

from __future__ import annotations

import time
from multiprocessing import shared_memory
from typing import Dict, List, Optional

import numpy as np

from multiverso_tpu.failsafe import deadline as fdeadline
from multiverso_tpu.failsafe.errors import WireCorruption
# checksums ride the seal module's fast_crc (round 19): hardware CRC32C
# when the native engine is loadable, zlib.crc32 otherwise — legal for
# this wire because both ends of an shm ring are the same build on the
# same host, so they always pick the same engine
from multiverso_tpu.parallel.seal import fast_crc
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.utils.log import CHECK, Log

#: header field offsets (little-endian u64 unless noted)
_OFF_SEQ = 0          # chunks written to this segment, monotonic
_OFF_ROUND = 8        # exchange round of the current frame
_OFF_TOTAL = 16       # whole-blob byte length of the current frame
_OFF_CHUNK_OFF = 24   # byte offset of the current chunk within the blob
_OFF_CHUNK_LEN = 32   # byte length of the current chunk
_OFF_CRC = 40         # u32: crc32 of the WHOLE blob (payload_crc mode)
_OFF_MAGIC = 44       # u32: segment layout magic
_OFF_HCRC = 48        # u32: crc32 of the frame header fields + seq
_HDR = 64

_MAGIC = 0x4D56_5348  # "MVSH"

#: hot spins before the waiter starts sleeping (an exchange peer is
#: usually microseconds away; sleeping immediately would add ~50us of
#: scheduler latency per chunk)
_HOT_SPINS = 400
_SLEEP_S = 50e-6


#: how often a stalled exchange consults the elastic membership lease
#: (see _peer_loss_probe); ~4x per second keeps the detection latency
#: far under any -mv_deadline_s worth arming
_PROBE_PERIOD_S = 0.25


def _peer_loss_probe(what: str):
    """A stalled exchange asks the elastic authority whether a peer is
    DEAD (lease expired) — a socket transport gets this for free (the
    dead peer's connection resets and the collective errors out fast),
    but shared memory has no connection to break: without the probe a
    silent death costs the FULL collective deadline before the engine
    can convert it, and the worker's own verb deadline wins that race.
    Returns the typed MembershipChanged to raise, or None (no elastic
    plane / every lease fresh / probe failed — keep waiting)."""
    try:
        from multiverso_tpu import elastic
        if not elastic.enabled():
            return None
        return elastic.peer_loss(what)
    except Exception:       # the deadline still bounds the wait
        return None


def _header_crc(seq: int, rnd: int, total: int, off: int, ln: int,
                crc: int) -> int:
    """CRC over the frame header's logical fields INCLUDING the seq
    value the chunk publishes under — always verified (a torn header
    mis-sizes the copy), and cheap: ~50 bytes per chunk."""
    return fast_crc(b"%d|%d|%d|%d|%d|%d"
                    % (seq, rnd, total, off, ln, crc)) & 0xFFFFFFFF


def segment_name(token: str, channel: int, rank: int) -> str:
    """POSIX shm name of (channel, rank)'s segment — short (the POSIX
    limit is system-dependent) and unique per world via ``token``."""
    return f"mv{token}c{channel}r{rank}"


def _attach(name: str) -> shared_memory.SharedMemory:
    """Attach an existing segment WITHOUT handing its lifetime to this
    process's resource tracker (py<3.13 registers attachments too and
    would unlink the owner's segment at our exit)."""
    try:
        return shared_memory.SharedMemory(name=name, track=False)
    except TypeError:       # Python < 3.13: no track parameter
        # suppress registration for the attach (unregistering AFTER
        # would also drop the creator's entry when both ends live in
        # one process — e.g. the in-process fault drills)
        from multiprocessing import resource_tracker
        orig = resource_tracker.register

        def _no_shm_register(name_, rtype):
            if rtype != "shared_memory":
                orig(name_, rtype)

        resource_tracker.register = _no_shm_register
        try:
            return shared_memory.SharedMemory(name=name)
        finally:
            resource_tracker.register = orig


class _Segment:
    """One (channel, rank) segment and its numpy field views."""

    def __init__(self, shm: shared_memory.SharedMemory, nprocs: int,
                 cap: int, owned: bool):
        self.shm = shm
        self.owned = owned
        self.cap = cap
        buf = shm.buf
        self.u64 = np.frombuffer(buf, np.uint64, count=_HDR // 8)
        self.u32 = np.frombuffer(buf, np.uint32, count=_HDR // 4)
        self.consumed = np.frombuffer(buf, np.uint64, count=nprocs,
                                      offset=_HDR)
        self.data = np.frombuffer(buf, np.uint8,
                                  count=cap, offset=_HDR + 8 * nprocs)

    def seq(self) -> int:
        return int(self.u64[_OFF_SEQ // 8])

    def close(self) -> None:
        # release the numpy views FIRST: SharedMemory.close() refuses
        # while exported memoryviews are alive
        self.u64 = self.u32 = self.consumed = self.data = None
        try:
            self.shm.close()
        except Exception:
            pass
        if self.owned:
            try:
                self.shm.unlink()
            except Exception:   # already unlinked (double close)
                pass


class ShmWire:
    """Same-host allgather-bytes transport over shared memory.

    One instance per process per world; ``exchange(blob, channel)`` is
    collective per channel — every rank of the world must call it for
    the same channel in the same per-channel order (the engine's SPMD
    window contract already guarantees exactly that, per shard)."""

    #: transport label (multihost.wire_name reads this off the
    #: installed instance)
    name = "shm"

    def __init__(self, token: str, rank: int, nprocs: int,
                 channels: int, data_bytes: int,
                 payload_crc: bool = True):
        CHECK(nprocs >= 2, "ShmWire needs a multi-process world")
        CHECK(channels >= 1, "ShmWire needs at least one channel")
        #: whole-blob CRC per frame. The engine install turns this
        #: OFF: every engine window/head-marker blob already carries
        #: the failsafe wire's seal trailer (parallel/seal.py,
        #: verified BEFORE parsing), and a second full-blob pass costs
        #: real bandwidth — zlib.crc32 MEASURED at ~0.8 GB/s on this
        #: host class (round 9, CPU host; slower than the memcpy it would
        #: guard). Round 19: the pass now rides seal.fast_crc
        #: (hardware CRC32C, ~8x zlib here), so payload_crc=True is
        #: merely cheap rather than bandwidth-halving — the engine
        #: still skips it because the blobs arrive pre-sealed. The
        #: frame HEADER is always CRC'd (cheap), and truncation stays
        #: structurally detected via the total/chunk accounting
        #: either way.
        self.payload_crc = bool(payload_crc)
        self.token = token
        self.rank = rank
        self.nprocs = nprocs
        self.channels = channels
        self.cap = max(int(data_bytes), 4096)
        self._size = _HDR + 8 * nprocs + self.cap
        #: own (writer) segments, one per channel — created HERE;
        #: peers attach after the world's creation barrier
        self._own: Dict[int, _Segment] = {}
        #: attached peer segments: (channel, rank) -> _Segment
        self._peer: Dict[tuple, _Segment] = {}
        #: per-channel exchange round + per-segment chunk-seq cursors
        self._round = [0] * channels
        self._wseq = [0] * channels
        self._rseq: Dict[tuple, int] = {}
        self._closed = False
        self._t_crc = tmetrics.counter("shm_wire.crc_failures")
        self._t_rounds = tmetrics.counter("shm_wire.exchanges")
        self._t_bytes = tmetrics.counter("shm_wire.bytes_out")
        # round 13 — saturation telemetry (watchdog plane): seconds this
        # rank's WRITER spent stalled with chunks still to publish (its
        # readers lag — backpressure on the ring, distinct from the
        # reader-side wait for a slow peer's frame, which critpath
        # attributes to the peer), and the largest frame ever published
        # (ring occupancy high-watermark vs -mv_shm_ring_bytes)
        self._t_wstall = tmetrics.counter("shm_wire.writer_stall_s")
        self._t_hw = tmetrics.gauge("shm_wire.frame_hw_bytes")
        self._t_occ = tmetrics.gauge("shm_wire.ring_occupancy_pct")
        self.writer_stall_s = 0.0
        self.frame_hw_bytes = 0
        for ch in range(channels):
            shm = shared_memory.SharedMemory(
                name=segment_name(token, ch, rank), create=True,
                size=self._size)
            shm.buf[:_HDR + 8 * nprocs] = bytes(_HDR + 8 * nprocs)
            seg = _Segment(shm, nprocs, self.cap, owned=True)
            seg.u32[_OFF_MAGIC // 4] = _MAGIC
            self._own[ch] = seg

    # -- wiring --------------------------------------------------------------

    def attach_peers(self) -> None:
        """Attach every peer's segments (call after a world barrier
        that proves creation completed on every rank)."""
        for ch in range(self.channels):
            for r in range(self.nprocs):
                if r == self.rank:
                    continue
                seg = _Segment(_attach(segment_name(self.token, ch, r)),
                               self.nprocs, self.cap, owned=False)
                CHECK(int(seg.u32[_OFF_MAGIC // 4]) == _MAGIC,
                      f"shm wire segment {segment_name(self.token, ch, r)} "
                      f"has a foreign layout")
                self._peer[(ch, r)] = seg
                self._rseq[(ch, r)] = 0

    def close(self) -> None:
        """Detach everything; unlink own segments. Idempotent."""
        if self._closed:
            return
        self._closed = True
        for seg in self._peer.values():
            seg.close()
        for seg in self._own.values():
            seg.close()
        self._peer.clear()
        self._own.clear()

    # -- the exchange --------------------------------------------------------

    def _chunks(self, blob: bytes) -> List[tuple]:
        """(offset, length) chunk plan — at least one chunk, so empty
        frames still publish a header readers can consume."""
        if not blob:
            return [(0, 0)]
        return [(off, min(self.cap, len(blob) - off))
                for off in range(0, len(blob), self.cap)]

    def exchange(self, blob: bytes, channel: int,
                 timeout_s: Optional[float] = None) -> List[bytes]:
        """Every rank's blob for this channel's next round, rank order.
        Collective per channel; bounded by ``-mv_deadline_s``, or by
        ``timeout_s`` when given (the replica fan-out thread passes its
        lease-derived bound explicitly — a dead reader must cost one
        bounded wait, whatever the engine's deadline flag says). NOTE a
        timed-out exchange leaves the channel's round counter advanced:
        the caller must scrap the wire, never retry the round."""
        CHECK(not self._closed, "shm wire used after close")
        CHECK(0 <= channel < self.channels,
              f"shm wire channel {channel} out of range "
              f"(wire has {self.channels})")
        rnd = self._round[channel]
        self._round[channel] += 1
        own = self._own[channel]
        if len(blob) > self.frame_hw_bytes:
            # high-watermark only (one compare per exchange): the gauge
            # answers "how close do frames come to the ring cap" —
            # multi-chunk frames (> cap) serialize through the single
            # data area and are exactly what the writer-stall measures
            self.frame_hw_bytes = len(blob)
            self._t_hw.set(float(len(blob)))
            self._t_occ.set(min(100.0, 100.0 * len(blob) / self.cap))
        crc = (fast_crc(blob) & 0xFFFFFFFF) if self.payload_crc else 0
        plan = self._chunks(blob)
        blob_view = memoryview(blob)
        peers = [r for r in range(self.nprocs) if r != self.rank]
        # reader state per peer: [assembled bytearray|None, total|None,
        # chunks_read, done, crc(latched), crc(running)]
        rstate = {r: [None, None, 0, False, 0, 0] for r in peers}
        wseq0 = self._wseq[channel]
        wi = 0                        # next own chunk to write
        deadline = (timeout_s if timeout_s is not None
                    else fdeadline.timeout_or_none())
        t0 = time.perf_counter()
        last_probe = t0
        spins = 0
        wstall_s = 0.0          # writer blocked on reader acks (local)
        while True:
            progressed = False
            # -- write side: publish the next chunk once every reader
            # consumed the previous one (single-buffer reuse)
            if wi < len(plan):
                floor = wseq0 + wi      # required consumed level
                if all(int(own.consumed[r]) >= floor for r in peers):
                    off, ln = plan[wi]
                    if ln:
                        own.data[:ln] = np.frombuffer(
                            blob_view[off:off + ln], np.uint8)
                    seq_next = wseq0 + wi + 1
                    own.u64[_OFF_ROUND // 8] = rnd
                    own.u64[_OFF_TOTAL // 8] = len(blob)
                    own.u64[_OFF_CHUNK_OFF // 8] = off
                    own.u64[_OFF_CHUNK_LEN // 8] = ln
                    own.u32[_OFF_CRC // 4] = crc
                    own.u32[_OFF_HCRC // 4] = _header_crc(
                        seq_next, rnd, len(blob), off, ln, crc)
                    # seq LAST: the store that makes the chunk visible
                    own.u64[_OFF_SEQ // 8] = seq_next
                    wi += 1
                    progressed = True
            # -- read side: drain whatever peers have published
            for r in peers:
                st = rstate[r]
                if st[3]:
                    continue
                seg = self._peer[(channel, r)]
                want = self._rseq[(channel, r)] + 1
                if seg.seq() < want:
                    continue
                peer_round = int(seg.u64[_OFF_ROUND // 8])
                if peer_round != rnd:
                    raise WireCorruption(
                        f"shm wire desync on channel {channel}: rank "
                        f"{r} is at exchange round {peer_round}, rank "
                        f"{self.rank} at {rnd} — a rank re-entered the "
                        f"exchange alone; the stream cannot be trusted")
                total = int(seg.u64[_OFF_TOTAL // 8])
                off = int(seg.u64[_OFF_CHUNK_OFF // 8])
                ln = int(seg.u64[_OFF_CHUNK_LEN // 8])
                frame_crc = int(seg.u32[_OFF_CRC // 4])
                if int(seg.u32[_OFF_HCRC // 4]) != _header_crc(
                        want, peer_round, total, off, ln, frame_crc):
                    self._t_crc.inc()
                    raise WireCorruption(
                        f"shm wire frame header from rank {r} failed "
                        f"its CRC32 (round {rnd}, chunk seq {want})")
                if st[0] is None:
                    st[0] = bytearray(total)
                    st[1] = total
                    # LATCH the frame CRC before any ack: once the
                    # final chunk is acked the writer may overwrite the
                    # header with the NEXT round's values — a post-ack
                    # header read would compare against the wrong CRC
                    st[4] = frame_crc
                if total != st[1] or off + ln > st[1]:
                    self._t_crc.inc()
                    raise WireCorruption(
                        f"shm wire frame from rank {r} truncated/"
                        f"inconsistent: total {total} vs {st[1]}, "
                        f"chunk [{off}:{off + ln}]")
                if ln:
                    # one copy, straight from the segment (bytearray
                    # slice assignment takes the buffer protocol), and
                    # the CRC runs over the COPIED bytes — cache-warm,
                    # and immune to any post-ack overwrite
                    st[0][off:off + ln] = seg.data[:ln].data
                    if self.payload_crc:
                        st[5] = fast_crc(
                            memoryview(st[0])[off:off + ln], st[5])
                st[2] += 1
                self._rseq[(channel, r)] = want
                # ack AFTER the copy: the writer may now overwrite
                seg.consumed[self.rank] = want
                expect_chunks = max(1, -(-st[1] // self.cap))
                if st[2] >= expect_chunks:
                    if self.payload_crc and (st[5] & 0xFFFFFFFF) != st[4]:
                        self._t_crc.inc()
                        raise WireCorruption(
                            f"shm wire frame from rank {r} failed its "
                            f"CRC32 (round {rnd}, {st[1]} bytes)")
                    st[3] = True
                progressed = True
            if wi >= len(plan) and all(st[3] for st in rstate.values()):
                break
            if progressed:
                spins = 0
                continue
            spins += 1
            if spins > _HOT_SPINS:
                time.sleep(_SLEEP_S)
                if wi < len(plan):
                    # chunks left to publish and every sleep here means
                    # a reader has not acked the previous one: ring
                    # BACKPRESSURE (the watchdog's shm_backpressure
                    # rule reads the counter's slope). Reader-side
                    # waits (wi done, peers not published) stay out —
                    # they are the PEER's problem, named by critpath.
                    wstall_s += _SLEEP_S
                now = time.perf_counter()
                if now - last_probe > _PROBE_PERIOD_S:
                    last_probe = now
                    dead = _peer_loss_probe(
                        f"shm wire exchange (channel {channel}, "
                        f"round {rnd}): peer silent")
                    if dead is not None:
                        raise dead
                if deadline is not None and now - t0 > deadline:
                    fdeadline.raise_deadline(
                        f"shm wire exchange (channel {channel}, round "
                        f"{rnd}): a peer never published/consumed its "
                        f"frame", fatal=True)
        self._wseq[channel] += len(plan)
        self._t_rounds.inc()
        self._t_bytes.inc(len(blob))
        if wstall_s > 0.0:
            self.writer_stall_s += wstall_s
            self._t_wstall.inc(wstall_s)
        out: List[bytes] = []
        for r in range(self.nprocs):
            out.append(blob if r == self.rank
                       else bytes(rstate[r][0]))
        return out

    # -- diagnostics ---------------------------------------------------------

    def stats(self) -> dict:
        return {"token": self.token, "rank": self.rank,
                "nprocs": self.nprocs, "channels": self.channels,
                "cap_bytes": self.cap,
                "rounds": [int(r) for r in self._round],
                "writer_stall_s": round(self.writer_stall_s, 6),
                "frame_hw_bytes": self.frame_hw_bytes}

    def mem_bytes(self) -> dict:
        """Ledger probe (telemetry/accounting.py): this process's shm
        footprint — the segments it OWNS (created, counted once
        process-wide) vs the peer segments it merely maps (shared
        pages, owned elsewhere), plus the frame high-watermark the
        occupancy gauge tracks."""
        return {"segment_bytes": len(self._own) * self._size,
                "peer_mapped_bytes": len(self._peer) * self._size,
                "frame_hw_bytes": self.frame_hw_bytes}
