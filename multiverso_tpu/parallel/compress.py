"""Tagged blob-compression codecs for the hot byte paths, jax-free.

PR 14's hardware-CRC32C seal took the checksum off the wire's critical
path (~7 GB/s); bytes SHIPPED are now the dominant cost on the fan-out
and cross-proc paths. This module is the reproduction of the reference's
compression layer (include/multiverso/util/quantization_util.h — per-blob
filters applied before the wire) recast in the repo's negotiation idiom:
every compressed array rides an ENVELOPE whose first byte is a codec
tag, exactly like the seal's algorithm trailer byte
(:mod:`multiverso_tpu.parallel.seal`), so mixed fleets roll forward
safely — readers upgrade first, and a reader that meets a tag from the
reserved range it does not know fails LOUDLY as "written by a newer
writer" instead of decoding garbage.

Codecs (tag space ``0xD0..0xDF``, disjoint from the seal's
``0xC0..0xCF`` so a misrouted blob can never verify):

* **raw** (``0xD0``) — identity: dtype/shape header + raw bytes. The
  lossless fallback every other codec's encoder may pick when it would
  not win.
* **int8 rows** (``0xD1``) — per-row scale quantization, LOSSY: each
  row stores one f32 scale (``max|row| / 127``) plus int8 codes; decode
  is ``q * scale``. ~4x smaller than f32 with max-abs error bounded by
  ``scale/2 <= max|row|/254`` per element. For gradient-shaped delta
  traffic (window Add values, replica delta rows).
* **bf16** (``0xD2``) — round-to-nearest-even truncation of f32 to the
  upper 16 bits, LOSSY: 2x smaller, relative error <= 2**-8. For value
  rows (base payloads, serve frames) where int8's shared row scale is
  too coarse.
* **bitmap-RLE** (``0xD3``) — LOSSLESS run-length coding of a sorted-
  unique non-negative int64 id set (the "rows dirtied since
  prev_version" descriptors in replica/delta.py): the conceptual dirty
  BITMAP's alternating gap/run lengths, varint-coded. Churn-local id
  sets cost ~2 bytes/id instead of 8; a dense "all rows" set collapses
  to a few bytes.

Everything is behind ``-mv_compress`` (default OFF — the wire stays
byte-identical to an uncompressed build), and the LOSSY codecs
additionally require a per-table opt-in via ``-mv_compress_lossy``
(comma-separated table ids, or ``all``), so KV/sparse tables stay
lossless by default. Telemetry: ``compress.pre_bytes.<path>`` /
``compress.post_bytes.<path>`` counters per hot path (``replica`` /
``window`` / ``serve``), served by ``/metrics``.

Lossy determinism contract: decode(encode(x)) is a pure function of the
envelope BYTES — no host state, no float environment dependence beyond
IEEE numpy ops — so every rank/reader that decodes the same blob
reconstructs bit-identical values. The windowed engine leans on this:
the sending rank applies its OWN verbs through the same decode
(sync/server.py materializes its local window), so SPMD replicas never
diverge under quantization.

This module is numpy-only (no jax, no seal import) — it sits on the
replica reader's import path, which must stay jax-free.
"""

from __future__ import annotations

import struct
from typing import Optional, Tuple

import numpy as np

from multiverso_tpu.failsafe.errors import WireCorruption
from multiverso_tpu.utils.configure import (MV_DEFINE_bool,
                                            MV_DEFINE_string,
                                            cached_bool_flag, cached_flag,
                                            cached_str_flag)

MV_DEFINE_bool("mv_compress", False,
               "compress hot-path wire blobs (replica fan-out bundles, "
               "cross-proc delta windows, replica serve frames) with the "
               "tagged codecs in parallel/compress.py; off = identity, "
               "byte-identical wire")
MV_DEFINE_string("mv_compress_lossy", "",
                 "comma-separated table ids (or 'all') whose float "
                 "payloads may ride the LOSSY int8/bf16 codecs; every "
                 "other table stays lossless regardless of -mv_compress")

_U8 = struct.Struct("<B")
_U32 = struct.Struct("<I")
_I64 = struct.Struct("<q")

#: reserved codec-tag space — the seal idiom (seal.py TAG_BASE 0xC0)
#: one nibble up, so the two reserved ranges can never be confused
TAG_BASE = 0xD0
TAG_RAW = 0xD0
TAG_INT8_ROWS = 0xD1
TAG_BF16 = 0xD2
TAG_RLE_IDS = 0xD3

#: telemetry counter names per hot byte path (pre = array bytes offered
#: to a codec, post = envelope bytes that actually shipped)
PATHS = ("replica", "window", "serve")

_enabled_flag = cached_bool_flag("mv_compress", False)
_lossy_raw_flag = cached_str_flag("mv_compress_lossy", "")


def _parse_lossy(raw) -> object:
    s = str(raw).strip().lower()
    if not s:
        return frozenset()
    if s in ("all", "*"):
        return "all"
    return frozenset(p.strip() for p in s.split(",") if p.strip())


#: parsed (cached) form of -mv_compress_lossy — per-payload membership
#: checks must not re-split a string on the fan-out/window hot paths
_lossy_set_flag = cached_flag("mv_compress_lossy", frozenset(),
                              _parse_lossy)


def enabled() -> bool:
    """True when ``-mv_compress`` is on (listener-cached read)."""
    return _enabled_flag()


def lossy_opted(table_id) -> bool:
    """True when ``table_id`` opted into the lossy codecs via
    ``-mv_compress_lossy`` (per-table contract: lossless by default)."""
    spec = _lossy_set_flag()
    return spec == "all" or str(table_id) in spec


def config_token() -> Tuple[bool, str]:
    """Hashable stamp of the live codec configuration — cache keys that
    must invalidate when an operator flips a flag mid-run (the
    publisher's content-addressed encode cache)."""
    return (_enabled_flag(), _lossy_raw_flag())


def _note(path: str, pre: int, post: int) -> None:
    """Per-path byte accounting (wire.py's per-blob registry-lookup
    idiom — one dict probe per blob, not per element; NULL instrument
    when telemetry is off)."""
    from multiverso_tpu.telemetry import metrics as _tmetrics
    _tmetrics.counter("compress.pre_bytes." + path).inc(pre)
    _tmetrics.counter("compress.post_bytes." + path).inc(post)


# -- envelope array header ---------------------------------------------------
#
# Same layout as the flat value grammar's array header (flat.py) —
# u8 dtype-str length, dtype str, u8 ndim, i64 dims — duplicated here
# (~15 lines) so this module stays import-free of the codec layers that
# import IT (flat.py speaks CompressedArray via its 'q' tag).


def _pack_header(parts: list, dtype: np.dtype, shape) -> None:
    ds = dtype.str.encode("ascii")
    parts.append(_U8.pack(len(ds)))
    parts.append(ds)
    parts.append(_U8.pack(len(shape)))
    for dim in shape:
        parts.append(_I64.pack(int(dim)))


def _unpack_header(blob, pos: int):
    (dlen,) = _U8.unpack_from(blob, pos)
    pos += 1
    dtype = np.dtype(bytes(blob[pos:pos + dlen]).decode("ascii"))
    pos += dlen
    (ndim,) = _U8.unpack_from(blob, pos)
    pos += 1
    shape = []
    for _ in range(ndim):
        shape.append(_I64.unpack_from(blob, pos)[0])
        pos += 8
    return dtype, tuple(shape), pos


def _wire_contig(arr: np.ndarray) -> np.ndarray:
    """Contiguous little-endian form for the envelope (flat.py's
    ``_norm_array`` rule)."""
    arr = np.asarray(arr)
    if arr.ndim:                # ascontiguousarray promotes 0-d to 1-d
        arr = np.ascontiguousarray(arr)
    if arr.dtype.byteorder == ">":
        arr = arr.astype(arr.dtype.newbyteorder("<"))
    return arr


# -- codecs ------------------------------------------------------------------


def encode_raw(arr: np.ndarray) -> bytes:
    """Identity envelope (lossless): header + raw bytes."""
    arr = _wire_contig(np.asarray(arr))
    parts: list = [_U8.pack(TAG_RAW)]
    _pack_header(parts, arr.dtype, arr.shape)
    if arr.size:
        parts.append(arr.tobytes())
    return b"".join(parts)


def _rows2d(arr: np.ndarray) -> np.ndarray:
    return arr.reshape(1, -1) if arr.ndim == 1 else arr


def encode_int8_rows(arr: np.ndarray) -> bytes:
    """Per-row-scale int8 quantization (LOSSY). ``arr`` is 1-D or 2-D
    float32/float64; a 1-D array quantizes as one row. Per element the
    reconstruction error is bounded by ``scale/2`` where ``scale =
    max|row|/127`` — an all-zero (or empty) row stores scale 0 and
    decodes exactly."""
    arr = _wire_contig(np.asarray(arr))
    if arr.ndim not in (1, 2) or arr.dtype.kind != "f":
        raise ValueError(
            f"int8 row codec wants a 1-D/2-D float array, got "
            f"{arr.dtype} ndim={arr.ndim}")
    rows = _rows2d(arr)
    if rows.size:
        maxabs = np.max(np.abs(rows), axis=1)
    else:
        maxabs = np.zeros(rows.shape[0], rows.dtype)
    scale = (maxabs / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, np.float32(1.0)).astype(rows.dtype)
    q = np.clip(np.rint(rows / safe[:, None]), -127, 127).astype(np.int8)
    parts: list = [_U8.pack(TAG_INT8_ROWS)]
    _pack_header(parts, arr.dtype, arr.shape)
    parts.append(scale.tobytes())
    parts.append(q.tobytes())
    return b"".join(parts)


def encode_bf16(arr: np.ndarray) -> bytes:
    """bfloat16 truncation of a float32 array (LOSSY, round-to-nearest-
    even): keeps the f32 exponent, drops 16 mantissa bits — relative
    error <= 2**-8. NaN/Inf survive (a NaN's payload is forced non-zero
    so rounding can never turn it into Inf). No ml_dtypes dependency:
    the wire stores raw u16 upper halves."""
    arr = _wire_contig(np.asarray(arr))
    if arr.dtype != np.float32:
        raise ValueError(f"bf16 codec wants float32, got {arr.dtype}")
    bits = arr.view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                          & np.uint32(1))
    special = (bits & np.uint32(0x7F800000)) == np.uint32(0x7F800000)
    hi = np.where(special, bits >> np.uint32(16),
                  rounded >> np.uint32(16)).astype(np.uint16)
    is_nan = special & ((bits & np.uint32(0x007FFFFF)) != 0)
    hi = np.where(is_nan, hi | np.uint16(1), hi)
    parts: list = [_U8.pack(TAG_BF16)]
    _pack_header(parts, arr.dtype, arr.shape)
    parts.append(np.ascontiguousarray(hi).tobytes())
    return b"".join(parts)


def _varint(out: bytearray, v: int) -> None:
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)


def _read_varint(blob, pos: int):
    shift = 0
    v = 0
    while True:
        b = blob[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, pos
        shift += 7


def rle_encodable(ids: np.ndarray) -> bool:
    """True when ``ids`` meets the bitmap-RLE contract: 1-D int64,
    sorted strictly increasing, non-negative (what TableJournal.drain /
    merge_descriptors emit by construction — np.nonzero/np.unique)."""
    if not isinstance(ids, np.ndarray) or ids.dtype != np.int64 \
            or ids.ndim != 1:
        return False
    if ids.size == 0:
        return True
    if int(ids[0]) < 0:
        return False
    return bool(np.all(np.diff(ids) > 0))


def encode_rle_ids(ids: np.ndarray) -> bytes:
    """Bitmap-RLE envelope (LOSSLESS) of a sorted-unique non-negative
    int64 id set: the runs of the conceptual dirty bitmap, coded as
    alternating varint (gap, run-length) pairs. Callers gate on
    :func:`rle_encodable`."""
    ids = np.asarray(ids)
    out = bytearray(_U8.pack(TAG_RLE_IDS))
    _varint(out, int(ids.size))
    if ids.size:
        brk = np.flatnonzero(np.diff(ids) != 1)
        starts = np.concatenate(([int(ids[0])],
                                 ids[brk + 1])).astype(np.int64)
        ends = np.concatenate((ids[brk],
                               [int(ids[-1])])).astype(np.int64)
        prev_end = -1
        for s, e in zip(starts.tolist(), ends.tolist()):
            _varint(out, s - prev_end - 1)      # zeros gap
            _varint(out, e - s + 1)             # ones run
            prev_end = e
    return bytes(out)


def decode_array(blob) -> np.ndarray:
    """Decode one codec envelope back to its array. Deterministic pure
    function of the bytes (the SPMD lossy-consistency contract). A tag
    from the reserved range this build does not know raises the typed
    loud error — the seal's "newer writer" posture."""
    if not len(blob):
        raise WireCorruption("empty compression envelope")
    tag = blob[0]
    if tag == TAG_RAW:
        dtype, shape, pos = _unpack_header(blob, 1)
        count = 1
        for dim in shape:
            count *= dim
        arr = np.frombuffer(blob, dtype, count=count, offset=pos)
        return arr.reshape(shape)
    if tag == TAG_INT8_ROWS:
        dtype, shape, pos = _unpack_header(blob, 1)
        nrows = shape[0] if len(shape) == 2 else 1
        scale = np.frombuffer(blob, np.float32, count=nrows, offset=pos)
        pos += nrows * 4
        count = 1
        for dim in shape:
            count *= dim
        q = np.frombuffer(blob, np.int8, count=count, offset=pos)
        if count == 0:      # reshape(-1) can't infer a dim of size 0
            return np.zeros(shape, dtype)
        out = (q.reshape(nrows, -1).astype(dtype)
               * scale[:, None].astype(dtype))
        return out.reshape(shape)
    if tag == TAG_BF16:
        dtype, shape, pos = _unpack_header(blob, 1)
        count = 1
        for dim in shape:
            count *= dim
        hi = np.frombuffer(blob, np.uint16, count=count, offset=pos)
        out = (hi.astype(np.uint32) << np.uint32(16)).view(np.float32)
        return out.reshape(shape)
    if tag == TAG_RLE_IDS:
        n, pos = _read_varint(blob, 1)
        out = np.empty(n, np.int64)
        filled = 0
        at = 0
        while filled < n:
            gap, pos = _read_varint(blob, pos)
            run, pos = _read_varint(blob, pos)
            start = at + gap
            out[filled:filled + run] = np.arange(start, start + run,
                                                 dtype=np.int64)
            filled += run
            at = start + run
        return out
    if TAG_BASE <= tag <= TAG_BASE + 0x0F:
        raise WireCorruption(
            f"compressed blob carries unknown codec tag {tag:#x} — "
            f"written by a newer writer (upgrade readers before "
            f"writers), or corrupted in the envelope; refusing to parse")
    raise WireCorruption(
        f"not a compression envelope (leading byte {tag:#x})")


class CompressedArray:
    """An ndarray in its tagged-envelope form. Travels through pickle
    (replica fan-out bundles) and through the flat value grammar's
    ``q`` tag (window wire, serve frames); consumers materialize with
    :meth:`decode` — or the flat decoder does it eagerly for them."""

    __slots__ = ("blob",)

    def __init__(self, blob: bytes):
        self.blob = bytes(blob)

    @property
    def nbytes(self) -> int:
        return len(self.blob)

    def decode(self) -> np.ndarray:
        return decode_array(self.blob)

    def __getstate__(self):
        return self.blob

    def __setstate__(self, state):
        self.blob = state

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"CompressedArray({len(self.blob)}B, tag=" \
               f"{self.blob[0]:#x})" if self.blob else "CompressedArray()"


# -- hot-path packers --------------------------------------------------------


def _pack_float(arr: np.ndarray, codec: str) -> Optional[bytes]:
    """Envelope for a float payload array under ``codec`` ('int8' or
    'bf16'); None when the array does not fit the codec or the envelope
    would not win."""
    if not isinstance(arr, np.ndarray) or arr.size == 0:
        return None
    if codec == "int8":
        if arr.ndim not in (1, 2) or arr.dtype.kind != "f":
            return None
        blob = encode_int8_rows(arr)
    else:
        if arr.dtype != np.float32:
            return None
        blob = encode_bf16(arr)
    return blob if len(blob) < arr.nbytes else None


def pack_payload(table_id, payload: dict, path: str = "replica") -> dict:
    """Compress one replica bundle payload's arrays (delta.py grammar):
    ``ids``/``keys`` descriptors ride bitmap-RLE (lossless, whenever it
    wins); ``rows``/``values`` float arrays ride int8 (delta-shaped —
    the payload carries an id/key vector) or bf16 (whole-state value
    rows) ONLY when ``table_id`` opted into lossy. Returns ``payload``
    itself when compression is off or nothing won."""
    if not enabled():
        return payload
    out = None
    pre = post = 0
    for key in ("ids", "keys"):
        v = payload.get(key)
        if isinstance(v, np.ndarray) and v.size and rle_encodable(v):
            blob = encode_rle_ids(v)
            if len(blob) < v.nbytes:
                out = out if out is not None else dict(payload)
                out[key] = CompressedArray(blob)
                pre += v.nbytes
                post += len(blob)
    if lossy_opted(table_id):
        delta_shaped = "ids" in payload or \
            (payload.get("fam") == "kv" and "keys" in payload)
        for key in ("rows", "values"):
            v = payload.get(key)
            blob = _pack_float(v, "int8" if delta_shaped and key != "values"
                               else "bf16")
            if blob is not None:
                out = out if out is not None else dict(payload)
                out[key] = CompressedArray(blob)
                pre += v.nbytes
                post += len(blob)
    if out is None:
        return payload
    _note(path, pre, post)
    return out


def unpack_payload(payload: dict) -> dict:
    """Materialize every CompressedArray in a bundle payload IN PLACE
    (the dict is freshly unpickled — nobody else holds it)."""
    for key, v in payload.items():
        if isinstance(v, CompressedArray):
            payload[key] = v.decode()
    return payload


def pack_window_values(table_id: int, payload: dict) -> dict:
    """Window-path Add compression: quantize a lossy-opted table's
    ``values`` deltas to int8. Returns a NEW payload dict holding a
    CompressedArray (callers persist it on the message, the
    DeferredArray idiom) or ``payload`` unchanged. The sending rank
    must apply its own verbs through :func:`materialize_window` so
    every rank reconstructs the identical dequantized delta."""
    if not enabled() or not lossy_opted(table_id):
        return payload
    blob = _pack_float(payload.get("values"), "int8")
    if blob is None:
        return payload
    v = payload["values"]
    out = dict(payload)
    out["values"] = CompressedArray(blob)
    _note("window", v.nbytes, len(blob))
    return out


def materialize_window(verbs: list) -> list:
    """Replace CompressedArray payload values with their decoded arrays
    across one window's verb records — the sending rank's twin of the
    peers' eager flat decode, sharing :func:`decode_array` so the
    reconstruction is bit-identical on every rank. Payload dicts are
    copied before substitution (the originals stay compressed on their
    messages for a possible re-pack)."""
    out = []
    for rec in verbs:
        kind, tid, payload = rec
        hit = None
        for key, v in payload.items():
            if isinstance(v, CompressedArray):
                hit = hit if hit is not None else dict(payload)
                hit[key] = v.decode()
        out.append((kind, tid, hit) if hit is not None else rec)
    return out


def pack_serve_rows(table_id: int, rows, path: str = "serve"):
    """Serve-frame compression (replica lookup responses): bf16 for a
    lossy-opted table's f32 result rows; anything else ships as-is."""
    if not enabled() or not lossy_opted(table_id):
        return rows
    blob = _pack_float(rows if isinstance(rows, np.ndarray) else None,
                       "bf16")
    if blob is None:
        return rows
    _note(path, rows.nbytes, len(blob))
    return CompressedArray(blob)
