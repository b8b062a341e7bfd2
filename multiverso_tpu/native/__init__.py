"""Native runtime loader (ctypes over native/libmultiverso_tpu.so).

The C++ runtime mirrors the reference's native core (actors, store,
updaters, BSP sync, c_api — see native/) and additionally exports fast
text parsers used by the python data pipelines. A source checkout builds
the library with ``make`` (a no-op when it is up to date) every time a
process first asks for it, so what loads is never older than
``native/src``; an installed wheel loads the copy built into it. Without
a library the callers fall back to pure python, and :func:`lib` says so
once.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from multiverso_tpu.utils.log import Log

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
# installed wheels carry the library as package data right here (built by
# setup.py); source checkouts build it in the repo's native/ dir
_PKG_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "libmultiverso_tpu.so")
_REPO_LIB_PATH = os.path.join(_NATIVE_DIR, "libmultiverso_tpu.so")

_WITHOUT = ("running without it: python libsvm parser and tokenizer, no "
            "native kv index or crc32c")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> Optional[str]:
    """Run the Makefile. None on success, else why it failed."""
    import fcntl
    try:
        # two processes of one job can both be first to ask (2-process
        # worlds start together): serialize their makes on a file lock
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            result = subprocess.run(
                ["make", "-C", _NATIVE_DIR, "-j4", "libmultiverso_tpu.so"],
                capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as exc:
        return repr(exc)
    if result.returncode != 0:
        return result.stderr[-4000:]
    return None


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None when unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
            # source checkout: make decides whether a library lying in
            # native/ is current — a stale one is rebuilt, never loaded
            err = _build()
            if err is not None:
                Log.Error("native runtime build failed (make -C %s); %s\n%s",
                          _NATIVE_DIR, _WITHOUT, err)
                return None
            path = _REPO_LIB_PATH
        elif os.path.exists(_PKG_LIB_PATH):
            path = _PKG_LIB_PATH
        else:
            Log.Info("native runtime not installed; %s", _WITHOUT)
            return None
        handle = ctypes.CDLL(path)
        _configure_signatures(handle)
        _lib = handle
        return _lib


def _configure_signatures(h: ctypes.CDLL) -> None:
    i64 = ctypes.c_int64
    h.MV_CountLibsvm.restype = i64
    h.MV_CountLibsvm.argtypes = [ctypes.c_char_p, i64,
                                 ctypes.POINTER(i64), ctypes.POINTER(i64)]
    h.MV_ParseLibsvm.restype = i64
    h.MV_ParseLibsvm.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_int,
        np.ctypeslib.ndpointer(np.int32), np.ctypeslib.ndpointer(np.float32),
        np.ctypeslib.ndpointer(np.int64), np.ctypeslib.ndpointer(np.int64),
        np.ctypeslib.ndpointer(np.float32)]
    h.MV_BuildVocabHash.restype = i64
    h.MV_BuildVocabHash.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int64), i64]
    h.MV_TokenizeToIds.restype = i64
    h.MV_TokenizeToIds.argtypes = [
        ctypes.c_char_p, i64, ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int32, np.ctypeslib.ndpointer(np.int64), i64,
        np.ctypeslib.ndpointer(np.int32), i64]
    h.MV_TokenizeLinesToIds.restype = i64
    h.MV_TokenizeLinesToIds.argtypes = h.MV_TokenizeToIds.argtypes
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    h.MV_HostStorePoolStats.argtypes = [
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
    # the versioned seal's hardware CRC32C (crc32c.cc)
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    h.MV_Crc32c.restype = ctypes.c_uint32
    h.MV_Crc32c.argtypes = [u8p, i64, ctypes.c_uint32]
    h.MV_Crc32cHw.restype = ctypes.c_int
    h.MV_Crc32cHw.argtypes = []
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    h.MV_KvIndexNew.restype = ctypes.c_void_p
    h.MV_KvIndexNew.argtypes = [i64]
    h.MV_KvIndexFree.argtypes = [ctypes.c_void_p]
    h.MV_KvIndexSize.restype = i64
    h.MV_KvIndexSize.argtypes = [ctypes.c_void_p]
    h.MV_KvIndexCapacity.restype = i64
    h.MV_KvIndexCapacity.argtypes = [ctypes.c_void_p]
    h.MV_KvIndexLookup.argtypes = [ctypes.c_void_p, i64p, i64, i32p]
    h.MV_KvIndexInsert.argtypes = [ctypes.c_void_p, i64p, i64, i32p]
    h.MV_KvIndexItems.argtypes = [ctypes.c_void_p, i64p, i32p]
    h.MV_KvIndexSetItems.argtypes = [ctypes.c_void_p, i64p, i32p, i64]


def parse_libsvm(text: bytes, weighted: bool = False
                 ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray,
                                     np.ndarray, np.ndarray]]:
    """Fast parse of a libsvm text chunk.

    -> (labels i32, weights f32, offsets i64[n+1], keys i64, values f32)
    or None when the native lib is unavailable.
    """
    h = lib()
    if h is None:
        return None
    n_samples = ctypes.c_int64()
    n_entries = ctypes.c_int64()
    h.MV_CountLibsvm(text, len(text), ctypes.byref(n_samples),
                     ctypes.byref(n_entries))
    ns, ne = n_samples.value, n_entries.value
    labels = np.empty(max(ns, 1), np.int32)
    weights = np.empty(max(ns, 1), np.float32)
    offsets = np.zeros(ns + 1, np.int64)
    keys = np.empty(max(ne, 1), np.int64)
    values = np.empty(max(ne, 1), np.float32)
    parsed = h.MV_ParseLibsvm(text, len(text), int(weighted), labels, weights,
                              offsets, keys, values)
    if parsed < 0:
        raise ValueError("native libsvm parser: malformed input")
    if parsed != ns:
        return None
    return labels[:ns], weights[:ns], offsets, keys[:ne], values[:ne]


class VocabTokenizer:
    """Native tokenize + vocab lookup (native/src/reader.cc
    MV_BuildVocabHash / MV_TokenizeToIds): builds an open-addressing word
    hash once, then maps whitespace-tokenized text to word ids in C++ —
    the reference WordEmbedding reader's hot loop (reader.cpp tokenize +
    Dictionary::GetWordIdx per token) off the python interpreter.
    Out-of-vocab tokens come back as -1 (caller filters)."""

    def __init__(self, handle: ctypes.CDLL, words):
        self._h = handle
        self._n = len(words)
        # the native side takes a plain ``const char**``: one
        # NUL-separated blob and a numpy array of addresses into it serve,
        # with no bytes object and no ctypes slot per word (at 2.1 M words
        # those were 2 s of a 2.4 s build)
        blob = np.frombuffer(("\0".join(words) + "\0").encode("utf-8"),
                             np.uint8)
        ends = np.flatnonzero(blob == 0)
        if len(ends) != self._n:
            # a word holds a NUL itself (C reads it up to there, as it
            # always did): take the ends from the words' own lengths
            encoded = [w.encode("utf-8") for w in words]
            blob = np.frombuffer(b"\0".join(encoded) + b"\0", np.uint8)
            ends = np.cumsum(np.fromiter(map(len, encoded), np.int64,
                                         self._n) + 1) - 1
        self._blob = blob       # the array keeps the bytes alive
        starts = np.concatenate(([0], ends[:-1] + 1))
        self._addrs = (starts + self._blob.ctypes.data).astype(np.uint64)
        self._words = self._addrs.ctypes.data_as(
            ctypes.POINTER(ctypes.c_char_p))
        cap = 8
        while cap < 2 * self._n + 1:
            cap <<= 1
        self._table = np.empty(cap, np.int64)
        self._cap = cap
        handle.MV_BuildVocabHash(self._words, self._n, self._table, cap)

    @classmethod
    def create(cls, words) -> Optional["VocabTokenizer"]:
        handle = lib()
        if handle is None or not len(words):
            return None
        return cls(handle, list(words))

    def tokenize(self, text: bytes, max_ids: int) -> np.ndarray:
        """Word ids of ``text`` in order, -1 for out-of-vocab tokens."""
        out = np.empty(max(max_ids, 1), np.int32)
        n = self._h.MV_TokenizeToIds(text, len(text), self._words, self._n,
                                     self._table, self._cap, out,
                                     len(out))
        return out[:n]

    def tokenize_lines(self, text: bytes) -> np.ndarray:
        """Word ids of a multi-line chunk with -2 sentinels at newlines —
        one foreign call per chunk (per-line calls cost more than the
        tokenizing). -1 still marks out-of-vocab."""
        out = np.empty(len(text) + 2, np.int32)
        n = self._h.MV_TokenizeLinesToIds(text, len(text), self._words,
                                          self._n, self._table, self._cap,
                                          out, len(out))
        return out[:n]


class KvIndex:
    """Native int64 -> int32 slot index (native/src/kv_index.cc): linear
    probing with the splitmix64 finalizer. Batch insert assigns slots in
    BATCH ORDER (the KV multihost contract: identical key streams produce
    identical indices on every host). Single-writer."""

    def __init__(self, handle: ctypes.CDLL, cap_hint: int):
        self._h = handle
        self._ptr = handle.MV_KvIndexNew(cap_hint)
        if not self._ptr:
            raise MemoryError("MV_KvIndexNew failed")

    @classmethod
    def create(cls, cap_hint: int = 1024) -> Optional["KvIndex"]:
        handle = lib()
        if handle is None:
            return None
        return cls(handle, cap_hint)

    def __del__(self):
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr:
            self._h.MV_KvIndexFree(ptr)

    def __len__(self) -> int:
        return int(self._h.MV_KvIndexSize(self._ptr))

    def capacity(self) -> int:
        """Allocated probing-table slots (>= len; the load-factor
        headroom the accounting ledger must count)."""
        return int(self._h.MV_KvIndexCapacity(self._ptr))

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.int64)
        out = np.empty(len(keys), np.int32)
        self._h.MV_KvIndexLookup(self._ptr, keys, len(keys), out)
        return out

    def insert(self, keys: np.ndarray) -> np.ndarray:
        """Missing keys get size++ in batch order; returns all slots."""
        keys = np.ascontiguousarray(keys, np.int64)
        out = np.empty(len(keys), np.int32)
        self._h.MV_KvIndexInsert(self._ptr, keys, len(keys), out)
        return out

    def items(self):
        """-> (keys i64[n], slots i32[n]), arbitrary order."""
        n = len(self)
        keys = np.empty(max(n, 1), np.int64)
        slots = np.empty(max(n, 1), np.int32)
        self._h.MV_KvIndexItems(self._ptr, keys, slots)
        return keys[:n], slots[:n]

    def set_items(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """Replace contents (keys must be unique; slots must be a
        permutation of 0..n-1 — the native side tracks one next-slot
        counter, so gapped slot sets would make items() return
        uninitialized tail entries)."""
        keys = np.ascontiguousarray(keys, np.int64)
        slots = np.ascontiguousarray(slots, np.int32)
        if len(keys) != len(slots):
            raise ValueError("keys/slots length mismatch")
        if len(slots) and not np.array_equal(
                np.sort(slots), np.arange(len(slots), dtype=np.int32)):
            raise ValueError("set_items slots must be a permutation of "
                             "0..n-1 (native used counter is next-slot)")
        self._h.MV_KvIndexSetItems(self._ptr, keys, slots, len(keys))


def crc32c_fn():
    """The native CRC32C entry point (``MV_Crc32c(data_u8, n, seed)``
    -> u32, zlib.crc32-style chaining), or None when the native lib is
    unavailable. Returned as the raw callable so
    the seal's hot loop (parallel/seal.py) pays the capability probe
    ONCE, not per frame. This module stays jax-free — the replica
    plane's reader processes verify fan-out seals through it."""
    h = lib()
    return None if h is None else h.MV_Crc32c


_charp_fn = None


def crc32c_charp_fn():
    """MV_Crc32c bound with a ``c_char_p`` first argument — the FAST
    binding for ``bytes`` inputs (the sealed-frame hot path): ctypes
    passes a bytes object as char* for ~2.7us/call vs ~6.5us through
    the ndpointer conversion (measured; the delta is pure argument
    marshalling). Lives on a second CDLL handle of the same library so
    the generic ndpointer binding (memoryviews, numpy views — the shm
    wire's streaming chunks) keeps working. None when unavailable."""
    global _charp_fn
    if _charp_fn is None:
        if lib() is None:
            return None
        fn = ctypes.CDLL(lib()._name).MV_Crc32c
        fn.restype = ctypes.c_uint32
        fn.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32]
        # mv-lint: ok(cross-domain-state): idempotent lazy init — every racing thread binds the same symbol of the same library; a double-store of an equivalent callable is benign
        _charp_fn = fn
    return _charp_fn


def crc32c(data, value: int = 0) -> Optional[int]:
    """CRC32C of ``data`` chained from ``value`` (the zlib.crc32 call
    shape), or None when the native runtime is unavailable."""
    fn = crc32c_fn()
    if fn is None:
        return None
    arr = np.frombuffer(data, np.uint8)    # zero-copy for bytes/views
    return int(fn(arr, arr.size, value & 0xFFFFFFFF))


def pool_stats() -> Optional[dict]:
    """The native host-store pool's dispatch tallies (round 13
    watchdog plane): {parallel_runs, inline_busy, inline_small,
    pool_threads}. ``inline_busy`` counts applies that found the pool
    owned by another engine shard and ran their slices inline — the
    saturation signal the apply-pool watchdog rule alerts on. None
    when the native runtime is unavailable."""
    handle = lib()
    if handle is None:
        return None
    out = np.zeros(4, np.int64)
    handle.MV_HostStorePoolStats(out)
    return {"parallel_runs": int(out[0]), "inline_busy": int(out[1]),
            "inline_small": int(out[2]), "pool_threads": int(out[3])}
