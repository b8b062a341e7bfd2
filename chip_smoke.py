#!/usr/bin/env python3
"""Does the system still start on the chip? One process, no JAX children.

Trains the WordEmbedding app (the BASELINE.json north star) twice through
its normal entry points — ``Option.parse_args``,
``DistributedWordEmbedding(opt).run()``, ``.close()``, the three calls
``models.wordembedding.distributed.main`` makes — at the width the
benchmark's WordEmbedding cells use: vocabulary 100,000, ``-size 128 -negative 5 -window 5
-use_adagrad 1 -pair_batch 8192 -min_count 1``, on a corpus generated from
a seed (four blocks of 130,000 words). Leg ``device_plane`` moves block
rows through ``device_fetch_rows`` / ``device_apply_rows`` (XLA gather,
Pallas scatter); leg ``device_pairs`` runs the fused program over the
tables' storage. Both export through the host-plane ``pull_embeddings``.
It uses however many chips it sees.

After each leg, while its tables are still up: the saved vectors must be
finite, of the expected shape and equal to the table's raw storage; every
table must sit on the Pallas row kernels, compiled not interpreted, with a
shard on every device; and one ``device_apply_rows`` on 1,000 rows must
match a numpy oracle bit for bit.

Exit code 0 and, as the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
only when JAX runs on a TPU and every check held. ``--rehearsal [N]`` runs
the same control flow at a tiny vocabulary on N virtual CPU devices; its
last line says REHEARSAL and is never the pass line.
"""

from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

SIZE, NEGATIVE, WINDOW, PAIR_BATCH = 128, 5, 5, 8192
TOPIC_WORDS = 100       # words that share contexts; a multiple of SENT_LEN
SENT_LEN = 20
BLOCKS = 4
#: the loss at zero output vectors: every one of the 1+K sigmoids at 0.5
LOSS_CEILING = (1 + NEGATIVE) * math.log(2.0)
ORACLE_ROWS = 1000

LEGS = (("device_plane", ["-device_plane", "1", "-is_pipeline", "0"]),
        ("device_pairs", ["-device_pairs", "1", "-is_pipeline", "0"]))


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def write_corpus(path: str, vocab: int, sentences: int, seed: int) -> None:
    """``sentences`` lines of SENT_LEN words over exactly ``vocab`` distinct
    words. Words come in topics of TOPIC_WORDS that only ever share a
    sentence with each other, topics and words within a topic are
    Zipf-distributed, and every word appears at least once — so there is
    structure to learn and the average pair loss must fall below the
    zero-vector ceiling."""
    import numpy as np
    rng = np.random.default_rng(seed)
    topics = vocab // TOPIC_WORDS

    def zipf(n):
        p = 1.0 / np.arange(1, n + 1)
        return p / p.sum()

    every_word = np.arange(vocab).reshape(-1, SENT_LEN)
    n_rand = sentences - len(every_word)
    topic = rng.choice(topics, n_rand, p=zipf(topics))
    within = rng.choice(TOPIC_WORDS, (n_rand, SENT_LEN), p=zipf(TOPIC_WORDS))
    ids = np.concatenate([every_word, topic[:, None] * TOPIC_WORDS + within])
    ids = ids[rng.permutation(len(ids))]
    words = np.char.add("w", np.arange(vocab).astype(str))
    with open(path, "w") as f:
        for row in ids:
            f.write(" ".join(words[row]) + "\n")


def read_vectors(path: str, vocab: int):
    """The word2vec binary file ``save_embeddings`` wrote -> (V, SIZE)."""
    import numpy as np
    with open(path, "rb") as f:
        buf = f.read()
    pos = buf.index(b"\n") + 1
    header = buf[:pos].split()
    check([int(x) for x in header] == [vocab, SIZE],
          f"vector file header {header} is not {vocab} x {SIZE}")
    out = np.empty((vocab, SIZE), np.float32)
    for i in range(vocab):
        pos = buf.index(b" ", pos) + 1      # past the word
        out[i] = np.frombuffer(buf, np.float32, SIZE, pos)
        pos += 4 * SIZE + 1                 # the row and its newline
    check(pos == len(buf), "vector file has trailing bytes")
    return out


def inspect_tables(we, devices) -> None:
    """Print where each table lives and which row path it takes; fail on
    the silent slow paths."""
    from multiverso_tpu.ops import rows as row_ops
    comm = we.comm
    tables = {"input": comm.input_table, "output": comm.output_table,
              "input_g2": comm.ie_g2_table, "output_g2": comm.eo_g2_table}
    interpret = row_ops._interpret()
    for name, table in tables.items():
        data = table.server().state["data"]
        pallas = row_ops.use_pallas(data)
        per_dev = {s.device.id: s.data.nbytes for s in data.addressable_shards}
        print(f"  table {name}: storage {tuple(data.shape)} {data.dtype} "
              f"use_pallas={pallas} interpret={interpret} "
              f"shard_bytes_per_device={per_dev}")
        if devices[0].platform == "tpu":
            check(pallas, f"table {name}: use_pallas is False on a TPU")
            check(not interpret, "ops.rows._interpret() is True on a TPU")
        missing = [d.id for d in devices if not per_dev.get(d.id)]
        check(not missing, f"table {name}: devices {missing} hold no shard")
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"  device {d.id}: bytes_in_use={stats.get('bytes_in_use')} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def oracle_round(we, vocab: int, seed: int) -> None:
    """device_fetch_rows / device_apply_rows / host GetRows on ORACLE_ROWS
    random rows against numpy, bit for bit (the += updater is one f32 add)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    table = we.comm.input_table
    srv = table.server()
    raw = srv.raw().copy()
    ids = rng.choice(vocab, min(ORACLE_ROWS, vocab), replace=False).astype(
        np.int32)
    before = np.asarray(srv.device_fetch_rows(ids))
    check(np.array_equal(before, raw[ids]),
          "device_fetch_rows disagrees with the table's raw storage")
    delta = rng.standard_normal((len(ids), SIZE)).astype(np.float32)
    srv.device_apply_rows(ids, delta)
    check(np.array_equal(table.GetRows(ids), before + delta),
          "device_apply_rows + GetRows disagrees with the numpy oracle")
    raw[ids] = before + delta
    check(np.array_equal(srv.raw(), raw),
          "device_apply_rows touched rows outside its id set")
    print(f"  oracle: fetch / apply / get on {len(ids)} rows match numpy")


def run_leg(name, extra, corpus, workdir, vocab, block_words, devices):
    import numpy as np

    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    from multiverso_tpu.models.wordembedding.option import Option
    out = os.path.join(workdir, f"vectors_{name}.bin")
    opt = Option.parse_args(
        ["-train_file", corpus, "-output", out, "-binary", "1",
         "-size", str(SIZE), "-negative", str(NEGATIVE),
         "-window", str(WINDOW), "-use_adagrad", "1",
         "-pair_batch", str(PAIR_BATCH), "-min_count", "1",
         "-data_block_size", str(8 * block_words)] + extra)
    t0 = time.perf_counter()
    we = DistributedWordEmbedding(opt)
    try:
        avg_loss = we.run()
        secs = time.perf_counter() - t0
        pairs = we.total_pairs
        print(f"leg {name}: {pairs} pairs trained, average pair loss "
              f"{avg_loss:.4f} (ceiling {LOSS_CEILING:.4f}), "
              f"vocabulary {we.dictionary.Size()}, {secs:.1f} s")
        check(we.dictionary.Size() == vocab,
              f"vocabulary is {we.dictionary.Size()}, not {vocab}")
        check(pairs > 0, "trained zero pairs")
        check(math.isfinite(avg_loss), f"average pair loss {avg_loss}")
        check(avg_loss < LOSS_CEILING,
              f"average pair loss {avg_loss:.4f} is not below the "
              f"zero-vector ceiling {LOSS_CEILING:.4f}")
        inspect_tables(we, devices)
        saved = read_vectors(out, vocab)
        check(bool(np.isfinite(saved).all()), "saved vectors are not finite")
        check(np.array_equal(saved, we.comm.input_table.server().raw()),
              "saved vectors differ from the input table's storage")
        check(float(np.abs(saved).max()) > 1.0 / SIZE,
              "saved vectors never left their initial range")
        oracle_round(we, vocab, seed=7)
    finally:
        we.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", nargs="?", const=1, type=int, default=0,
                    metavar="N", help="tiny run on N virtual CPU devices")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    if not os.path.isdir(os.path.join(HERE, "multiverso_tpu")):
        print(f"FAIL: no multiverso_tpu package beside {__file__}")
        return 2
    sys.path.insert(0, HERE)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={args.rehearsal}").strip()
    import jax

    from multiverso_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    cached_before = len(os.listdir(cache_dir)) if os.path.isdir(
        cache_dir) else 0
    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.update([name]))
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"FAIL: jax found no backend: {exc}")
        return 1
    dev = devices[0]
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"{len(devices)} device(s)")
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = "absent"
    print("versions " + " ".join(f"{k}={v}" for k, v in versions.items()))
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"FAIL: platform is {dev.platform!r}, not 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              "--rehearsal is the CPU mode")
        return 1
    print(f"compile cache {cache_dir} ({cached_before} entries at start)")

    from multiverso_tpu import native
    had_lib = os.path.exists(
        os.path.join(HERE, "native", "libmultiverso_tpu.so"))
    handle = native.lib()
    print("native library: "
          + ("found built" if had_lib else "built this run") + ", "
          + (f"loaded from {handle._name}" if handle else "NOT LOADED"))

    vocab = 2_000 if args.rehearsal else 100_000
    block_words = 13_000 if args.rehearsal else 130_000
    failures = []
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        check(handle is not None, "the native library is not loaded")
        corpus = os.path.join(workdir, "corpus.txt")
        write_corpus(corpus, vocab, BLOCKS * block_words // SENT_LEN, seed=1)
        print(f"corpus: {vocab} distinct words, {BLOCKS * block_words} words, "
              f"{BLOCKS} blocks of {block_words}")
        for name, extra in LEGS:
            try:
                run_leg(name, extra, corpus, workdir, vocab, block_words,
                        devices)
            except Exception as exc:
                traceback.print_exc()
                failures.append(f"leg {name}: {exc!r}")
    except SmokeFailure as exc:
        failures.append(str(exc))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    requests = events["/jax/compilation_cache/compile_requests_use_cache"]
    hits = events["/jax/compilation_cache/cache_hits"]
    print(f"compiles: {requests} requests, {hits} served from the cache, "
          f"{requests - hits} compiled")
    print(f"wall {time.perf_counter() - t_start:.1f} s "
          f"({'cold' if not cached_before else 'warm'} compile cache)")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    if args.rehearsal:
        print("REHEARSAL")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
