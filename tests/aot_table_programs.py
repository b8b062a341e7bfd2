"""A MatrixTable's row programs lowered and compiled for a DESCRIBED v5e,
with no chip and no table in memory (run as a child process: it makes
``jax.default_backend()`` answer "tpu" so that the programs take the
branches the chip takes — the Pallas row write at 128 lanes, the
dense-run ``cond`` on one shard).

``--dump DIR`` writes the lowered text of the programs the benchmark's
cells compile for tables WITHOUT updater state (``FENCE``; on one chip
with the dense read of each bucket, ``slice_rows.<bucket>``), one file a
program, the Mosaic bodies' debug locations stripped: run it on two
trees and ``diff -r`` the directories (ISSUE 29's fence, PERF.md 6).

``--alias`` compiles the row programs of tables WITH per-worker updater
state (``STATEFUL``) and prints, a program, whether every state leaf is
aliased input to output and how many table-sized ``copy`` instructions
the compiled module holds: tests/test_ops.py asserts on it.

``--tiny`` compiles the row programs of AdaGrad tables of 1 to 5 live rows
at one lane tile (``TINY``) and prints how many Mosaic kernels each holds.

``--read`` compiles the dense read (``MatrixServerTable._slice_rows``) and
the gather it stands in for at ``lm_vocab_steps``' shape (``READ``) and
prints, a program, how many instructions write an array as large as the
rows it returns and how many gathers it holds.

``--pairs`` compiles the WordEmbedding app's ``-device_pairs`` block
program over four shards at ``we_pairs_4c``'s shapes (``PAIRS``) and
prints its collectives, its Mosaic kernels, how many instructions hold a
table's whole rows and its table-sized passes.

``--block`` compiles the same block program on ONE chip at ``we_cbow_hs``'
and ``we_pairs``' shapes (``BLOCK``) and prints its Mosaic kernels, its
``while`` loops (the block's, and one a table whose update walks its
distinct rows in chunks), how many of the four tables are aliased input
to output, its passes over a whole table and the MiB of temporaries the
compiler gives it beside its operands.

``--scan`` compiles the WordEmbedding app's block-round scan program
(``models/wordembedding/distributed.py`` ``_block_scan_fn``) on one chip
at ``we_rows``' shapes (``SCAN``): a state over ``_SPARSE_BYTES``, so the
touched-rows AdaGrad step, and prints its Mosaic kernels, how many of the
state's four matrices are aliased input to output and its passes over a
whole state matrix outside the in-place writes.

``--pooled`` compiles the pooled verbs' two programs
(``tables/pooled.py``: ``_fetch_pooled`` / ``_apply_pooled``, built over
the table's own row programs) at ``POOLED``'s shapes, one chip and four,
and prints their Mosaic kernels, the state leaves the apply aliases, its
table-sized passes and, sharded, the shape of every all-reduce (the
gather's ``psum``, a row a position: the segment sum runs after it).

``--locations`` calls ``compile_cache.enable()`` as an entry point does
and lowers ``FENCE``'s programs: a Mosaic kernel's body travels inside the
program as bytecode WITH its debug locations, which JAX's persistent
cache therefore keys on. It prints, a program that holds a kernel, the
files its bodies name, and whether ``SHIFT``'s program lowered through a
caller 300 lines further down has the same bodies byte for byte.

Prints ``SKIP ...`` and exits 0 where the topology description is missing.
"""

import argparse
import contextlib
import os
import re
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

jax.default_backend = lambda: "tpu"     # the branch the chip runs

from multiverso_tpu.parallel.mesh import MeshContext  # noqa: E402
from multiverso_tpu.tables.matrix_table import MatrixServerTable  # noqa: E402

# (name, rows, cols, chips, updater, workers, id buckets, merged (nb, k, uniq))
FENCE = [
    # mt_host_verbs: Adds of 10,000 rows from 4 workers, merged by window
    ("mt_host_verbs", 9_000_000, 50, 1, "default", 4, (10_240,),
     ((1, 10_000, 16_384), (2, 10_000, 32_768), (4, 10_000, 65_536))),
    # tables_rounds_4c: 65,536 ids a table a round, fewer once unique
    ("tables_rounds_4c", 12_000_000, 128, 4, "default", 1,
     (49_152, 57_344, 65_536), ()),
    ("we_rows", 1_048_500, 128, 1, "default", 1, (524_288, 655_360), ()),
    ("we_pairs", 2_097_100, 128, 1, "default", 1, (8_192,), ()),
    ("sgd_1c", 1_048_500, 128, 1, "sgd", 2, (8_192,), ((2, 4_096, 8_192),)),
    ("sgd_4c", 1_048_500, 128, 4, "sgd", 2, (8_192,), ()),
    ("momentum_1c", 163_840, 2_048, 1, "momentum", 2, (32_768, 163_840),
     ((1, 32_768, 16_384),)),
    ("momentum_4c", 1_048_500, 128, 4, "momentum", 2, (8_192,), ()),
]
STATEFUL = [
    # lm_vocab_steps' shapes (2,048 columns; a whole table in order, and
    # 32,768 positions over fewer distinct rows) at three workers, and a
    # one-tile table on 2x2: what tests/test_ops.py compiles
    ("adagrad_2048_w3", 163_840, 2_048, 1, "adagrad", 3, (163_840,),
     ((1, 32_768, 16_384),)),
    ("adagrad_128_4c_w3", 1_048_500, 128, 4, "adagrad", 3, (8_192,),
     ((2, 4_096, 8_192),)),
]
STATEFUL_MORE = [   # --alias-all: the cell's own worker count, and dcasgd
    ("adagrad_2048_w1", 163_840, 2_048, 1, "adagrad", 1, (16_384, 163_840),
     ((1, 32_768, 16_384),)),
    ("adagrad_2048_w3_touched", 163_840, 2_048, 1, "adagrad", 3, (16_384,),
     ()),
    ("dcasgd_2048_w3", 163_840, 2_048, 1, "dcasgd", 3, (16_384,), ()),
]
TINY = [    # --tiny: rec_bag_steps' smallest tables, 1, 2, 4 and 5 live rows
    # (and the trash row) at one lane tile under AdaGrad: a verb's 2,048
    # positions, and the 8-lane bucket their distinct rows fold into
    (f"adagrad_128_r{rows}", rows, 128, 1, "adagrad", 1, (2_048,),
     ((1, 2_048, 8),)) for rows in (1, 2, 4, 5)]


READ = [   # --read: lm_head's fetch, the whole table in order; a run of a
    # bucket under the table; and each with a run shorter than its bucket
    ("adagrad_2048_w1", 163_840, 2_048, 1, "adagrad", 1, (163_840, 16_384),
     ()),
]


def _devices(chips):
    from jax.experimental import topologies
    return topologies.get_topology_desc("v5e:2x2", "tpu").devices[:chips]


@contextlib.contextmanager
def _no_allocation():
    """While a table is built: zeros and placements are shapes only."""
    zeros, place = jnp.zeros, MeshContext.place
    jnp.zeros = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        tuple(np.atleast_1d(shape)), dtype)
    MeshContext.place = lambda self, a, sharding: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding)
    try:
        yield
    finally:
        jnp.zeros, MeshContext.place = zeros, place


def build(rows, cols, chips, updater, workers):
    ctx = MeshContext.create(_devices(chips))
    zoo = types.SimpleNamespace(mesh_ctx=ctx, num_workers=workers)
    with _no_allocation():
        srv = MatrixServerTable(rows, cols, np.float32, zoo, updater)
    return srv, ctx


def programs(srv, ctx, buckets, merged):
    """(name, jitted, args) of the row programs a table runs."""
    rep = (NamedSharding(ctx.mesh, P()) if ctx.mesh.size > 1
           else SingleDeviceSharding(ctx.mesh.devices.flat[0]))
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=rep)
    opt = {"worker_id": s((), jnp.int32)}
    for k in ("momentum", "learning_rate", "rho", "lambda_"):
        opt[k] = s((), jnp.float32)
    state = srv.state
    update_gather = jax.jit(srv.device_update_gather_rows,
                            donate_argnums=(0,))
    for b in buckets:
        ids, deltas = s((b,), jnp.int32), s((b, srv.num_cols), jnp.float32)
        yield f"update_rows.{b}", srv._update_rows, (state, ids, deltas, opt)
        yield (f"gather_rows.{b}", srv._gather_rows,
               (state["data"], state["aux"], ids))
        yield (f"update_gather_rows.{b}", update_gather,
               (state, ids, deltas, opt))
    for nb, k, uniq in merged:
        yield (f"merged_add_rows.{nb}x{k}.{uniq}", srv._merged_add_rows,
               (state, s((uniq,), jnp.int32),
                s((nb, k, srv.num_cols), jnp.float32),
                s((nb * k,), jnp.int32), opt))


_LOC = re.compile(r'loc\((?:[^()]|\([^()]*\))*\)|#loc\d*( = .*)?')
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _mosaic_text(match, locations=False):
    """A Mosaic kernel's body (base64 MLIR bytecode, file paths and line
    numbers of ops/pallas_rows.py inside) as text, without its locations
    unless asked for them."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        body = ir.Module.parse(base64.b64decode(match.group(1)))
        return "body: " + body.operation.get_asm(
            enable_debug_info=locations)


def dump(directory):
    os.makedirs(directory, exist_ok=True)
    for name, rows, cols, chips, updater, workers, buckets, merged in FENCE:
        srv, ctx = build(rows, cols, chips, updater, workers)
        assert type(srv.state["aux"]) is dict
        progs = list(programs(srv, ctx, buckets, merged))
        if chips == 1:   # the whole-table Add, where a test can place one
            progs.append(("update_full", srv._update_full,
                          (srv.state, srv.state["data"], progs[0][2][3])))
        lowered = [(prog, fn.lower(*args)) for prog, fn, args in progs]
        if chips == 1:   # the dense read, at each bucket the table holds
            scalar = jax.ShapeDtypeStruct(
                (), jnp.int32,
                sharding=SingleDeviceSharding(ctx.mesh.devices.flat[0]))
            lowered += [(f"slice_rows.{b}", srv._slice_rows.lower(
                srv.state["data"], scalar, scalar, bucket=b,
                num_cols=cols)) for b in buckets if b <= srv.block_rows]
        for prog, low in lowered:
            text = low.as_text(debug_info=True)
            text = _BODY.sub(_mosaic_text, _LOC.sub("", text))
            text = "\n".join(ln.rstrip() for ln in text.splitlines()
                             if ln.strip())
            with open(os.path.join(directory, f"{name}.{prog}.txt"),
                      "w") as f:
                f.write(text)
            print("LOWERED", name, prog, len(text))
        print("STATE", name, jax.tree.structure(srv.state),
              sorted(vars(srv)))


_FILE = re.compile(r'"([^"]+\.py)"')
SHIFT = ("we_pairs", "update_rows.8192")


def _bodies(lowered):
    return list(_BODY.finditer(lowered.as_text(debug_info=True)))


def locations():
    """LOC <table> <program> kernels=<n> files=<the files its bodies name>
    SHIFT <table> <program> kernels=<n> identical=<bool>"""
    from multiverso_tpu.utils import compile_cache
    compile_cache.enable()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name, rows, cols, chips, updater, workers, buckets, merged in FENCE:
        srv, ctx = build(rows, cols, chips, updater, workers)
        for prog, fn, args in programs(srv, ctx, buckets, merged):
            bodies = _bodies(fn.lower(*args))
            if not bodies:
                continue
            files = {os.path.relpath(f, repo) if os.path.isabs(f) else f
                     for m in bodies
                     for f in _FILE.findall(_mosaic_text(m, locations=True))}
            print("LOC", name, prog, f"kernels={len(bodies)}",
                  "files=" + ",".join(sorted(files)), flush=True)
    # the traceable verb behind two callers of the same text, the second
    # defined 300 lines further down its file
    name, rows, cols, chips, updater, workers, buckets, merged = next(
        spec for spec in FENCE if spec[0] == SHIFT[0])
    srv, ctx = build(rows, cols, chips, updater, workers)
    args = next(args for prog, _, args in programs(srv, ctx, buckets, merged)
                if prog == SHIFT[1])
    shifted = []
    for down in (0, 300):
        scope = {}
        exec(compile("\n" * down + "def caller(fn, *args):\n"
                     "    return fn(*args)\n", "caller.py", "exec"), scope)
        jax.clear_caches()     # or the first trace answers both
        via = jax.jit(
            lambda *a, c=scope["caller"]: c(srv.device_update_rows, *a),
            donate_argnums=(0,))
        shifted.append([m.group(1) for m in _bodies(via.lower(*args))])
    print("SHIFT", *SHIFT, f"kernels={len(shifted[0])}",
          f"identical={shifted[0] == shifted[1]}", flush=True)


#: what may produce a table-sized array in a row program: the state
#: itself passed along, and the in-place writes
_IN_PLACE = {"parameter", "tuple", "get-tuple-element", "bitcast",
             "conditional", "scatter", "dynamic-update-slice", "custom-call"}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\(?)f32\[([\d,]*)\]\S* ([\w-]+)\((.*)")


def table_sized_passes(hlo, leaf_elems):
    """Instructions of a compiled module that write an array as large as
    a (shard of a) state leaf and are not an in-place row write: a
    ``copy``, or a fusion whose root is neither a scatter nor a
    dynamic-update-slice. Each is a pass over a whole table."""
    roots, body = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", ln)
        if head:
            body = head.group(1)
        elif ln.lstrip().startswith("ROOT ") and body:
            m = _INSTR.match(ln)
            roots[body] = (m.group(4), m.group(5)) if m else ("tuple", "")

    def root_op(op, rest):
        while op == "fusion":    # a fusion's work is its body's root
            called = re.search(r"calls=%(\S+?)[,\s}]", rest + " ").group(1)
            op, rest = roots.get(called, ("?", ""))
        return op
    found, body = [], None
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", ln)
        if head:
            body = head.group(1)
            continue
        m = _INSTR.match(ln)
        if not m or m.group(2) or (body or "").startswith("fused_"):
            continue
        name, _, dims, op, rest = m.groups()
        if not dims or int(np.prod([int(d) for d in dims.split(",")])) \
                < leaf_elems:
            continue
        if root_op(op, rest) not in _IN_PLACE:
            found.append(f"{name} = f32[{dims}] {op}:{root_op(op, rest)}")
    return found


def alias(specs):
    """ALIAS <table> <program> aliased=<n>/<state leaves> passes=<n>"""
    for name, rows, cols, chips, updater, workers, buckets, merged in specs:
        srv, ctx = build(rows, cols, chips, updater, workers)
        leaves = jax.tree.leaves(srv.state)
        leaf_elems = min(int(np.prod(leaf.shape)) for leaf in leaves) \
            // srv.num_servers
        for prog, fn, args in programs(srv, ctx, buckets, merged):
            if prog.startswith("gather_rows"):
                continue
            try:
                hlo = fn.lower(*args).compile().as_text()
            except Exception as exc:  # noqa: BLE001 (e.g. over the HBM)
                print(f"ALIAS {name} {prog} FAILED "
                      f"{str(exc).splitlines()[0][:200]}", flush=True)
                continue
            head = hlo.split("\n", 1)[0]
            aliased = len(re.findall(r"\{\d+\}: \(\d+, \{\}", head))
            passes = table_sized_passes(hlo, leaf_elems)
            print(f"ALIAS {name} {prog} aliased={aliased}/{len(leaves)} "
                  f"passes={len(passes)}", flush=True)
            for ln in passes:
                print("  PASS", ln, flush=True)


def tiny(specs):
    """TINY <table> <program> kernels=<n>: the program compiled for the
    chip, with so many Mosaic kernels in it (the rows' write and the
    history's, where the program writes)."""
    for name, rows, cols, chips, updater, workers, buckets, merged in specs:
        srv, ctx = build(rows, cols, chips, updater, workers)
        for prog, fn, args in programs(srv, ctx, buckets, merged):
            hlo = fn.lower(*args).compile().as_text()
            kernels = len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                                     hlo))
            print(f"TINY {name} {prog} kernels={kernels}", flush=True)


POOLED = [  # --pooled: (name, rows, chips, position rung, bag rung,
    # distinct class): rec_pooled_steps' table 20, its largest verb, and
    # its table 10 on one chip; a one-row table under 2,048 positions (its
    # buckets are over the table: passes say nothing there); a table over
    # four shards whose bags are a quarter of its positions
    ("adagrad_128_pooled_t20", 1_250_000, 1, 229_376, 65_536, 262_144),
    ("adagrad_128_pooled", 95_874, 1, 6_144, 6_144, 8_192),
    ("adagrad_128_pooled_r1", 1, 1, 2_048, 2_048, 8),
    ("adagrad_128_pooled_4c", 1_048_500, 4, 8_192, 2_048, 8_192),
]


def pooled(specs):
    """POOLED <table> fetch_pooled.<positions>x<bags> kernels=<n>
    all_reduce=<shapes>
    POOLED <table> apply_pooled.<positions>x<bags>.<distinct> kernels=<n>
    aliased=<n>/<state leaves> passes=<n>"""
    from multiverso_tpu.tables import pooled as pooled_verbs
    for name, rows, chips, positions, bags, distinct in specs:
        srv, ctx = build(rows, 128, chips, "adagrad", 1)
        programs = pooled_verbs._programs(srv)
        rep = (NamedSharding(ctx.mesh, P()) if chips > 1
               else SingleDeviceSharding(ctx.mesh.devices.flat[0]))
        s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dtype, sharding=rep)
        opt = {k: s((), jnp.float32)
               for k in ("momentum", "learning_rate", "rho", "lambda_")}
        opt["worker_id"] = s((), jnp.int32)
        state, lanes = srv.state, s((positions,), jnp.int32)
        leaves = jax.tree.leaves(state)
        kernels = lambda hlo: len(re.findall(  # noqa: E731
            r"custom_call_target=\"tpu_custom_call\"", hlo))
        hlo = programs.fetch.lower(state["data"], state["aux"], lanes,
                                   lanes, bags=bags).compile().as_text()
        reduces = sorted(set(re.findall(
            r"= (f32\[[0-9,]+\])\S* all-reduce(?:-start)?\(", hlo)))
        print(f"POOLED {name} fetch_pooled.{positions}x{bags} "
              f"kernels={kernels(hlo)} all_reduce={','.join(reduces)}",
              flush=True)
        hlo = programs.apply.lower(
            state, s((distinct,), jnp.int32), s((bags, 128), jnp.float32),
            lanes, lanes, opt).compile().as_text()
        aliased = len(re.findall(r"\{\d+\}: \(\d+, \{\}",
                                 hlo.split("\n", 1)[0]))
        passes = table_sized_passes(hlo, min(
            int(np.prod(leaf.shape)) for leaf in leaves) // srv.num_servers)
        print(f"POOLED {name} apply_pooled.{positions}x{bags}.{distinct} "
              f"kernels={kernels(hlo)} aliased={aliased}/{len(leaves)} "
              f"passes={len(passes)}", flush=True)
        for ln in passes:
            print("  PASS", ln, flush=True)


def read(specs):
    """READ <table> <program> passes=<n> gathers=<n>: instructions of the
    compiled read that write an array as large as the bucket of rows it
    returns (``table_sized_passes`` at that size: the least is one, the
    rows themselves), and ``gather`` instructions."""
    for name, rows, cols, chips, updater, workers, buckets, _ in specs:
        srv, ctx = build(rows, cols, chips, updater, workers)
        data, aux = srv.state["data"], srv.state["aux"]
        one = SingleDeviceSharding(ctx.mesh.devices.flat[0])
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        for b in buckets:
            ids = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
            lowered = [
                (f"slice_rows.{b}", srv._slice_rows.lower(
                    data, scalar, None, bucket=b, num_cols=cols)),
                (f"slice_rows.{b}.under", srv._slice_rows.lower(
                    data, scalar, scalar, bucket=b, num_cols=cols)),
                (f"gather_rows.{b}", srv._gather_rows.lower(data, aux, ids))]
            for prog, low in lowered:
                hlo = low.compile().as_text()
                passes = table_sized_passes(hlo, b * cols)
                gathers = len(re.findall(r"\bgather\(", hlo))
                print(f"READ {name} {prog} passes={len(passes)} "
                      f"gathers={gathers}", flush=True)
                for ln in passes:
                    print("  PASS", ln, flush=True)


# (name, vocabulary, chips, tokens a block padded, batches a block, the
# app's objective, the longest Huffman code)
PAIRS = [("we_pairs_4c", 8_388_600, 4, 163_840, 256, {}, 0)]
# one chip: we_cbow_hs' blocks of 131,080 tokens (a lane a token, 20 batches
# laid out in 32) and we_pairs' (ten lanes a token, 200 batches in 256)
BLOCK = [("we_cbow_hs", 2_097_100, 1, 163_840, 32,
          dict(cbow=True, hs=True, negative_num=0), 27),
         ("we_pairs", 2_097_100, 1, 163_840, 256, {}, 0)]
_KERNEL = r"custom_call_target=.tpu_custom_call"


def _block_program(vocab, chips, t_pad, nb, objective, max_code):
    """-> (``device_pairs``' block program over ``chips`` shards of four
    ``vocab`` x 128 tables, compiled; a table's server)."""
    from multiverso_tpu.models.wordembedding import device_pairs as dp
    from multiverso_tpu.models.wordembedding.option import Option
    ctx = MeshContext.create(_devices(chips))
    zoo = types.SimpleNamespace(mesh_ctx=ctx, num_workers=1)
    with _no_allocation():
        srvs = [MatrixServerTable(vocab, 128, np.float32, zoo, "default")
                for _ in range(4)]
    tables = [types.SimpleNamespace(server=lambda s=s: s) for s in srvs]
    trainer = dp.DevicePairsTrainer.__new__(dp.DevicePairsTrainer)
    trainer.opt = Option(**{**dict(
        embedding_size=128, window_size=5, negative_num=5, use_adagrad=True,
        device_pairs=True, pair_batch_size=8192), **objective})
    trainer.comm = types.SimpleNamespace(
        input_table=tables[0], output_table=tables[1],
        ie_g2_table=tables[2], eo_g2_table=tables[3])
    trainer._max_code = max_code
    whole = (NamedSharding(ctx.mesh, P()) if chips > 1
             else SingleDeviceSharding(ctx.mesh.devices.flat[0]))
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=whole)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    aux = ((s((vocab, max_code), jnp.int32),
            s((vocab, -(-max_code // 32)), jnp.uint32),
            s((vocab,), jnp.int32)) if trainer.opt.hs
           else (s((1 << 24,), jnp.int32),))
    compiled = trainer._program(t_pad, nb).lower(
        tuple(srv.state["data"] for srv in srvs), aux,
        s((t_pad,), jnp.int32), s((t_pad,), jnp.int32),
        s(key.shape, key.dtype), s((), jnp.float32)).compile()
    return compiled, srvs[0]


def pairs(specs):
    """PAIRS <cell> block_program all_gather=<n> all_reduce=<n>
    kernels=<n> whole_table=<n> passes=<n>: the fused generate-and-train
    program (``models/wordembedding/device_pairs.py``) over ``chips``
    shards, compiled for the chip: its collectives by kind, its Mosaic
    kernels (the four row writes of the touched-rows step), instructions
    that hold an array of a table's WHOLE stored rows (a table gathered
    onto one chip) and passes over a shard outside the in-place writes."""
    for name, *spec in specs:
        compiled, srv = _block_program(*spec)
        hlo = compiled.as_text()
        count = lambda pat: len(re.findall(pat, hlo))  # noqa: E731
        passes = table_sized_passes(hlo, srv.shard_rows * 128)
        print(f"PAIRS {name} block_program "
              f"all_gather={count(r' all-gather(-start)?[(]')} "
              f"all_reduce={count(r' all-reduce(-start)?[(]')} "
              f"kernels={count(_KERNEL)} "
              f"whole_table={count(rf'[[]{srv.padded_rows},128[]]')} "
              f"passes={len(passes)}", flush=True)
        for ln in passes:
            print("  PASS", ln, flush=True)


def block(specs):
    """BLOCK <cell> block_program kernels=<n> whiles=<n> aliased=<n>/4
    passes=<n> temp_mb=<n>: the one-chip block program compiled for the
    chip."""
    for name, *spec in specs:
        compiled, srv = _block_program(*spec)
        hlo = compiled.as_text()
        aliased = len(re.findall(r"\{\d+\}: \(\d+, \{\}",
                                 hlo.split("\n", 1)[0]))
        passes = table_sized_passes(hlo, srv.shard_rows * 128)
        print(f"BLOCK {name} block_program "
              f"kernels={len(re.findall(_KERNEL, hlo))} "
              f"whiles={len(re.findall(r' while[(]', hlo))} "
              f"aliased={aliased}/4 passes={len(passes)} temp_mb="
              f"{compiled.memory_analysis().temp_size_in_bytes >> 20}",
              flush=True)
        for ln in passes:
            print("  PASS", ln, flush=True)


# (name, fetched input rows, fetched output rows, batches a block, pairs a
# batch, negatives): we_rows fetches every output row of 1,048,500 and some
# 330,000 input rows a block
SCAN = [("we_rows", 330_000, 1_048_500, 96, 8_192, 5)]


def scan(specs):
    """SCAN <cell> block_scan touched=<bool> kernels=<n> aliased=<n>/4
    passes=<n>: the block round's one program a block, compiled for the
    chip over the communicator's rung-long training copies."""
    from multiverso_tpu.models.wordembedding.communicator import training_rows
    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    from multiverso_tpu.models.wordembedding.model import (TrainState,
                                                           make_train_step)
    from multiverso_tpu.models.wordembedding.option import Option
    one = SingleDeviceSharding(_devices(1)[0])
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    for name, rows_in, rows_out, nb, batch, negatives in specs:
        we = DistributedWordEmbedding(Option(use_adagrad=True))
        ie = s((training_rows(rows_in), 128), jnp.float32)
        eo = s((training_rows(rows_out), 128), jnp.float32)
        state = TrainState(ie, eo, ie, eo)
        program, touched = we._block_scan_fn(state, make_train_step(True))
        lanes = 1 + negatives
        hlo = program.lower(
            state, s((nb, batch, 1), jnp.int32), s((nb, batch, 1), jnp.float32),
            s((nb, batch, lanes), jnp.int32),
            s((nb, batch, lanes), jnp.float32),
            s((nb, batch, lanes), jnp.float32),
            s((), jnp.float32), s((), jnp.int32)).compile().as_text()
        aliased = len(re.findall(r"\{\d+\}: \(\d+, \{\}",
                                 hlo.split("\n", 1)[0]))
        kernels = len(re.findall(_KERNEL, hlo))
        passes = table_sized_passes(hlo, ie.shape[0] * 128)
        print(f"SCAN {name} block_scan touched={touched} kernels={kernels} "
              f"aliased={aliased}/4 passes={len(passes)}", flush=True)
        for ln in passes:
            print("  PASS", ln, flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump")
    ap.add_argument("--alias", action="store_true")
    ap.add_argument("--alias-all", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--read", action="store_true")
    ap.add_argument("--pairs", action="store_true")
    ap.add_argument("--block", action="store_true")
    ap.add_argument("--scan", action="store_true")
    ap.add_argument("--pooled", action="store_true")
    ap.add_argument("--locations", action="store_true")
    a = ap.parse_args()
    try:
        _devices(1)
    except Exception as exc:  # noqa: BLE001
        print(f"SKIP no v5e topology description: {exc!r}")
        sys.exit(0)
    if a.dump:
        dump(a.dump)
    if a.alias or a.alias_all:
        alias(STATEFUL + (STATEFUL_MORE if a.alias_all else []))
    if a.tiny:
        tiny(TINY)
    if a.read:
        read(READ)
    if a.pairs:
        pairs(PAIRS)
    if a.block:
        block(BLOCK)
    if a.scan:
        scan(SCAN)
    if a.pooled:
        pooled(POOLED)
    if a.locations:
        locations()
