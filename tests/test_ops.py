"""Pallas/XLA row ops and the sharded matrix hot path.

The interpreter runs the Pallas scatter kernel off-TPU, so these tests
exercise the same kernel code the TPU path compiles (ops/pallas_rows.py);
the end-to-end class drives the full MatrixTable PS path with
``-use_pallas=on``: XLA reads, interpreted Pallas writes, the chip's split.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


class TestPallasKernels:
    def test_scatter_set(self):
        from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
        rng = np.random.default_rng(1)
        data = rng.standard_normal((16, 5)).astype(np.float32)
        ids = np.array([2, 9, 15], np.int32)
        rows = rng.standard_normal((3, 5)).astype(np.float32)
        out = pallas_scatter_set_rows(jnp.asarray(data), jnp.asarray(ids),
                                      jnp.asarray(rows), interpret=True)
        expect = data.copy()
        expect[ids] = rows
        np.testing.assert_array_equal(np.asarray(out), expect)

    @pytest.mark.parametrize("layout", ["chunk_aligned", "ragged",
                                        "non_contiguous"])
    def test_coalesced_contiguous_chunks(self, layout):
        """A chunk whose ids are strictly consecutive takes the single
        multi-row-DMA branch (pallas_rows._contig), any other the per-row
        branch, a ragged tail the replicated last pair: each must equal
        a numpy scatter exactly."""
        from multiverso_tpu.ops.pallas_rows import (CHUNK,
                                                    pallas_scatter_set_rows)
        rng = np.random.default_rng(3)
        rows_n = 4 * CHUNK
        data = rng.standard_normal((rows_n, 8)).astype(np.float32)
        run = np.arange(CHUNK, dtype=np.int32) + 17
        rest = np.setdiff1d(np.arange(rows_n, dtype=np.int32), run)
        ids = {
            # chunk 0 a run (one DMA), chunk 1 shuffled (row DMAs)
            "chunk_aligned": np.concatenate(
                [run, rng.permutation(rest)[:CHUNK]]),
            # a run that ends mid-chunk: the tail lanes repeat the last pair
            "ragged": np.concatenate([run, run[-1] + 1 + np.arange(5)]),
            # no chunk is a run, and the count is no chunk multiple
            "non_contiguous": rng.permutation(rest)[:CHUNK + 9],
        }[layout].astype(np.int32)
        new_rows = rng.standard_normal((len(ids), 8)).astype(np.float32)
        out = pallas_scatter_set_rows(jnp.asarray(data), jnp.asarray(ids),
                                      jnp.asarray(new_rows), interpret=True)
        expect = data.copy()
        expect[ids] = new_rows
        np.testing.assert_array_equal(np.asarray(out), expect)

    def test_scatter_preserves_untouched(self):
        from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
        data = np.arange(40, dtype=np.float32).reshape(8, 5)
        out = pallas_scatter_set_rows(
            jnp.asarray(data), jnp.asarray(np.array([3], np.int32)),
            jnp.asarray(np.zeros((1, 5), np.float32)), interpret=True)
        out = np.asarray(out)
        np.testing.assert_array_equal(out[[0, 1, 2, 4, 5, 6, 7]],
                                      data[[0, 1, 2, 4, 5, 6, 7]])
        np.testing.assert_array_equal(out[3], 0.0)


class TestDispatch:
    def test_modes(self, mv_env):
        from multiverso_tpu import ops
        from multiverso_tpu.utils.configure import SetCMDFlag
        SetCMDFlag("use_pallas", "off")
        assert not ops.use_pallas()
        SetCMDFlag("use_pallas", "on")
        assert ops.use_pallas()
        SetCMDFlag("use_pallas", "auto")
        assert ops.use_pallas() == (jax.default_backend() == "tpu")

    def test_only_one_lane_tile_rows_are_eligible(self):
        from multiverso_tpu.ops.rows import _pallas_eligible
        assert _pallas_eligible(jax.ShapeDtypeStruct((4, 128), jnp.float32))
        # Mosaic refuses wider rows (rows._pallas_eligible) -> XLA path
        for cols in (64, 256, 1024):
            assert not _pallas_eligible(
                jax.ShapeDtypeStruct((4, cols), jnp.float32))
        assert not _pallas_eligible(
            jax.ShapeDtypeStruct((4, 128), jnp.bfloat16))


_AOT_CHILD = r"""
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from multiverso_tpu.ops import pallas_rows as pr
from multiverso_tpu.ops.rows import _pallas_eligible
try:
    dev = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
except Exception as exc:
    print(f"SKIP no v5e topology description: {exc!r}")
    sys.exit(0)
sh = SingleDeviceSharding(dev)
admitted = []
for cols in (128, 256, 512, 2048):
    data = jax.ShapeDtypeStruct((100_001, cols), jnp.float32, sharding=sh)
    if not _pallas_eligible(data):
        continue
    admitted.append(cols)
    ids = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=sh)
    rows = jax.ShapeDtypeStruct((8192, cols), jnp.float32, sharding=sh)
    pr.pallas_scatter_set_rows.lower(data, ids, rows).compile()
print("ADMITTED", admitted)
"""


class TestKernelsCompileForTpu:
    def test_every_admitted_width_compiles_for_v5e(self):
        """Tier-1 runs the kernel in interpreter mode only; this compiles
        it with Mosaic against a v5e topology description (no chip
        needed). A width ``_pallas_eligible`` admits but Mosaic refuses
        would crash a table's first Add on the chip."""
        import os
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
        res = subprocess.run([sys.executable, "-c", _AOT_CHILD], env=env,
                             capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-3000:]
        last = res.stdout.strip().splitlines()[-1]
        if last.startswith("SKIP"):
            pytest.skip(last)
        # the widths the repo's own tables use must stay on the kernels
        assert last.startswith("ADMITTED [128"), res.stdout[-2000:]


def _aot_table_programs(*modes):
    """tests/aot_table_programs.py as a child (it makes
    ``jax.default_backend()`` answer "tpu"): its output's lines and the
    whole of it; skips where no v5e topology can be described."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    res = subprocess.run(
        [sys.executable, os.path.join(here, "aot_table_programs.py"),
         *modes], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    if lines and lines[-1].startswith("SKIP"):
        pytest.skip(lines[-1])
    return lines, res.stdout


class TestKernelBodiesNameNoCaller:
    """What identifies a compiled program (``utils/compile_cache.py``): a
    Mosaic kernel's body travels inside its program as bytecode with its
    debug locations, so JAX's persistent cache keys on them. After
    ``compile_cache.enable()`` a location is the line that made the
    operation (``ops/pallas_rows.py``) and not the stack of callers above
    it: an edit of ``tables/matrix_table.py``, or of an app above it,
    moves no program's key (PERF.md section 6, PR 44). The tool lowers
    only, so this child loads no TPU library and writes no cache."""

    @pytest.fixture(scope="class")
    def lowered(self):
        lines, out = _aot_table_programs("--locations")
        return [ln.split() for ln in lines], out

    def test_bodies_name_the_kernels_file_alone(self, lowered):
        lines, out = lowered
        named = {(ln[1], ln[2]): ln[4] for ln in lines if ln[0] == "LOC"}
        # the cells' Pallas programs are all there (20 at PR 44)
        assert len(named) >= 20, out[-3000:]
        assert ("mt_host_verbs", "merged_add_rows.4x10000.65536") in named
        assert ("tables_rounds_4c", "update_gather_rows.65536") in named
        assert set(named.values()) == {
            "files=multiverso_tpu/ops/pallas_rows.py"}, out[-3000:]

    def test_a_caller_300_lines_down_lowers_the_same_body(self, lowered):
        lines, out = lowered
        shift = [ln for ln in lines if ln[0] == "SHIFT"]
        assert shift == [["SHIFT", "we_pairs", "update_rows.8192",
                          "kernels=1", "identical=True"]], out[-3000:]


class TestStatefulRowProgramsAliasOnTpu:
    """The row programs of a table WITH per-worker updater state, compiled
    for a described v5e by tests/aot_table_programs.py (a child: it makes
    ``jax.default_backend()`` answer "tpu"; in this file, after the kernel
    compile above, because one process at a time holds the TPU library):
    every state leaf is aliased input to output and no instruction passes
    over a whole table outside the in-place row writes. This is what keeps
    ``lm_vocab_steps``' gain (PERF.md section 6, PR 29) between chip
    checks: a (workers, rows, cols) leaf, an ``.at[wid]`` in an updater or
    a read-back that breaks the in-place chain shows here as a table-sized
    ``copy`` or fusion."""

    PROGRAMS = [
        ("adagrad_2048_w3", "update_rows.163840"),        # the dense run
        ("adagrad_2048_w3", "update_gather_rows.163840"),
        ("adagrad_2048_w3", "merged_add_rows.1x32768.16384"),
        ("adagrad_128_4c_w3", "update_rows.8192"),        # shard_map, Pallas
        ("adagrad_128_4c_w3", "update_gather_rows.8192"),
        ("adagrad_128_4c_w3", "merged_add_rows.2x4096.8192"),
    ]

    @pytest.fixture(scope="class")
    def compiled(self):
        import re
        lines, out = _aot_table_programs("--alias", "--tiny", "--read",
                                         "--pairs", "--scan", "--block",
                                         "--pooled")
        found = {}
        for ln in lines:
            m = re.match(
                r"(?:ALIAS|TINY|READ|PAIRS|SCAN|BLOCK|POOLED) (\S+) (\S+) "
                r"(.*)", ln)
            if m:
                found[m.group(1), m.group(2)] = m.group(3)
        return found, out

    @pytest.mark.parametrize("table,program", PROGRAMS)
    def test_state_aliases_through_the_row_program(self, compiled, table,
                                                   program):
        found, out = compiled
        assert (table, program) in found, out[-2000:]
        # data and the history, both donated, both updated in place
        assert found[table, program] == "aliased=2/2 passes=0", out[-3000:]

    def test_sharded_block_program_gathers_no_table(self, compiled):
        """``we_pairs_4c`` (PERF.md section 6, PR 37): the WordEmbedding
        app's ``-device_pairs`` block program over four shards of
        8,388,600 x 128 tables compiles for a v5e 2x2 (a plain ``jit``
        over the sharded storage does not: Mosaic kernels cannot be
        partitioned by the compiler), moves rows between chips by
        all-reduce alone, keeps the four row writes on the kernel, holds
        no array of a table's whole rows and passes over no shard."""
        found, out = compiled
        assert ("we_pairs_4c", "block_program") in found, out[-2000:]
        got = dict(kv.split("=") for kv in
                   found["we_pairs_4c", "block_program"].split())
        assert int(got.pop("all_reduce")) >= 1, out[-3000:]
        assert got == {"all_gather": "0", "kernels": "4",
                       "whole_table": "0", "passes": "0"}, out[-3000:]

    def test_block_round_scan_updates_touched_rows_in_place(self, compiled):
        """``we_rows`` (PERF.md section 6, PR 41): the block round's scan
        program over a state of 1,048,576 x 128 output rows takes the
        touched-rows AdaGrad step, compiles for a v5e with its four row
        writes on the kernel, updates all four state matrices in place and
        passes over none of them (the dense step streamed every fetched
        row through two gradient matrices a batch)."""
        found, out = compiled
        assert ("we_rows", "block_scan") in found, out[-2000:]
        assert found["we_rows", "block_scan"] == (
            "touched=True kernels=4 aliased=4/4 passes=0"), out[-3000:]

    @pytest.mark.parametrize("cell,whiles", [
        # the block's loop and one a table whose update walks its distinct
        # rows in chunks: both tables under CBOW + HS (81,920 and 221,184
        # lanes a step), the output table under skip-gram (49,152; its
        # input update is a lane a pair, no loop)
        ("we_cbow_hs", 3), ("we_pairs", 2)])
    def test_block_program_updates_in_place_inside_its_loops(
            self, compiled, cell, whiles):
        """``we_cbow_hs`` and ``we_pairs`` (PERF.md section 6, PR 49): the
        one-chip ``-device_pairs`` block program compiles for a v5e with
        every row write on the kernel (four calls: a chunk of one batch's
        pairs is within ``ops.SMEM_IDS_BYTES`` whatever the lanes a step,
        where CBOW + HS's 221,184 output lanes whole were XLA's scatter),
        all four tables aliased input to output and no pass over a table
        inside the update's loop (its dense-run ``cond`` included)."""
        found, out = compiled
        assert (cell, "block_program") in found, out[-2000:]
        got = found[cell, "block_program"].split()
        assert got[:4] == ["kernels=4", f"whiles={whiles}", "aliased=4/4",
                           "passes=0"], out[-3000:]
        assert got[4].startswith("temp_mb=")

    @pytest.mark.parametrize("rows", [1, 2, 4, 5])
    @pytest.mark.parametrize("program,kernels", [
        ("gather_rows.2048", 0), ("update_rows.2048", 2),
        ("merged_add_rows.1x2048.8", 2)])
    def test_tiny_tables_compile_with_the_kernel(self, compiled, rows,
                                                 program, kernels):
        """``rec_bag_steps``' smallest tables: 1 to 5 live rows and the
        trash row, under Mosaic's tiling of 8 sublanes. A verb's 2,048
        positions and the 8-lane bucket of their distinct rows both compile
        for a v5e, the rows and the history each written by the kernel."""
        found, out = compiled
        table = f"adagrad_128_r{rows}"
        assert (table, program) in found, out[-2000:]
        assert found[table, program] == f"kernels={kernels}", out[-3000:]

    @pytest.mark.parametrize("table,program,want", [
        # rec_pooled_steps' largest verb (table 20): XLA's scatter, the id
        # vector is over the kernel's budget; rows and history in place
        ("adagrad_128_pooled_t20", "fetch_pooled.229376x65536",
         "kernels=0 all_reduce="),
        ("adagrad_128_pooled_t20", "apply_pooled.229376x65536.262144",
         "kernels=0 aliased=2/2 passes=0"),
        ("adagrad_128_pooled", "fetch_pooled.6144x6144",
         "kernels=0 all_reduce="),
        ("adagrad_128_pooled", "apply_pooled.6144x6144.8192",
         "kernels=2 aliased=2/2"),
        ("adagrad_128_pooled_r1", "fetch_pooled.2048x2048",
         "kernels=0 all_reduce="),
        ("adagrad_128_pooled_r1", "apply_pooled.2048x2048.8",
         "kernels=2 aliased=2/2"),
        # over four shards the gather's psum carries a row a position,
        # as device_fetch_rows': the segment sum runs after it
        ("adagrad_128_pooled_4c", "fetch_pooled.8192x2048",
         "kernels=0 all_reduce=f32[8192,128]"),
        ("adagrad_128_pooled_4c", "apply_pooled.8192x2048.8192",
         "kernels=2 aliased=2/2 passes=0")])
    def test_pooled_programs_compile_for_v5e(self, compiled, table, program,
                                             want):
        """``device_fetch_pooled`` / ``device_apply_pooled``'s programs
        (``tables/pooled.py``): the apply writes rows and history in
        place, by the kernel where the row update takes it."""
        found, out = compiled
        assert (table, program) in found, out[-2000:]
        assert found[table, program].startswith(want), out[-3000:]

    @pytest.mark.parametrize("program", [
        "slice_rows.163840",            # lm_head's fetch: the whole table
        "slice_rows.163840.under",      # a run from row 0, shorter
        "slice_rows.16384",             # a run of a bucket under the table
        "slice_rows.16384.under"])
    def test_dense_read_is_one_pass_and_no_gather(self, compiled, program):
        """``lm_vocab_steps``' ``lm_head`` fetch (PERF.md section 6, PR 36):
        the dense read of 163,840 x 2,048 compiles for a v5e to ONE
        instruction as large as the rows it returns, mask included (the
        gather of the same bucket is two, the gather and then its mask:
        ``--read`` prints both)."""
        found, out = compiled
        table = "adagrad_2048_w1"
        assert (table, program) in found, out[-2000:]
        assert found[table, program] == "passes=1 gathers=0", out[-3000:]
        bucket = program.split(".")[1]
        # the count of gathers finds one where there is one
        assert found[table, f"gather_rows.{bucket}"].endswith("gathers=1")


class TestMatrixTableWithPallas:
    """Full PS path with the Pallas write kernel (interpret mode on CPU)."""

    @pytest.fixture()
    def pallas_env(self, mv_env):
        from multiverso_tpu.utils.configure import SetCMDFlag
        SetCMDFlag("use_pallas", "on")
        yield mv_env
        SetCMDFlag("use_pallas", "auto")

    def test_row_add_get(self, pallas_env):
        from multiverso_tpu import ops
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        table = pallas_env.MV_CreateTable(
            MatrixTableOption(num_rows=33, num_cols=7))
        assert ops.use_pallas(table.server().state["data"])
        ids = np.array([0, 4, 17, 32], np.int32)
        deltas = np.arange(4 * 7, dtype=np.float32).reshape(4, 7)
        table.AddRows(ids, deltas)
        table.AddRows(ids, deltas)
        got = table.GetRows(ids)
        np.testing.assert_allclose(got, 2 * deltas)
        # untouched rows stay zero
        np.testing.assert_allclose(table.GetRows([1, 16, 31]), 0.0)

    def test_wider_than_one_tile_takes_the_xla_path(self, pallas_env):
        """256 f32 columns: Mosaic refuses the row kernels there, so even
        ``-use_pallas=on`` must route the table to XLA (on the chip the
        old gate crashed this table's first Add) and stay exact."""
        from multiverso_tpu import ops
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        table = pallas_env.MV_CreateTable(
            MatrixTableOption(num_rows=300, num_cols=256))
        assert not ops.use_pallas(table.server().state["data"])
        rng = np.random.default_rng(5)
        ids = rng.choice(300, 40, replace=False).astype(np.int32)
        deltas = rng.standard_normal((40, 256)).astype(np.float32)
        table.AddRows(ids, deltas)
        table.AddRows(ids, deltas)
        np.testing.assert_array_equal(table.GetRows(ids), deltas + deltas)
        untouched = np.setdiff1d(np.arange(300), ids).astype(np.int32)
        assert not table.GetRows(untouched).any()

    def test_full_table_roundtrip(self, pallas_env):
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        rng = np.random.default_rng(3)
        table = pallas_env.MV_CreateTable(
            MatrixTableOption(num_rows=19, num_cols=4))
        full = rng.standard_normal((19, 4)).astype(np.float32)
        table.Add(full)
        np.testing.assert_allclose(table.Get(), full, rtol=1e-6)
        # row view consistent with full view after row-wise updates
        table.AddRows([3, 18], np.ones((2, 4), np.float32))
        expect = full.copy()
        expect[[3, 18]] += 1.0
        np.testing.assert_allclose(table.Get(), expect, rtol=1e-6)


class TestDenseRunPath:
    """The runtime dense fast path (lax.cond -> bulk dynamic_slice) must be
    bit-identical to the general path. Trash id = data.shape[0]-1 (the
    table layer's convention); trash lanes are don't-care on gather and
    must not leak writes to live rows."""

    combine = staticmethod(lambda r, d: r + d)

    def _mk(self, n_rows=64, cols=8, seed=0):
        rng = np.random.default_rng(seed)
        data = rng.standard_normal((n_rows, cols)).astype(np.float32)
        return rng, data

    @pytest.mark.parametrize("ids", [
        [10, 11, 12, 13],                   # clean run
        [63, 20, 21, 22],                   # leading trash (63 = trash)
        [30, 31, 32, 63],                   # trailing trash
        [63, 40, 41, 63],                   # both
        [5, 7, 8, 9],                       # NOT a run -> general
        [63, 12, 63, 13],                   # interior trash -> general
        [58, 59, 60, 61],                   # run near the end (61+4>63? ok)
    ])
    def test_update_and_gather_match_general(self, ids):
        from multiverso_tpu.ops import rows as rops
        rng, data = self._mk()
        ids = np.asarray(ids, np.int32)
        deltas = rng.standard_normal((len(ids), 8)).astype(np.float32)
        trash = 63
        live = ids != trash

        out = np.asarray(jax.jit(rops.update_rows, static_argnames="combine")(
            jnp.asarray(data), jnp.asarray(ids), jnp.asarray(deltas),
            self.combine))
        expect = data.copy()
        expect[ids[live]] += deltas[live]
        rows_mask = [r for r in range(64) if r != trash]
        np.testing.assert_allclose(out[rows_mask], expect[rows_mask],
                                   rtol=1e-6)

        got = np.asarray(jax.jit(rops.gather_rows)(
            jnp.asarray(data), jnp.asarray(ids)))
        np.testing.assert_allclose(got[live], data[ids[live]], rtol=1e-6)

        new_rows = rng.standard_normal((len(ids), 8)).astype(np.float32)
        out2 = np.asarray(jax.jit(rops.scatter_set_rows)(
            jnp.asarray(data), jnp.asarray(ids), jnp.asarray(new_rows)))
        expect2 = data.copy()
        expect2[ids[live]] = new_rows[live]
        np.testing.assert_allclose(out2[rows_mask], expect2[rows_mask],
                                   rtol=1e-6)

    @pytest.mark.parametrize("ids", [[4, 5, 6, 7], [0, 30, 62, 9]])
    def test_update_gather_rows_fused(self, ids):
        from multiverso_tpu.ops import rows as rops
        rng, data = self._mk(seed=3)
        ids = np.asarray(ids, np.int32)
        deltas = rng.standard_normal((len(ids), 8)).astype(np.float32)
        new_data, rows = jax.jit(rops.update_gather_rows,
                                 static_argnames="combine")(
            jnp.asarray(data), jnp.asarray(ids), jnp.asarray(deltas),
            self.combine)
        expect = data.copy()
        expect[ids] += deltas
        live_rows = [r for r in range(64) if r != 63]
        np.testing.assert_allclose(np.asarray(new_data)[live_rows],
                                   expect[live_rows], rtol=1e-6)
        # the Get half returns POST-update rows
        np.testing.assert_allclose(np.asarray(rows), expect[ids], rtol=1e-5)

    @pytest.mark.parametrize("start,count,bucket", [
        (0, 8, 8), (16, 8, 8), (55, 8, 8),      # a run that is its bucket
        (0, 5, 8), (20, 1, 8), (55, 3, 8),      # shorter: pad lanes zero
        (0, 63, 63), (0, 40, 63), (0, 1, 63),   # as long as the live rows
    ])
    @pytest.mark.parametrize("num_cols", [8, 5], ids=["all_cols", "5_cols"])
    def test_slice_rows_is_the_masked_gather(self, start, count, bucket,
                                             num_cols):
        """The dense read against the general one: the gather of ``start
        .. start + count - 1`` padded to ``bucket`` trash lanes, masked as
        the table masks it, the storage pad cut off. Bit for bit."""
        from multiverso_tpu.ops import rows as rops
        _, data = self._mk(seed=7)
        trash = data.shape[0] - 1
        ids = np.full(bucket, trash, np.int32)
        ids[:count] = np.arange(start, start + count)
        want = np.where((ids != trash)[:, None],
                        np.asarray(rops.gather_rows(jnp.asarray(data),
                                                    jnp.asarray(ids))),
                        0)[:, :num_cols]
        program = jax.jit(rops.slice_rows,
                          static_argnames=("bucket", "num_cols"))
        got = program(jnp.asarray(data), np.int32(start),
                      None if count == bucket else np.int32(count),
                      bucket=bucket, num_cols=num_cols)
        np.testing.assert_array_equal(np.asarray(got), want)
        # a traced count that equals the bucket masks nothing either
        got = program(jnp.asarray(data), np.int32(start), np.int32(count),
                      bucket=bucket, num_cols=num_cols)
        np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("bucket,masked,primitives", [
        (8, False, {"dynamic_slice"}),
        (8, True, {"dynamic_slice", "select_n"}),
        (63, False, {"dynamic_slice"}),     # from the constant row 0
        (63, True, {"dynamic_slice", "select_n"}),
    ])
    def test_slice_rows_has_no_cond_and_no_gather(self, bucket, masked,
                                                  primitives):
        """One slice, one select where a lane is masked; the start is a
        constant where the bucket is as long as the live rows."""
        from multiverso_tpu.ops import rows as rops
        _, data = self._mk()
        count = jnp.int32(3) if masked else None
        program = jax.make_jaxpr(
            lambda d, s, c: rops.slice_rows(d, s, c, bucket, 8))(
                jnp.asarray(data), jnp.int32(0), count)
        def equations(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from equations(sub)
        names = {e.primitive.name for e in equations(program.jaxpr)}
        assert primitives <= names
        assert not names & {"cond", "gather", "scatter", "while"}
        cut, = (e for e in program.jaxpr.eqns
                if e.primitive.name == "dynamic_slice")
        start_is_constant = type(cut.invars[1]).__name__ == "Literal"
        assert start_is_constant == (bucket == data.shape[0] - 1)

    def test_table_round_verb_matches_separate_verbs(self, mv_env):
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        from multiverso_tpu.updaters.base import AddOption
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=40, num_cols=5))
        srv = table.server()
        ids = np.array([3, 17, 29], np.int32)
        deltas = np.arange(15, dtype=np.float32).reshape(3, 5)
        padded = srv.pad_ids(ids)
        pdeltas = np.zeros((len(padded), 5), np.float32)
        pdeltas[:3] = deltas
        state, rows = jax.jit(srv.device_update_gather_rows)(
            jax.tree.map(jnp.copy, srv.state), jnp.asarray(padded),
            jnp.asarray(pdeltas), AddOption().as_jnp())
        srv.state = state
        np.testing.assert_allclose(np.asarray(rows)[:3], deltas, rtol=1e-6)
        np.testing.assert_allclose(table.GetRows(ids), deltas, rtol=1e-6)


class TestShardedLayout:
    def test_storage_roundtrip_many_servers(self, mv_env):
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        from multiverso_tpu.zoo import Zoo
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=13, num_cols=3))
        server = Zoo.Get().server_tables[-1]
        assert server.num_servers == len(jax.devices())
        full = np.arange(13 * 3, dtype=np.float32).reshape(13, 3)
        st = server._to_storage(full)
        assert st.shape == (server.padded_rows, server.store_cols)
        assert server.store_cols >= 3
        # pad columns are zero and stay zero (updaters are identity on them)
        np.testing.assert_array_equal(st[:, 3:], 0.0)
        np.testing.assert_array_equal(server._from_storage(st), full)

    def test_tiny_table_fewer_rows_than_servers(self, mv_env):
        # reference CHECK(size_ > MV_NumServers()) rejects this
        # (array_table.cpp:14, skipped python test test_multiverso.py:36-41);
        # the TPU layout supports it.
        from multiverso_tpu.tables.matrix_table import MatrixTableOption
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=3, num_cols=2))
        table.AddRows([0, 2], np.ones((2, 2), np.float32))
        np.testing.assert_allclose(table.GetRows([0, 1, 2]),
                                   [[1, 1], [0, 0], [1, 1]])
