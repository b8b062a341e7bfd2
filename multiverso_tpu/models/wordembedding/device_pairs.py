"""On-device pair generation + training: the TPU-native WE hot loop.

The reference generates (center, context, negatives) training pairs on
the CPU and feeds them to its trainer threads
(Applications/WordEmbedding/src/data_block.h + trainer.cpp:45-118); the
host-plane port mirrors that (data.py ``_skipgram_neg_arrays``), which
means every block ships ~80x its token payload in stacked pair tensors
over the host->device link (one 180k-word block = ~65MB of pair tensors
vs ~1.5MB of tokens).

``-device_pairs 1`` moves the expansion INTO the block's XLA program:
the host uploads only the subsampled token stream (ids + sentence ids),
and one jit'd, donated program derives the pairs and trains:

  * sentence positions/lengths via segment cummax/cummin over the
    sentence-id vector;
  * the word2vec shrunk window ``b ~ U[1, window]`` per center
    (reference wordembedding.cpp:58-75 ``rand % window``) and one
    masked shift pass per offset d in [-W..W]\\{0} — the same
    construction as data.py:159-213, lanes masked instead of compacted
    (SPMD static shapes). Skip-gram emits one pair per (center,
    context) lane; CBOW stacks the offsets into the pair's INPUT lanes
    (the step's imask mean is the context average,
    wordembedding.cpp cbow branch);
  * negatives from the reference's quantized unigram^0.75 SLOT table
    (util.h SetNegativeSamplingDistribution) uploaded once — one
    random-int gather per draw, the fastest sampler measured on v5e
    (every jnp.searchsorted method is slower, and a float32 CDF loses
    the rare-word tail at word2vec-scale vocabularies);
  * center-collision negative lanes masked (reference skips
    target==word_idx draws);
  * hierarchical softmax from path tables taken whole from the
    Huffman encoder's arrays (a node id a lane, a word's turns as
    bits, its path length) and gathered per center — the output
    lanes become the center's root path (huffman_encoder.cpp), labels
    (1 - code) and the mask made in the program, no negative draws;
  * the standard train step (model.make_train_step) scanned over the
    lane batches, operating DIRECTLY on the tables' sharded storage
    (ids remapped to the interleaved layout: sid = r + r//block_rows).

Subsampling stays on the host (data.py KeepMask): word2vec's removal
semantics physically shorten sentences (windows then reach farther),
which requires compaction — a data-dependent shape. It is one
vectorized pass over the tokens and rides the loader thread.

All four mode combinations (skipgram/cbow x NEG/HS) ride the fused
path (on a chip at a word2vec vocabulary: skipgram+NEG and CBOW+HS,
the cells we_pairs and we_cbow_hs), and multi-process worlds train COLLECTIVELY: per-process token
shards merge as one batch-sharded global vector whose gradients sum
inside the traced program (round 4; rounds 2-3 covered
skipgram+NEG, single-process only). Within a process the caller owns
the tables while training (the device-plane single-writer contract).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace


class _LazyStats:
    """One element of a shared (4,) INT32 device stats array (loss
    bits, pair count, steps the loop ran, row lanes their updates ran);
    float()/int() fetch the WHOLE array once (cached on the array handle
    by jax), so a block's loss+pairs harvest costs one transfer. The
    array is integer-typed with the f32 loss BITCAST into lane 0: the
    reverse packing (count bitcast into an f32 lane) shipped the count
    as a DENORMAL float, which the TPU flushes to zero in flight —
    silently zeroing every block's pair count (the avg-loss display
    became the raw sum). Integer lanes are never flushed, and an int32
    count stays exact past 2^24 pairs (a 100MB reference-scale block
    holds ~75M)."""

    __slots__ = ("_arr", "_i", "_bits")

    def __init__(self, arr, i, bits=False):
        self._arr = arr
        self._i = i
        self._bits = bits

    def _value(self):
        lane = np.asarray(self._arr)[self._i: self._i + 1]
        return lane.view(np.float32)[0] if self._bits else lane[0]

    def __float__(self):
        return float(self._value())

    def __int__(self):
        return int(self._value())

    def lane(self, i):
        """Another lane of the same array: the same one fetch."""
        return _LazyStats(self._arr, i)


# Module-level program cache: keyed by every static the program closes
# over, so a fresh trainer instance (e.g. a second app run in the same
# process — the benchmark's warm-up, then its timed ``train()``) reuses
# the compiled executable instead of retracing per instance.
_PROGRAM_CACHE = {}

#: above this table size (bytes of one table), the adagrad scan body
#: switches from model.make_train_step's dense full-table update (fast
#: for small vocabs — pure streaming passes) to the sparse touched-rows
#: step below: dense pays O(V*D) per batch, which at word2vec-scale
#: vocabularies (1M x 128 ≈ 512MB/table) would dwarf the batch itself.
_SPARSE_BYTES = 64 << 20


def _make_sparse_adagrad_step(eps: float = 1e-10, lanes=None):
    """Touched-rows adagrad batch step over FULL storage tables:
    identical math to model.make_train_step's adagrad branch (the batch's
    per-row summed gradient feeds g2 before the update), but the g2/row
    updates gather+scatter only the rows the batch touches —
    ops.dedup_rows combines duplicate ids by sum exactly like the dense
    scatter-add did. All ids are pre-mapped storage ids; dedup pad lanes
    (-1) route to the storage trash row (shape-1, don't-care).

    ``lanes`` makes it the step of ONE shard of tables block-sharded by
    rows, to run under ``shard_map`` over the ``server`` axis: the tables
    are the shard's own rows, ids are LOGICAL rows, and ``lanes`` is the
    table's ``device_local_lanes`` (ids -> (mine, the shard's row or its
    trash row)). A fetch gathers the rows the shard owns and the partial
    rows are summed (all but one are zero: the sum is exact); the update
    needs nothing from another shard — every shard holds the batch's
    deduplicated gradients and writes the rows it owns, the others' lanes
    and the pad lanes going to its own trash row.

    Returns ``(state, loss, lanes_run)``: the row lanes the two tables'
    updates ran, of the ``inputs.size + outputs.size`` the batch laid
    out (all of them per shard, where ``lanes`` is given)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from multiverso_tpu import ops
    from multiverso_tpu.models.wordembedding.model import TrainState
    from multiverso_tpu.parallel.mesh import SERVER_AXIS

    @jax.named_scope("we.device_pairs_step.fetch")
    def fetch(tab, ids):
        if lanes is None:
            return ops.gather_rows(tab, ids)
        mine, safe = lanes(ids)
        rows = jnp.where(mine[:, None], ops.gather_rows(tab, safe), 0)
        return lax.psum(rows, SERVER_AXIS)

    def step(state, inputs, imask, outputs, labels, omask, lr):
        ie, eo = state.ie, state.eo
        D = ie.shape[1]
        in_rows = fetch(ie, inputs.reshape(-1)).reshape(
            inputs.shape + (D,))
        with jax.named_scope("we.device_pairs_step.context_mean"):
            denom = jnp.maximum(imask.sum(axis=1, keepdims=True), 1.0)
            h = (in_rows * imask[:, :, None]).sum(axis=1) / denom
        out_rows = fetch(eo, outputs.reshape(-1)).reshape(
            outputs.shape + (D,))
        logits = jnp.einsum("pd,pcd->pc", h, out_rows)
        f = jax.nn.sigmoid(logits)
        err = (labels - f) * omask
        loss = -jnp.sum(omask * (labels * jnp.log(f + 1e-7) +
                                 (1 - labels) * jnp.log(1 - f + 1e-7)))
        hid_err = jnp.einsum("pc,pcd->pd", err, out_rows)
        eo_contrib = err[:, :, None] * h[:, None, :]
        ie_contrib = hid_err[:, None, :] * imask[:, :, None]

        def update(tab, g2tab, uids, grads):
            if lanes is None:
                trash = tab.shape[0] - 1
                uids = jnp.where(uids < 0, trash, uids)
            else:
                _, uids = lanes(uids)
            g2_rows = ops.gather_rows(g2tab, uids) + grads * grads
            rows = ops.gather_rows(tab, uids) + jnp.where(
                g2_rows > eps, lr * grads / jnp.sqrt(g2_rows + 1e-12), 0.0)
            # the dense-run cond is the one-shard program's (inside a
            # shard_map body it defeats donation,
            # matrix_table._update_rows_local)
            dense = lanes is None
            return (ops.scatter_set_rows(tab, uids, rows, dense=dense),
                    ops.scatter_set_rows(g2tab, uids, g2_rows, dense=dense))

        @jax.named_scope("we.device_pairs_step.update")
        def row_update(tab, g2tab, ids, contrib):
            """-> (tab, g2tab, the lanes the update ran)."""
            uids, grads = ops.dedup_rows(ids.reshape(-1),
                                         contrib.reshape(-1, D))
            n, chunk = uids.shape[0], ids.shape[0]
            if lanes is not None or n == chunk:
                return (*update(tab, g2tab, uids, grads), n)
            # dedup_rows leaves the batch's distinct ids in its first
            # lanes, ascending, and -1 in every lane after them (an eighth
            # of a CBOW + HS batch's lanes hold a distinct row): the update
            # walks those first lanes in chunks of one batch's pairs and
            # stops after the last that holds one, a trip count the device
            # reads. A row's arithmetic is its own lane's, so the live
            # rows come out as the update of all n lanes leaves them; the
            # trash row gets fewer writes. The chunk divides n (a whole
            # number of lanes a pair), so no two chunks overlap, and it is
            # one shape for every table and lane count: the row kernel is
            # traced once, and writes every chunk (an id vector over
            # ops.SMEM_IDS_BYTES whole would be XLA's scatter)
            live = jnp.max(jnp.where(uids >= 0, jnp.arange(1, n + 1), 0))
            trips = (live + chunk - 1) // chunk

            def body(i, tables):
                at = i * chunk
                return update(*tables,
                              lax.dynamic_slice(uids, (at,), (chunk,)),
                              lax.dynamic_slice(grads, (at, 0), (chunk, D)))
            tab, g2tab = lax.fori_loop(0, trips, body, (tab, g2tab))
            return tab, g2tab, trips * chunk

        eo, eo_g2, eo_run = row_update(eo, state.eo_g2, outputs, eo_contrib)
        ie, ie_g2, ie_run = row_update(ie, state.ie_g2, inputs, ie_contrib)
        return TrainState(ie, eo, ie_g2, eo_g2), loss, eo_run + ie_run

    return step


class DevicePairsTrainer:
    """Owns the uploaded sampling tables; programs cache module-wide."""

    def __init__(self, opt, comm, counts, huffman=None):
        self.opt = opt
        self.comm = comm
        self._block_counter = 0
        # what the block program takes whole on every shard (the sampling
        # tables here, a block's token stream later): over more than one
        # shard it is put in the sharding the tables' row programs state
        # for ids, replicated over the mesh, ONE copy a chip, and not left
        # on the default device for jit to send to the others at every
        # call. One shard keeps its bare copy: a committed operand is
        # another lowered module, and that world compiles what it did
        import jax
        import jax.numpy as jnp
        srv = comm.input_table.server()
        put = self._put = (srv._put_small if srv.num_servers > 1 else
                           lambda host: jax.tree.map(jnp.asarray, host))
        if opt.hs:
            # hierarchical softmax: each center's output lanes gather from
            # the path tables like the NEG table (reference
            # huffman_encoder.cpp paths; inner-node ids live in the output
            # table rows like word2vec syn1). They upload ONCE, the
            # encoder's arrays whole and no wider than what they say: a
            # node id a lane (int32), a word's turns as bits of uint32
            # words and its path length; labels (1 - code) and the lane
            # mask are made from those in the program. The driver's
            # already-built encoder is reused when passed.
            enc = huffman
            if enc is None:
                from multiverso_tpu.models.wordembedding.huffman import (
                    HuffmanEncoder)
                enc = HuffmanEncoder()
                enc.BuildFromTermFrequency(counts)
            MC, pts, codes = enc.max_code_length, enc.points, enc.codes
            if not MC:      # one word has no path: a lane under a zero mask
                MC = 1
                pts, codes = (np.zeros((len(counts), 1), a.dtype)
                              for a in (pts, codes))
            turns = np.packbits(codes, axis=1, bitorder="little")
            turns = np.pad(turns, ((0, 0), (0, -turns.shape[1] % 4))).view(
                "<u4")
            self._hs_lengths = enc.lengths      # the host's, for counters
            self._hs_points, self._hs_bits, self._hs_len = put(
                (pts, turns, enc.lengths))
            self._max_code = MC
            tmetrics.gauge("we.hs.max_code").set(MC)
            tmetrics.gauge("we.hs.table_bytes").set(
                pts.nbytes + turns.nbytes + enc.lengths.nbytes)
            self._slots = None
        else:
            # negative-sampling SLOT table (reference util.h
            # SetNegativeSamplingDistribution; same quantization law as
            # sampler.Sampler): word i owns round(p_i * T) consecutive
            # slots. A float32 CDF + searchsorted loses the tail at
            # word2vec-scale vocabularies (rare words' mass rounds to
            # zero-width intervals) AND is slower — one random-int gather
            # beats every searchsorted method measured on v5e.
            probs = np.asarray(counts, np.float64) ** 0.75
            cum = np.cumsum(probs / probs.sum())
            T = int(min(max(1 << 20, 64 * len(counts)), 1 << 24))
            bounds = np.round(cum * T).astype(np.int64)
            self._slots = put(np.repeat(
                np.arange(len(counts), dtype=np.int32),
                np.diff(bounds, prepend=0)))

    # -- table storage plumbing --------------------------------------------

    def _servers(self):
        c = self.comm
        servers = [c.input_table.server(), c.output_table.server()]
        if self.opt.use_adagrad:
            servers += [c.ie_g2_table.server(), c.eo_g2_table.server()]
        return servers

    def _take_states(self):
        return tuple(s.state["data"] for s in self._servers())

    def _put_states(self, arrays) -> None:
        for srv, arr in zip(self._servers(), arrays):
            srv.state = dict(srv.state)
            srv.state["data"] = arr

    # -- the block program --------------------------------------------------

    def _sparse(self) -> bool:
        """Whether the block program's step is the touched-rows one."""
        data = self.comm.input_table.server().state["data"]
        return bool(self.opt.use_adagrad
                    and data.size * data.dtype.itemsize > _SPARSE_BYTES)

    @property
    def step_update_lanes(self) -> int:
        """The row lanes the touched-rows step's two updates lay out a
        batch, a lane each input and output of a pair (0 under the dense
        steps): what ``we.update.lanes.laid_out`` counts a step run."""
        opt = self.opt
        if not self._sparse():
            return 0
        return opt.pair_batch_size * (
            (2 * opt.window_size if opt.cbow else 1)
            + (self._max_code if opt.hs else 1 + opt.negative_num))

    def _program(self, t_pad: int, nb: int):
        opt = self.opt
        srv = self.comm.input_table.server()
        sparse = self._sparse()
        # storage of more than one shard: the touched-rows step runs per
        # shard (its row kernel cannot be partitioned by the compiler, and
        # no chip may hold a table whole); one shard compiles what it
        # always did
        sharded = sparse and srv.num_servers > 1
        cache_key = (t_pad, nb, opt.window_size, opt.negative_num,
                     opt.pair_batch_size, opt.use_adagrad, sparse,
                     srv.block_rows, opt.cbow, opt.hs,
                     self._max_code if opt.hs else 0,
                     srv._mesh if sharded else None)
        if cache_key in _PROGRAM_CACHE:
            return _PROGRAM_CACHE[cache_key]
        import jax
        import jax.numpy as jnp
        from jax import lax

        from multiverso_tpu.models.wordembedding.model import (TrainState,
                                                               make_train_step)

        W, K = opt.window_size, opt.negative_num
        B = opt.pair_batch_size
        step = (_make_sparse_adagrad_step(
                    lanes=srv.device_local_lanes if sharded else None)
                if sparse else make_train_step(opt.use_adagrad))
        block_rows = srv.block_rows   # all four tables share the layout
        use_adagrad = opt.use_adagrad

        def smap(r):
            """logical row -> interleaved storage row (matrix_table
            layout: block_rows live rows + 1 trash row per shard). The
            per-shard step takes logical rows: each shard finds its own."""
            return r if sharded else r + r // block_rows

        cbow, hs = opt.cbow, opt.hs

        @jax.named_scope("we.device_pairs_step")
        def program(states, aux, ids, sent, key, lr):
            n = t_pad
            ar = jnp.arange(n, dtype=jnp.int32)
            valid = ids >= 0
            prev = jnp.concatenate([jnp.full((1,), -9, jnp.int32),
                                    sent[:-1]])
            is_start = sent != prev
            start = lax.cummax(jnp.where(is_start, ar, 0))
            pos = ar - start
            nxt = jnp.concatenate([sent[1:], jnp.full((1,), -9, jnp.int32)])
            is_end = sent != nxt
            end = lax.cummin(jnp.where(is_end, ar, n)[::-1])[::-1]
            slen = end - start + 1
            kb, kneg = jax.random.split(key)
            b = jax.random.randint(kb, (n,), 1, W + 1)

            shifts_l, ok_l = [], []
            for d in list(range(-W, 0)) + list(range(1, W + 1)):
                if d > 0:
                    shifted = jnp.concatenate(
                        [ids[d:], jnp.full((d,), -1, jnp.int32)])
                else:
                    shifted = jnp.concatenate(
                        [jnp.full((-d,), -1, jnp.int32), ids[:d]])
                ok = (valid & (abs(d) <= b) & (pos + d >= 0)
                      & (pos + d < slen) & (shifted >= 0))
                shifts_l.append(shifted)
                ok_l.append(ok)

            if cbow:
                # one pair per CENTER: the input lanes are the center's
                # shrunk-window context words, mean-combined by the step's
                # imask (reference wordembedding.cpp cbow branch)
                ibool = jnp.stack(ok_l, axis=1)           # (n, 2W)
                inputs = jnp.where(ibool, jnp.stack(shifts_l, axis=1), 0)
                imask = ibool.astype(jnp.float32)
                pmask = ibool.any(axis=1)                 # center usable
                centers = jnp.where(pmask, ids, 0)
            else:
                # skip-gram: one pair per (center, context) lane
                pmask = jnp.concatenate(ok_l)
                centers = jnp.where(pmask, jnp.concatenate([ids] * (2 * W)),
                                    0)
                contexts = jnp.where(pmask, jnp.concatenate(shifts_l), 0)
                inputs = contexts[:, None]
                imask = pmask[:, None].astype(jnp.float32)
            P = centers.shape[0]              # t_pad (cbow) | 2W*t_pad

            if hs:
                # output lanes = the center's Huffman path: inner-node
                # rows + (1-code) labels, gathered from the uploaded
                # tables exactly like the NEG slot gather
                with jax.named_scope("we.device_pairs_step.path"):
                    hs_points, hs_bits, hs_len = aux
                    lane = np.arange(hs_points.shape[1])
                    outputs = jnp.take(hs_points, centers, axis=0)
                    turns = jnp.take(hs_bits, centers, axis=0)
                    code = (turns[:, lane // 32]
                            >> (lane % 32).astype(np.uint32)) & 1
                    labels = 1.0 - code.astype(jnp.float32)
                    on_path = lane[None, :] < jnp.take(hs_len,
                                                       centers)[:, None]
                    omask = (on_path & pmask[:, None]).astype(jnp.float32)
            else:
                (slots,) = aux
                draws = jax.random.randint(kneg, (P, K), 0, slots.shape[0])
                negs = jnp.take(slots, draws)
                outputs = jnp.concatenate([centers[:, None], negs], axis=1)
                omask = jnp.concatenate(
                    [pmask[:, None],
                     pmask[:, None] & (negs != centers[:, None])],
                    axis=1).astype(jnp.float32)
                labels = jnp.broadcast_to(
                    jnp.concatenate([jnp.ones((1,), jnp.float32),
                                     jnp.zeros((K,), jnp.float32)])[None, :],
                    (P, 1 + K))

            def batched(a):
                pad = nb * B - P
                a = jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                return a.reshape(nb, B, -1)

            stacked = (batched(smap(inputs)), batched(imask),
                       batched(smap(outputs)), batched(labels),
                       batched(omask))
            if use_adagrad:
                state = TrainState(states[0], states[1], states[2],
                                   states[3])
            else:
                state = TrainState(states[0], states[1], None, None)

            # the loop runs the lane-batches that hold a pair, in their
            # order, and not the nb its buckets lay out (t_pad and nb are
            # each rounded up to a rung; a skip-gram segment ends in dead
            # lanes). A batch without a pair is a step that rewrites the
            # rows it gathered with what it gathered: zero gradients,
            # zero loss. The trip count is read from the mask, the same
            # on every shard (tokens and key are whole on each).
            live = batched(pmask).any(axis=(1, 2))
            n_live = jnp.sum(live, dtype=jnp.int32)
            order = jnp.argsort(~live, stable=True)

            def body(i, carry):
                st, losses, lanes_run = carry
                at = order[i]
                # the touched-rows step says how many row lanes its
                # updates ran; the dense steps have no lanes to say
                st, loss, *ran = step(st, *(lax.dynamic_index_in_dim(
                    a, at, keepdims=False) for a in stacked), lr)
                return st, losses.at[at].set(loss), lanes_run + sum(ran)

            # a dead batch's loss is the zero its step would return: the
            # block's sum is over all nb, and keeps its bits
            state, losses, lanes_run = lax.fori_loop(
                0, n_live, body,
                (state, jnp.zeros((nb,), jnp.float32), jnp.int32(0)))
            out = ((state.ie, state.eo, state.ie_g2, state.eo_g2)
                   if use_adagrad else (state.ie, state.eo))
            # ONE (4,) INT32 stats array: the caller's lazy harvest pays
            # a single host fetch per block instead of four.
            # The f32 loss rides as raw BITS in lane 0 (see _LazyStats —
            # an f32-typed array would flush the bitcast count lane as a
            # denormal on TPU); lane 2 is the steps the loop ran, lane 3
            # the row lanes the touched-rows step ran its updates at (0
            # under the dense steps; int32 holds a 100MB block's 22,000
            # skip-gram steps of 57,344 lanes).
            loss_bits = lax.bitcast_convert_type(
                jnp.sum(losses).astype(jnp.float32), jnp.int32)
            stats = jnp.stack([loss_bits, jnp.sum(pmask).astype(jnp.int32),
                               n_live, lanes_run])
            return out, stats

        if sharded:
            # the whole block program per shard: the tables by rows, all
            # else (tokens, sampling tables, key, rate) whole on each, so
            # every shard draws the same pairs and computes the same
            # batch; only the fetched rows cross between chips (the step's
            # psum). The stats come out equal on every shard.
            from jax.sharding import PartitionSpec as P

            from multiverso_tpu.parallel.mesh import SERVER_AXIS
            rows, whole = P(SERVER_AXIS, None), P()
            program = jax.shard_map(
                program, mesh=srv._mesh,
                in_specs=((rows,) * 4, whole, whole, whole, whole, whole),
                out_specs=((rows,) * 4, whole),
                check_vma=False)  # pallas_call outputs carry no vma info
        _PROGRAM_CACHE[cache_key] = jax.jit(program, donate_argnums=(0,))
        return _PROGRAM_CACHE[cache_key]

    # -- per-block entry ----------------------------------------------------

    def train_block(self, token_ids: np.ndarray, token_sent: np.ndarray,
                    lr: float, agreed=None):
        """One block: upload the (tiny) token stream, run the fused
        generate+train program in place on the tables. Returns DEVICE
        scalars (loss_sum, pair_count) — harvest them lazily so dispatch
        overlaps the next block's host prep.

        Multi-process (round 4): COLLECTIVE, lockstep blocks (every
        process calls train_block once per logical block — the same
        contract as every multi-process device-plane verb). Each
        process's padded token stream becomes one shard of a global
        batch-sharded vector (place_parts); per-process sentence ids
        offset into disjoint ranges so the program's segment pass sees
        the process boundary as a sentence break; the dense grads (or
        deduped touched-row updates) SUM across processes inside the
        traced program (GSPMD inserts the collectives — the reference's
        every-worker's-Add-accumulates, the collective-merge contract
        of matrix_table's parts round), and the identical update
        applies everywhere. The returned stats are GLOBAL (all
        processes' pairs)."""
        import jax
        import jax.numpy as jnp

        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.parallel.mesh import place_parts

        nproc = multihost.process_count()
        srv = self.comm.input_table.server()
        T = len(token_ids)
        if nproc > 1:
            from multiverso_tpu.parallel.mesh import (local_device_count,
                                                      parts_bucket)
            # the shared local bucket (must divide evenly over this
            # process's devices — the checked parts_bucket helper every
            # parts verb uses, floored at 1024 like the single-process
            # bucket so tail blocks don't mint fresh program shapes) and
            # the global sentence-id span (subsampling keeps ORIGINAL
            # sentence indices, so max(token_sent) routinely exceeds T —
            # the offset must come from the gathered max, not the
            # bucket). ``agreed`` carries both from the driver's single
            # per-block allgather; a direct caller pays one here.
            if agreed is None:
                local_max_sent = int(token_sent.max(initial=-1)) + 1
                parts = multihost.host_allgather_objects_capped(
                    (T, local_max_sent), "we_dp_agreed")
                agreed = (max(p[0] for p in parts),
                          max(p[1] for p in parts))
            mesh = srv._mesh
            t_pad = parts_bucket(max(1024, agreed[0]),
                                 local_device_count(mesh))
            sent_span = max(agreed[1], 1)
        else:
            t_pad = next_bucket(T, min_bucket=1024)
        if nproc <= 1 and T == 0:
            return jnp.float32(0.0), jnp.int32(0)
        with ttrace.span("worker.we.upload", cat="worker"):
            ids = np.full(t_pad, -1, np.int32)
            ids[:T] = token_ids
            sent = np.full(t_pad, -1, np.int32)
            rank = multihost.process_index()
            if nproc > 1:
                # disjoint per-process sentence ranges: offset by the
                # GLOBAL max sentence id so shards can never merge across
                # the process boundary in the concatenated vector
                sent[:T] = token_sent + rank * sent_span
                ids_g = place_parts(mesh, ids, nproc)
                sent_g = place_parts(mesh, sent, nproc)
                n_total = nproc * t_pad
            else:
                sent[:T] = token_sent
                # whole on every shard, as the block program states it
                with ttrace.child(".place"):
                    ids_g, sent_g = self._put((ids, sent))
                n_total = t_pad
            # the block's tokens by the shard that owns their input row:
            # block sharding of ids ordered by count makes shard 0 hot
            for k, n in enumerate(np.bincount(token_ids // srv.block_rows,
                                              minlength=srv.num_servers)):
                tmetrics.counter(f"we.block.tokens.shard{k}").inc(int(n))
            # the block's output lanes that lie on a path, of those the
            # program lays out (every token's, padded to the longest code)
            if self.opt.hs:
                tmetrics.counter("we.hs.path_lanes.valid").inc(
                    int(np.take(self._hs_lengths, token_ids).sum()))
                tmetrics.counter("we.hs.path_lanes.padded").inc(
                    T * self._max_code)
            if self.opt.cbow:
                # a token is a centre where its sentence holds another
                pair = token_sent[1:] == token_sent[:-1]
                tmetrics.counter("we.cbow.centres").inc(int(
                    (np.append(pair, False) | np.append(False, pair)).sum()))
        with ttrace.span("worker.we.dispatch", cat="worker"):
            P = n_total if self.opt.cbow \
                else 2 * self.opt.window_size * n_total
            nb = next_bucket(-(-P // self.opt.pair_batch_size), min_bucket=4)
            program = self._program(n_total, nb)
            # the steps the block's buckets lay out; the harvest counts
            # those the program's loop ran (we.block.steps.run)
            tmetrics.counter("we.block.steps.laid_out").inc(nb)
            self._block_counter += 1
            key = jax.random.fold_in(jax.random.PRNGKey(self.opt.seed),
                                     self._block_counter)
            aux = ((self._hs_points, self._hs_bits, self._hs_len)
                   if self.opt.hs else (self._slots,))
            states, stats = program(
                self._take_states(), aux, ids_g, sent_g, key,
                jnp.float32(lr))
        self._put_states(states)
        # stats is a (4,) int32 device array; one np.asarray in the
        # harvest fetches its scalars (lane 0 is the bitcast f32 loss;
        # lanes 2 and 3, the steps run and the row lanes their updates
        # ran, are reached from the pair count's handle)
        return _LazyStats(stats, 0, bits=True), _LazyStats(stats, 1)
