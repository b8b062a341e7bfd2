"""The pooled device-plane verbs against their plain arithmetic, small, on
the CPU: ``device_fetch_pooled`` sums a bag's rows where they live and
``device_apply_pooled`` takes one gradient a bag back
(``multiverso_tpu/tables/pooled.py``; the reference is
``tables/pooled_reference.py`` over ``updaters/reference.py``).

* the pooled fetch equals ``pooled_reference.pool`` on seeded tables: bit
  for bit on whole-number rows (a float32 sum of small whole numbers is
  exact in any order), within a summation bound on real ones, on one
  device and on four (the ``shard_map`` gather, then the segment sum);
* the pooled apply equals ``device_apply_rows`` of the gradients spread to
  the positions (``pooled_reference.spread``) BIT FOR BIT, rows and
  updater state, whatever the numbers: both sum a row's positions by the
  same host inverse map in the same segment sum; and equals the plain
  reference under ``default`` (``+=``), SGD, momentum and AdaGrad;
* jaggedness: empty bags first, last and in runs, a bag of one, one bag
  holding every position, a row repeated inside a bag and across bags;
* the share ties to the whole: ``split_bags`` of whole bags to every one
  of 4 and of 32 servers: the partial pooled sums add up to the whole
  bags' over the uncut table, and every server's rows after the pooled
  apply of the whole bags' gradients are its block of the uncut replay;
* compile discipline, counters, spans, refusals.

Summation bound: a bag of up to 2,048 float32 rows within +-1 summed in
another order than the reference's (float64, rounded once) differs by a
few units in the last place of the partial sums, 2,048 * 2**-23 at the
very worst.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.parallel import multihost
from multiverso_tpu.tables import (MatrixTableOption, pooled_reference,
                                   share_reference)
from multiverso_tpu.telemetry import metrics, trace
from multiverso_tpu.updaters import reference
from multiverso_tpu.updaters.base import AddOption
from multiverso_tpu.utils.log import FatalError

COLS = 128
RTOL, ATOL = 2e-5, 2e-6            # tests/test_bag_tables.py's
SUM_ATOL = 2048 * 2.0 ** -23
OPTION = dict(learning_rate=0.004, rho=0.1, momentum=0.5)
UPDATERS = ["default", "sgd", "momentum", "adagrad"]
DEVICES = [1, 4]


def _empty_first(rng):
    lengths = np.array([0, 0, 3, 1, 2], np.int32)
    return 37, rng.integers(0, 37, 6), lengths


def _empty_last(rng):
    lengths = np.array([3, 1, 2, 0, 0], np.int32)
    return 37, rng.integers(0, 37, 6), lengths


def _empty_in_runs(rng):
    lengths = np.array([3, 0, 5, 1, 0, 0, 0, 2, 7, 0, 0, 1], np.int32)
    return 37, rng.integers(0, 37, lengths.sum()), lengths


def _a_bag_of_one(rng):
    return 37, np.array([11]), np.array([1], np.int32)


def _one_bag_holds_every_position(rng):
    return 37, rng.integers(0, 37, 29), np.array([29], np.int32)


def _a_row_repeated_inside_a_bag(rng):
    ids = np.array([4, 9, 9, 9, 2, 9, 9, 30, 1])
    return 37, ids, np.array([1, 6, 2], np.int32)


def _rows_repeated_across_bags(rng):
    lengths = np.full(12, 4, np.int32)
    return 10, rng.integers(0, 10, 48), lengths     # 48 ids over 10 rows


def _one_row_table(rng):
    lengths = rng.multinomial(2048, np.full(300, 1 / 300)).astype(np.int32)
    return 1, np.zeros(2048, np.int64), lengths


def _every_bag_one_position(rng):
    return 37, rng.integers(0, 37, 21), np.ones(21, np.int32)


def _empty_bags_alone(rng):
    return 37, np.zeros(0, np.int64), np.zeros(3, np.int32)


CASES = {"empty_bags_first": _empty_first,
         "empty_bags_last": _empty_last,
         "empty_bags_in_runs": _empty_in_runs,
         "a_bag_of_one": _a_bag_of_one,
         "one_bag_holds_every_position": _one_bag_holds_every_position,
         "a_row_repeated_inside_a_bag": _a_row_repeated_inside_a_bag,
         "rows_repeated_across_bags": _rows_repeated_across_bags,
         "one_row_table_2048_positions": _one_row_table,
         "every_bag_one_position": _every_bag_one_position,
         "empty_bags_alone": _empty_bags_alone}


def _table(mv, init, updater="adagrad"):
    return mv.MV_CreateTable(MatrixTableOption(
        num_rows=len(init), num_cols=COLS,
        updater_type=None if updater == "default" else updater,
        initializer=lambda shape: init)).server()


@pytest.fixture(params=DEVICES, ids=lambda n: f"{n}dev")
def world(request):
    import multiverso_tpu as mv
    mv.MV_Init([], devices=jax.devices()[:request.param])
    yield mv
    mv.MV_ShutDown()


def _whole(rng, rows):
    return rng.integers(-8, 9, (rows, COLS)).astype(np.float32)


def _real(rng, rows):
    return rng.uniform(-1, 1, (rows, COLS)).astype(np.float32)


def _aux(srv):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(srv.state["aux"])]


# -- the fetch -----------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_fetch_pooled_equals_the_reference(world, case):
    rng = np.random.default_rng(59)
    rows, ids, lengths = CASES[case](rng)
    from multiverso_tpu.tables import pooled
    for init, atol in ((_whole(rng, rows), 0.0),
                       (_real(rng, rows), SUM_ATOL)):
        srv = _table(world, init)
        want = pooled_reference.pool(init, ids, lengths)
        got = srv.device_fetch_pooled(ids, lengths)
        assert isinstance(got, jax.Array) and got.dtype == jnp.float32
        assert got.shape == (len(lengths), COLS)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=atol)
        assert not np.asarray(got)[lengths == 0].any()
        # the rung as it is: the same rows, zeros after them
        rung = np.asarray(srv.device_fetch_pooled(ids, lengths, padded=True))
        assert rung.shape == (pooled.bag_bucket(len(lengths)), COLS)
        np.testing.assert_array_equal(rung[:len(lengths)], np.asarray(got))
        assert not rung[len(lengths):].any()


def test_a_repeat_counts_as_often_as_it_stands(world):
    init = _whole(np.random.default_rng(1), 12)
    got = np.asarray(_table(world, init).device_fetch_pooled(
        [3, 3, 3, 3, 3, 5], [5, 1]))
    np.testing.assert_array_equal(got[0], 5 * init[3])
    np.testing.assert_array_equal(got[1], init[5])


# -- the apply -----------------------------------------------------------------

@pytest.mark.parametrize("updater", UPDATERS)
@pytest.mark.parametrize("case", CASES)
def test_apply_pooled_is_apply_rows_of_the_spread_deltas(world, case,
                                                         updater):
    """Twin tables, two applies each of REAL gradients: rows and updater
    state bit for bit. Both verbs sum a row's positions by the host's
    inverse map in one segment sum over the same position order, and run
    the same row update at the same distinct bucket."""
    rng = np.random.default_rng(59)
    rows, ids, lengths = CASES[case](rng)
    init = _real(rng, rows)
    pooled, plain = _table(world, init, updater), _table(world, init,
                                                         updater)
    for _ in range(2):
        grads = (0.01 * rng.standard_normal((len(lengths), COLS))
                 ).astype(np.float32)
        pooled.device_apply_pooled(ids, lengths, jnp.asarray(grads),
                                   AddOption(**OPTION))
        if len(ids):
            plain.device_apply_rows(
                ids, jnp.asarray(pooled_reference.spread(grads, lengths)),
                AddOption(**OPTION))
    np.testing.assert_array_equal(pooled.raw(), plain.raw())
    for a, b in zip(_aux(pooled), _aux(plain)):
        np.testing.assert_array_equal(a, b)
    if len(ids):
        assert not np.array_equal(pooled.raw(), init)
    else:
        np.testing.assert_array_equal(pooled.raw(), init)


@pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("updater", UPDATERS)
def test_fetch_then_apply_equals_the_reference(world, updater, on_device):
    """Three steps as a trainer makes them (a gradient from the pooled
    row, handed back a row a bag) against the plain reference's pooled
    Adds, rule by rule."""
    rng = np.random.default_rng(7)
    rows, ids, lengths = _empty_in_runs(rng)
    init = (0.1 * rng.standard_normal((rows, COLS))).astype(np.float32)
    srv = _table(world, init, updater)
    want = reference.new_state(init, updater)
    for step in range(3):
        pooled = srv.device_fetch_pooled(ids, lengths)
        np.testing.assert_allclose(
            np.asarray(pooled),
            pooled_reference.pool(want["data"], ids, lengths),
            rtol=RTOL, atol=ATOL)
        grads = jnp.float32(OPTION["learning_rate"]) * (
            0.25 * pooled + jnp.float32(0.01 * (step + 1)))
        srv.device_apply_pooled(
            ids, lengths, grads if on_device else np.asarray(grads),
            AddOption(**OPTION))
        pooled_reference.apply_bags(updater, want, ids, lengths,
                                    np.asarray(grads), **OPTION)
    np.testing.assert_allclose(srv.raw(), want["data"], rtol=RTOL, atol=ATOL)
    if updater == "adagrad":
        np.testing.assert_allclose(
            srv.aux_to_logical("hist", srv.state["aux"]["hist"])[0],
            want["hist"][0], rtol=RTOL, atol=ATOL)


def test_rows_no_position_names_keep_their_values_bit_for_bit(world):
    rng = np.random.default_rng(5)
    rows, ids, lengths = _empty_in_runs(rng)
    init = _real(rng, rows)
    srv = _table(world, init)
    srv.device_apply_pooled(ids, lengths,
                            np.ones((len(lengths), COLS), np.float32),
                            AddOption(**OPTION))
    idle = np.setdiff1d(np.arange(rows), ids)
    assert len(idle)
    np.testing.assert_array_equal(srv.raw()[idle], init[idle])
    assert not np.asarray(srv.aux_to_logical(
        "hist", srv.state["aux"]["hist"]))[0][idle].any()


def test_the_delta_is_not_donated_and_the_rung_is_taken_back(world):
    """A gradient at the bags' rung (what a padded fetch returned) is
    applied as it is; rows past ``len(lengths)`` are never read; the
    caller's array stays readable."""
    from multiverso_tpu.tables import pooled
    rng = np.random.default_rng(5)
    rows, ids, lengths = _empty_in_runs(rng)
    init = _real(rng, rows)
    short, long = _table(world, init), _table(world, init)
    grads = (0.01 * rng.standard_normal((len(lengths), COLS))
             ).astype(np.float32)
    at_rung = np.full((pooled.bag_bucket(len(lengths)), COLS), np.nan,
                      np.float32)
    at_rung[:len(lengths)] = grads
    kept = jnp.asarray(at_rung)
    short.device_apply_pooled(ids, lengths, jnp.asarray(grads))
    long.device_apply_pooled(ids, lengths, kept)
    np.testing.assert_array_equal(short.raw(), long.raw())
    assert np.isfinite(long.raw()).all()
    np.testing.assert_array_equal(np.asarray(kept), at_rung)


# -- the share and the whole ---------------------------------------------------

@pytest.mark.parametrize("keep_empty", [True, False],
                         ids=["empty_kept", "empty_dropped"])
@pytest.mark.parametrize("servers,rows", [(4, 37), (32, 95)])
def test_the_shares_pool_and_apply_to_the_whole(servers, rows, keep_empty):
    """An uncut table of ``rows`` rows replayed by the reference, and
    ``servers`` tables of a block each (one world, one device: a server of
    the deployment is a table here) driven by the pooled verbs with what
    ``split_bags`` hands each. Whole-number rows and gradients, the
    default updater: every comparison is bit for bit. Under AdaGrad the
    blocks equal the uncut replay's blocks within the row tolerance."""
    import multiverso_tpu as mv
    rng = np.random.default_rng(servers)
    lengths = rng.integers(0, 9, 40).astype(np.int32)
    ids = rng.integers(0, rows, lengths.sum())
    init = _whole(rng, rows)
    grads = rng.integers(-4, 5, (len(lengths), COLS)).astype(np.float32)
    small = (0.01 * grads).astype(np.float32)
    whole = pooled_reference.pool(init, ids, lengths)
    uncut = {u: pooled_reference.apply_bags(
        u, reference.new_state(init, u), ids, lengths, d, **OPTION)
        for u, d in (("default", grads), ("adagrad", small))}
    mv.MV_Init([], devices=jax.devices()[:1])
    try:
        total, seen = np.zeros_like(whole), 0
        blocks = {"default": [], "adagrad": []}
        for s in range(servers):
            first, past = share_reference.share_bounds(rows, servers, s)
            mine, part, bags = pooled_reference.split_bags(
                ids, lengths, rows, servers, s, keep_empty=keep_empty)
            assert part.sum() == len(mine) and len(part) == len(bags)
            assert keep_empty or (part > 0).all()
            seen += len(mine)
            if past == first:           # a server past the table's end
                assert not len(mine)
                continue
            if not len(bags):           # no bag reaches this server
                blocks["default"].append(init[first:past])
                blocks["adagrad"].append(init[first:past])
                continue
            srv = _table(mv, init[first:past], "default")
            total[bags] += np.asarray(srv.device_fetch_pooled(mine, part))
            srv.device_apply_pooled(mine, part, grads[bags])
            blocks["default"].append(srv.raw())
            ada = _table(mv, init[first:past], "adagrad")
            ada.device_apply_pooled(mine, part, small[bags],
                                    AddOption(**OPTION))
            blocks["adagrad"].append(ada.raw())
        assert seen == len(ids)
        np.testing.assert_array_equal(total, whole)
        np.testing.assert_array_equal(np.concatenate(blocks["default"]),
                                      uncut["default"]["data"])
        np.testing.assert_allclose(np.concatenate(blocks["adagrad"]),
                                   uncut["adagrad"]["data"], rtol=RTOL,
                                   atol=ATOL)
    finally:
        mv.MV_ShutDown()


def test_split_bags_keeps_order_and_offsets():
    ids, lengths = [9, 0, 5, 4, 7, 1], [2, 0, 3, 1]
    got = [pooled_reference.split_bags(ids, lengths, 10, 2, s)
           for s in range(2)]
    np.testing.assert_array_equal(got[0][0], [0, 4, 1])      # rows 0..4
    np.testing.assert_array_equal(got[0][1], [1, 0, 1, 1])
    np.testing.assert_array_equal(got[1][0], [4, 0, 2])      # rows 5..9
    np.testing.assert_array_equal(got[1][1], [1, 0, 2, 0])
    np.testing.assert_array_equal(got[0][2], [0, 1, 2, 3])
    dropped = pooled_reference.split_bags(ids, lengths, 10, 2, 1,
                                          keep_empty=False)
    np.testing.assert_array_equal(dropped[1], [1, 2])
    np.testing.assert_array_equal(dropped[2], [0, 2])


def test_reference_pool_and_spread_are_transposes():
    """<pool(rows), d> == <rows, spread(d)> over the positions: the
    backward of a sum, on whole numbers exactly."""
    rng = np.random.default_rng(0)
    lengths = np.array([2, 0, 3, 1])
    ids = rng.integers(0, 9, 6)
    rows = rng.integers(-8, 9, (9, 4)).astype(np.float32)
    d = rng.integers(-4, 5, (4, 4)).astype(np.float32)
    left = (pooled_reference.pool(rows, ids, lengths) * d).sum()
    right = (rows[ids] * pooled_reference.spread(d, lengths)).sum()
    assert left == right
    with pytest.raises(ValueError):
        pooled_reference.pool(rows, ids, [2, 2])
    with pytest.raises(ValueError):
        pooled_reference.spread(d, [1, 1])


# -- what a verb costs, compiles, counts and refuses ---------------------------

def _moved(before, after, name):
    return (after.get(name, {}).get("value", 0.0)
            - before.get(name, {}).get("value", 0.0))


def test_one_copy_and_one_program_a_verb(world):
    """At the rungs the verbs pad to nothing else runs: the padded fetch
    is a copy of two int vectors and one call, the apply of the rung a
    copy of three and one call; the cut to ``len(lengths)`` rows is one
    call more."""
    rng = np.random.default_rng(2)
    rows, ids, lengths = _empty_in_runs(rng)
    srv = _table(world, _real(rng, rows))
    srv._device_opt(AddOption(**OPTION))    # the option's scalars: kept
    before = metrics.snapshot()
    pooled = srv.device_fetch_pooled(ids, lengths, padded=True)
    fetched = metrics.snapshot()
    srv.device_apply_pooled(ids, lengths, pooled, AddOption(**OPTION))
    applied = metrics.snapshot()
    srv.device_fetch_pooled(ids, lengths)
    cut = metrics.snapshot()
    for a, b, copies, calls in ((before, fetched, 2, 1),
                                (fetched, applied, 3, 1),
                                (applied, cut, 2, 2)):
        assert _moved(a, b, "table.device.calls") == calls
        assert _moved(a, b, "table.device.h2d_copies") == copies
        assert _moved(a, b, "table.device.d2h_copies") == 0
    n, bags = len(ids), len(lengths)
    empty = int((lengths == 0).sum())
    for name, want in (
            ("table.device_fetch_pooled.bags", bags),
            ("table.device_fetch_pooled.positions", n),
            ("table.device_fetch_pooled.empty_bags", empty),
            ("table.device_fetch_pooled.bytes", bags * COLS * 4),
            ("table.device_apply_pooled.bags", bags),
            ("table.device_apply_pooled.positions", n),
            ("table.device_apply_pooled.unique_rows", len(np.unique(ids))),
            ("table.device_apply_pooled.bytes", bags * COLS * 4),
            ("table.device_apply_pooled.d2h_bytes", 0),
            # the row verbs' counters are theirs alone
            ("table.device_fetch.rows", 0),
            ("table.device_apply.rows", 0),
            ("table.device_apply.combined_verbs", 0)):
        assert _moved(before, applied, name) == want, name
    assert "table.device_apply_pooled.d2h_bytes" in applied


def test_a_second_verb_in_a_key_builds_no_program(world):
    """Programs are keyed by (position rung, bag rung, distinct class): a
    verb whose positions, bags and distinct rows differ inside one key
    builds nothing; a new bag rung builds one fetch and one apply."""
    from multiverso_tpu.tables import pooled
    rng = np.random.default_rng(3)
    srv = _table(world, _real(rng, 400))

    def drive(positions, bags, rows):
        named = rng.permutation(400)[:rows]
        ids = named[rng.integers(0, rows, positions)]
        ids[:rows] = named          # exactly ``rows`` distinct rows
        cuts = np.sort(rng.choice(np.arange(1, positions), bags - 1,
                                  replace=False))
        lengths = np.diff(np.concatenate([[0], cuts, [positions]]))
        got = srv.device_fetch_pooled(ids, lengths, padded=True)
        srv.device_apply_pooled(ids, lengths, got, AddOption(**OPTION))
        return pooled.program_key(positions, bags, len(np.unique(ids)))

    def builds():
        snap = metrics.snapshot()
        return tuple(snap.get(f"jit.program.{name}.builds",
                              {"value": 0.0})["value"]
                     for name in ("_fetch_pooled", "_apply_pooled"))

    first = drive(600, 300, 100)
    programs = srv._pooled_programs
    sizes = (programs.fetch._cache_size(), programs.apply._cache_size())
    built = builds()
    assert sizes == (1, 1)
    for shape in ((590, 310, 90), (640, 257, 120), (513, 320, 70)):
        key = drive(*shape)
        assert key == first == (640, 320, 128)
    assert (programs.fetch._cache_size(), programs.apply._cache_size()
            ) == sizes
    assert builds() == built
    assert drive(600, 350, 100)[1] == 384           # a new bag rung
    assert (programs.fetch._cache_size(), programs.apply._cache_size()
            ) == (2, 2)
    assert builds() == (built[0] + 1, built[1] + 1)


def test_program_key_is_the_three_rungs():
    from multiverso_tpu.parallel.mesh import next_bucket
    from multiverso_tpu.tables import pooled
    assert pooled.program_key(204800, 62650, 188300) == (
        next_bucket(204800), 65536, 262144)
    assert pooled.program_key(2048, 2016, 1) == (2048, 2048, 8)
    assert pooled.program_key(55296, 37350, 54000) == (57344, 40960, 65536)
    assert pooled.bag_bucket(3) == 8 and pooled.bag_bucket(300) == 320


def test_a_table_that_never_pools_pays_nothing():
    """No module, no program, no attribute before the first pooled verb:
    ``import multiverso_tpu`` and a table's creation and row verbs leave
    ``tables/pooled.py`` unimported."""
    import subprocess
    code = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        "import multiverso_tpu as mv\n"
        "from multiverso_tpu.tables import MatrixTableOption\n"
        "mv.MV_Init([])\n"
        "srv = mv.MV_CreateTable(MatrixTableOption(num_rows=37, "
        "num_cols=128, updater_type='adagrad')).server()\n"
        "srv.device_apply_rows([1, 2, 2], np.ones((3, 128), np.float32))\n"
        "srv.device_fetch_rows([1, 2, 2])\n"
        "assert 'multiverso_tpu.tables.pooled' not in sys.modules\n"
        "assert '_pooled_programs' not in srv.__dict__\n"
        "srv.device_fetch_pooled([1, 2, 2], [2, 1])\n"
        "assert 'multiverso_tpu.tables.pooled' in sys.modules\n"
        "assert srv._pooled_programs.apply._cache_size() == 0\n"
        "mv.MV_ShutDown()\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=110,
                         cwd=__file__.rsplit("/tests/", 1)[0])
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), (
        out.stdout + out.stderr)


class TestSpans:
    """The two verbs' spans, as ``tests/test_telemetry.py`` reads the
    row verbs': names, nesting, order, the program a ``.call`` names."""

    @staticmethod
    def _spans():
        return [e for e in trace.to_chrome_trace()["traceEvents"]
                if e["ph"] == "X"]

    @staticmethod
    def _children(spans, parent):
        return sorted((e for e in spans if e["args"]["parent_id"]
                       == parent["args"]["span_id"]), key=lambda e: e["ts"])

    def _drive(self, trace_on):
        import multiverso_tpu as mv
        trace._reset_for_tests()
        metrics._reset_for_tests()
        mv.MV_Init(["-trace=true"] if trace_on else [])
        try:
            rng = np.random.default_rng(2)
            rows, ids, lengths = _empty_in_runs(rng)
            srv = _table(mv, _real(rng, rows))
            for _ in range(2):      # the second run is the one read
                mark = len(self._spans())
                pooled = srv.device_fetch_pooled(ids, lengths, padded=True)
                srv.device_apply_pooled(ids, lengths, pooled,
                                        AddOption(**OPTION))
            return self._spans()[mark:]
        finally:
            mv.MV_ShutDown()

    def test_no_span_with_trace_off(self):
        assert self._drive(False) == []

    @pytest.mark.parametrize("verb,want", [
        ("device_fetch_pooled", [
            (".prepare", []),
            (".dispatch", [(".place", None), (".call", "_fetch_pooled")])]),
        ("device_apply_pooled", [
            (".prepare", [(".unique", None), ("combine", None)]),
            (".dispatch", [(".place", None), (".call", "_apply_pooled")])]),
    ])
    def test_spans_of_a_verb(self, verb, want):
        spans = self._drive(True)
        name = "server.table." + verb
        top = [e for e in spans if e["name"] == name]
        assert len(top) == 1 and top[0]["cat"] == "server"
        assert "table_id" in top[0]["args"]
        kids = self._children(spans, top[0])
        assert [k["name"] for k in kids] == [name + s for s, _ in want]
        for kid, (suffix, grandkids) in zip(kids, want):
            got = [(g["name"], g["args"].get("program"))
                   for g in self._children(spans, kid)]
            assert got == [
                ((name + "." + s if s == "combine" else kid["name"] + s), p)
                for s, p in grandkids]


REFUSED = {
    "lengths_short_of_the_ids": ([1, 2, 3], [1, 1]),
    "lengths_over_the_ids": ([1, 2, 3], [2, 2]),
    "negative_length": ([1, 2, 3], [4, -1]),
    "no_bag_at_all": ([], []),
    "lengths_not_whole_numbers": ([1, 2, 3], [1.5, 1.5]),
    "id_out_of_range": ([1, 2, 37], [2, 1]),
    "negative_id": ([1, -1, 3], [2, 1]),
}


@pytest.mark.parametrize("case", REFUSED)
def test_bad_bags_are_refused_by_both_verbs(mv_env, case):
    ids, lengths = REFUSED[case]
    srv = _table(mv_env, np.zeros((37, COLS), np.float32))
    with pytest.raises(FatalError):
        srv.device_fetch_pooled(ids, lengths)
    with pytest.raises(FatalError):
        srv.device_apply_pooled(ids, lengths,
                                np.ones((len(lengths), COLS), np.float32))
    assert not srv.raw().any()


def test_deltas_of_another_length_are_refused(mv_env):
    srv = _table(mv_env, np.zeros((37, COLS), np.float32))
    for shape in ((3, COLS), (2, COLS - 1), (2 * COLS,)):
        with pytest.raises(FatalError):
            srv.device_apply_pooled([1, 2, 3], [2, 1],
                                    np.ones(shape, np.float32))
    assert not srv.raw().any()


def test_a_multi_process_world_is_refused(mv_env, monkeypatch):
    """The docstring's refusal: a ``CHECK`` that says why, before
    anything is counted or copied; no silent wrong answer."""
    srv = _table(mv_env, np.zeros((37, COLS), np.float32))
    monkeypatch.setattr(multihost, "world_size", lambda: 2)
    before = metrics.snapshot()
    with pytest.raises(FatalError, match="one-process world"):
        srv.device_fetch_pooled([1, 2, 3], [2, 1])
    with pytest.raises(FatalError, match="one-process world"):
        srv.device_apply_pooled([1, 2, 3], [2, 1],
                                np.ones((2, COLS), np.float32))
    assert _moved(before, metrics.snapshot(), "table.device.calls") == 0
    monkeypatch.undo()
    assert not srv.raw().any()
