"""The start-up ledger (``telemetry/startup.py``): set-up by phase, and
what JAX traced, lowered, compiled or loaded, by program.

JAX reports every jitted function a program calls while it is traced; the
ledger keeps the outermost phase of a thread, and these tests hold it to
that. Every case builds functions of its own: a function object that was
built before would be served by jit's in-memory cache and report nothing.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.telemetry import metrics, startup
from multiverso_tpu.telemetry import trace as ttrace


@pytest.fixture(autouse=True)
def _listening():
    startup.listen()


def _hist(name):
    rec = metrics.snapshot().get(name, {})
    return rec.get("count", 0), rec.get("sum", 0.0)


def _value(name):
    return metrics.snapshot().get(name, {}).get("value", 0.0)


def _program():
    """A jitted function that calls two jitted functions."""
    @jax.jit
    def ledger_inner_a(x):
        return x * 2

    @jax.jit
    def ledger_inner_b(x):
        return x + 1

    @jax.jit
    def ledger_outer(x):
        return ledger_inner_a(x) + ledger_inner_b(x)

    return ledger_outer


PHASES = ("jit.trace_s", "jit.lower_s", "jit.backend_s")


@pytest.mark.parametrize("hist", PHASES)
def test_a_program_that_calls_jitted_functions_is_one_sample(hist):
    prog, x = _program(), np.ones(4, np.float32)   # numpy: no eager program
    before = _hist(hist)
    prog(x)
    first = _hist(hist)
    assert first[0] == before[0] + 1 and first[1] > before[1]
    prog(x)                                 # the same shape: nothing built
    assert _hist(hist) == first
    prog(np.ones(5, np.float32))            # a new shape: one more
    assert _hist(hist)[0] == first[0] + 1


def test_the_inner_functions_are_not_programs_of_their_own():
    _program()(np.ones(3, np.float32))
    snap = metrics.snapshot()
    assert snap["jit.program.ledger_outer.builds"]["value"] >= 1
    for inner in ("ledger_inner_a", "ledger_inner_b"):
        assert f"jit.program.{inner}.seconds" not in snap
        assert f"jit.program.{inner}.builds" not in snap


def test_the_table_names_the_program():
    @jax.jit
    def ledger_named_program(x):
        return x - 3

    ledger_named_program(np.ones(2, np.float32))
    assert _value("jit.program.ledger_named_program.builds") == 1
    row = next(r for r in startup.report()
               if r["program"] == "ledger_named_program")
    # trace + lower + backend, summed under the name without its jit(...)
    assert row["builds"] == 1 and row["seconds"] > 0
    assert not any(r["program"].startswith("jit(")
                   for r in startup.report())


def test_an_eager_operation_shows_under_its_own_name():
    before = _value("jit.program.cumsum.builds")
    jnp.cumsum(jnp.asarray(np.arange(7, dtype=np.float32)))
    assert _value("jit.program.cumsum.builds") == before + 1


def test_the_report_is_sorted_by_seconds():
    snap = {"jit.program.a.seconds": {"value": 0.5},
            "jit.program.a.builds": {"value": 2.0},
            "jit.program.mod.fn.seconds": {"value": 1.5},
            "jit.program.mod.fn.builds": {"value": 1.0},
            "jit.program.mod.fn.cache_hits": {"value": 1.0},
            "jit.program.traced_only.seconds": {"value": 0.1},
            "jit.trace_s": {"count": 3, "sum": 2.1}}
    assert startup.report(snap) == [
        {"program": "mod.fn", "seconds": 1.5, "builds": 1, "cache_hits": 1},
        {"program": "a", "seconds": 0.5, "builds": 2, "cache_hits": 0},
        {"program": "traced_only", "seconds": 0.1, "builds": 0,
         "cache_hits": 0}]


def test_a_second_build_is_a_hit_of_the_persistent_cache(tmp_path):
    from jax._src import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        cc.reset_cache()
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], -1)

        def build():
            @jax.jit
            def ledger_cached_program(x):
                return x * 7 + 2
            return ledger_cached_program(np.ones(6, np.float32))

        misses = _value("jit.cache_misses")
        build()
        if _value("jit.cache_misses") != misses + 1:
            pytest.skip("this backend's persistent cache kept no program")
        hits, loads = _value("jit.cache_hits"), _hist("jit.cache_load_s")
        jax.clear_caches()
        build()
        if _value("jit.cache_hits") != hits + 1:
            pytest.skip("this backend's persistent cache served no program")
        assert _hist("jit.cache_load_s")[0] == loads[0] + 1
        assert _value("jit.program.ledger_cached_program.builds") == 2
        assert _value("jit.program.ledger_cached_program.cache_hits") == 1
        row = next(r for r in startup.report()
                   if r["program"] == "ledger_cached_program")
        assert (row["builds"], row["cache_hits"]) == (2, 1)
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_compile_inside_a_phase_is_not_unphased():
    @jax.jit
    def ledger_phased(x):
        return x * 5

    @jax.jit
    def ledger_unphased(x):
        return x * 6

    x = np.ones(4, np.float32)
    before, samples = _value("jit.unphased_s"), _hist("jit.backend_s")[0]
    with startup.phase("test.ledger.compile"):
        ledger_phased(x)
    assert _hist("jit.backend_s")[0] == samples + 1
    assert _value("jit.unphased_s") == before
    ledger_unphased(x)
    assert _value("jit.unphased_s") > before


def test_a_phase_inside_a_phase_is_counted_once():
    phased = _value("startup.phased_s")
    with startup.phase("test.ledger.outer"):
        with startup.phase("test.ledger.inner"):
            pass
        inside = _value("startup.phased_s")
    assert inside == phased                     # the inner one added nothing
    outer, inner = (_value("test.ledger.outer_s"),
                    _value("test.ledger.inner_s"))
    assert 0 < inner <= outer
    assert _value("startup.phased_s") == pytest.approx(phased + outer)
    with startup.phase("test.ledger.outer"):    # again: the gauge adds
        pass
    assert _value("test.ledger.outer_s") > outer


def test_a_phase_on_another_thread_is_outermost_there():
    phased, done = _value("startup.phased_s"), []

    def other():
        with startup.phase("test.ledger.thread"):
            done.append(1)

    with startup.phase("test.ledger.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert _value("startup.phased_s") > phased
    assert done


def test_a_histogram_phase_keeps_one_sample_a_run():
    before = _hist("test.ledger.made_s")[0]
    for _ in range(3):
        with startup.phase("test.ledger.made", histogram=True):
            pass
    snap = metrics.snapshot()["test.ledger.made_s"]
    assert snap["type"] == "histogram" and snap["count"] == before + 3


def test_listen_twice_registers_once():
    from jax._src import monitoring
    startup.listen()
    startup.listen()
    for listeners, ours in (
            (monitoring.get_scalar_listeners(), startup._on_start),
            (monitoring.get_event_duration_listeners(),
             startup._on_duration),
            (monitoring.get_event_listeners(), startup._on_event)):
        assert listeners.count(ours) == 1


def test_mv_init_leaves_the_gauges_of_a_start():
    import multiverso_tpu as mv
    mv.__dict__.pop("MV_Init", None)    # the lazy import runs again
    mv.MV_Init([])
    try:
        snap = metrics.snapshot()
        for name in ("mv.import_s", "mv.init_s", "mv.init.mesh_s",
                     "mv.init.planes_s"):
            assert snap[name]["type"] == "gauge" and snap[name]["value"] > 0
        assert snap["mv.init_s"]["value"] >= (
            snap["mv.init.mesh_s"]["value"]
            + snap["mv.init.planes_s"]["value"])
    finally:
        mv.MV_ShutDown()


def test_table_creation_is_a_phase_and_still_one_sample_a_table():
    import multiverso_tpu as mv
    from multiverso_tpu.tables import MatrixTableOption
    mv.MV_Init([])
    try:
        made, phased = _hist("table.create_s"), _value("startup.phased_s")
        mv.MV_CreateTable(MatrixTableOption(num_rows=24, num_cols=4))
        now = _hist("table.create_s")
        assert now[0] == made[0] + 1
        assert _value("startup.phased_s") >= phased + (now[1] - made[1])
    finally:
        mv.MV_ShutDown()


def _compile_under_a_span(argv):
    import multiverso_tpu as mv

    @jax.jit
    def ledger_hot_path(x):
        return x / 2

    ttrace._reset_for_tests()
    mv.MV_Init(argv)
    try:
        with ttrace.span("server.test.verb"):
            ledger_hot_path(np.ones(4, np.float32))
        return ttrace.to_chrome_trace()["traceEvents"]
    finally:
        mv.MV_ShutDown()
        ttrace._reset_for_tests()


def test_with_trace_on_a_compile_is_a_span_under_the_open_span():
    events = _compile_under_a_span(["-trace=true"])
    by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
    verb = by_name["server.test.verb"]
    for kind in ("trace", "lower", "backend"):
        held = by_name[f"server.test.verb.jit.{kind}"]
        assert held["args"]["program"] == "ledger_hot_path"
        assert verb["ts"] <= held["ts"]
        assert held["ts"] + held["dur"] <= verb["ts"] + verb["dur"]


def test_with_trace_off_the_ring_stays_empty():
    events = _compile_under_a_span([])
    assert [e for e in events if e.get("ph") != "M"] == []


def test_telemetry_off_leaves_the_registry_empty():
    import multiverso_tpu as mv
    from multiverso_tpu.tables import MatrixTableOption

    @jax.jit
    def ledger_before_the_flags(x):
        return x + 9

    @jax.jit
    def ledger_after_the_flags(x):
        return x + 10

    ledger_before_the_flags(np.ones(2, np.float32))
    assert metrics.snapshot()       # counted under the flag's default
    mv.__dict__.pop("MV_Init", None)
    mv.MV_Init(["-telemetry=false"])
    try:
        ledger_after_the_flags(np.ones(2, np.float32))
        mv.MV_CreateTable(MatrixTableOption(num_rows=16, num_cols=4))
        assert metrics.snapshot() == {}
        assert startup.report() == []
    finally:
        mv.MV_ShutDown()


def test_a_named_thread_reads_its_name_in_the_kernel():
    comm = []

    def run():
        ttrace.name_native_thread()
        path = f"/proc/self/task/{threading.get_native_id()}/comm"
        if os.path.exists(path):
            with open(path) as f:
                comm.append(f.read().strip())

    t = threading.Thread(target=run, name="mv-ledger-test-thread")
    t.start()
    t.join()
    if not comm:
        pytest.skip("no /proc to read a thread's name from")
    assert comm == ["mv-ledge-thread"]      # 15 bytes: the first 8, the last 7


def test_an_actor_thread_carries_its_name():
    import multiverso_tpu as mv
    mv.MV_Init([])
    try:
        names = set()
        for task in os.listdir("/proc/self/task"):
            with open(f"/proc/self/task/{task}/comm") as f:
                names.add(f.read().strip())
        assert any(n.startswith("mv-server") for n in names), names
    finally:
        mv.MV_ShutDown()
