"""mvlint (multiverso_tpu.analysis) tests: framework contract, call-graph
resolution, per-rule fixture catches, the frozen zero-findings package
baseline, and the CLI exit-code contract (0 clean / 1 findings / 2
usage) that lets CI gate on the pass."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from multiverso_tpu.analysis import core
from multiverso_tpu.analysis import run_analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mvlint_fixtures")
BAD = os.path.join(FIXTURES, "bad")
CLEAN = os.path.join(FIXTURES, "clean")


def _write_pkg(root, files):
    for rel, text in files.items():
        path = os.path.join(str(root), rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(text))
    return str(root)


class TestSuppressionContract:
    def test_trailing_marker_suppresses_and_is_not_stale(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(msg):
                print(msg)  # mv-lint: ok(no-bare-print): fixture reason
            """})
        res = run_analysis(root=root, rules=["no-bare-print"])
        assert res.clean
        assert len(res.suppressed) == 1
        assert res.suppressed[0].rule == "no-bare-print"

    def test_own_line_marker_targets_next_code_line(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(msg):
                # mv-lint: ok(no-bare-print): fixture reason
                print(msg)
            """})
        res = run_analysis(root=root, rules=["no-bare-print"])
        assert res.clean and len(res.suppressed) == 1

    def test_reasonless_marker_is_a_finding(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(msg):
                print(msg)  # mv-lint: ok(no-bare-print)
            """})
        res = run_analysis(root=root, rules=["no-bare-print"])
        rules = {f.rule for f in res.findings}
        # the marker is rejected AND the print itself still reports
        assert rules == {"mvlint-suppression", "no-bare-print"}
        assert any("no reason" in f.message for f in res.findings)

    def test_unknown_rule_marker_is_a_finding(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            X = 1  # mv-lint: ok(no-such-rule): because
            """})
        res = run_analysis(root=root, rules=["no-bare-print"])
        assert [f.rule for f in res.findings] == ["mvlint-suppression"]
        assert "unknown rule" in res.findings[0].message

    def test_stale_suppression_is_a_finding(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(msg):
                return msg  # mv-lint: ok(no-bare-print): nothing here
            """})
        res = run_analysis(root=root, rules=["no-bare-print"])
        assert [f.rule for f in res.findings] == ["stale-suppression"]

    def test_stale_judged_only_for_rules_that_ran(self, tmp_path):
        """A --rules subset must not flag other rules' suppressions."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(msg):
                return msg  # mv-lint: ok(no-bare-print): nothing here
            """})
        res = run_analysis(root=root, rules=["bounded-blocking"])
        assert res.clean

    def test_trailing_marker_on_continuation_line_suppresses(
            self, tmp_path):
        """A marker trailing the CLOSING line of a call that spans
        lines binds to the whole simple statement — it lands on the
        finding anchored at the call's first line instead of failing
        to suppress and then reporting itself stale."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(table, rank, ids, deltas):
                if rank == 0:
                    table.AddRows(ids,
                                  deltas)  # mv-lint: ok(spmd-stream-guard): single submitter
            """})
        res = run_analysis(root=root, rules=["spmd-stream-guard"])
        assert res.clean, [f.render() for f in res.findings]
        assert len(res.suppressed) == 1

    def test_marker_on_compound_header_keeps_exact_line_scope(
            self, tmp_path):
        """A marker trailing an `if` header must NOT quietly excuse
        violations inside the block — compound statements are not the
        suppression anchor unit."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(table, rank, delta):
                if rank == 0:  # mv-lint: ok(spmd-stream-guard): header only
                    table.Add(delta)
            """})
        res = run_analysis(root=root, rules=["spmd-stream-guard"])
        rules = sorted(f.rule for f in res.findings)
        # the violation still reports AND the marker is stale
        assert rules == ["spmd-stream-guard", "stale-suppression"], \
            [f.render() for f in res.findings]

    def test_empty_rule_list_is_rejected(self):
        """run_analysis(rules=[]) must not run zero checkers and
        return clean=True — the CLI maps this KeyError to exit 2."""
        with pytest.raises(KeyError, match="empty rule list"):
            run_analysis(rules=[])

    def test_marker_in_allowlisted_file_reports_redundant(
            self, tmp_path):
        """A marker in a file the rule wholesale-ALLOWs can never be
        used — the finding must say the marker is redundant with the
        allowlist, not claim the violation it excused is gone."""
        root = _write_pkg(tmp_path / "p", {"parallel/shm_wire.py": """\
            def layout(table, rank, delta):
                if rank == 0:
                    # mv-lint: ok(spmd-stream-guard): peer ring layout
                    table.Add(delta)
            """})
        res = run_analysis(root=root, rules=["spmd-stream-guard"])
        assert [f.rule for f in res.findings] == ["stale-suppression"]
        assert "redundant" in res.findings[0].message \
            and "allowlisted" in res.findings[0].message, \
            res.findings[0].message

    def test_marker_text_inside_docstring_is_ignored(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": '''\
            def f():
                """Suppress with '# mv-lint: ok(rule)' — doc text only."""
                return 1
            '''})
        res = run_analysis(root=root, rules=["no-bare-print"])
        assert res.clean


class TestCallGraph:
    def _graph(self, tmp_path, files):
        from multiverso_tpu.analysis import callgraph
        pkg = core.PackageIndex(_write_pkg(tmp_path / "pkg", files))
        return callgraph.CallGraph(pkg)

    def test_module_attr_and_from_import_resolution(self, tmp_path):
        g = self._graph(tmp_path, {
            "wire.py": "def exchange_bytes(b):\n    return [b]\n",
            "user.py": """\
                from .wire import exchange_bytes
                from . import wire

                def a(b):
                    return exchange_bytes(b)

                def b(b):
                    return wire.exchange_bytes(b)
                """})
        assert "wire.py:exchange_bytes" in g.edges["user.py:a"]
        assert "wire.py:exchange_bytes" in g.edges["user.py:b"]

    def test_self_methods_resolve_through_inheritance(self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            class Base:
                def leaf(self):
                    return 1

            class Child(Base):
                def top(self):
                    return self.leaf()
            """})
        assert "m.py:Base.leaf" in g.edges["m.py:Child.top"]

    def test_constructor_type_inference(self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            class Probe:
                def sample_now(self):
                    return 0

            def use():
                p = Probe()
                return p.sample_now()
            """})
        assert "m.py:Probe.sample_now" in g.edges["m.py:use"]

    def test_lambda_and_callback_refs_charge_the_enclosing_def(
            self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            def bounded(fn):
                return fn()

            def fence():
                return 0

            def caller():
                bounded(lambda: fence())

            def by_name():
                bounded(fence)
            """})
        assert "m.py:fence" in g.edges["m.py:caller"]
        assert "m.py:fence" in g.edges["m.py:by_name"]

    def test_external_receivers_do_not_fan_out(self, tmp_path):
        """subprocess.run must NOT link to a package method named run."""
        g = self._graph(tmp_path, {"m.py": """\
            import subprocess

            class Job:
                def run(self):
                    return 1

            def build():
                subprocess.run(["make"])
            """})
        assert "m.py:Job.run" not in g.edges.get("m.py:build", set())

    def test_fallback_links_distinctive_names_not_container_names(
            self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            class Table:
                def ledger_probe(self):
                    return 0

                def get(self, k):
                    return k

            def scan(tables):
                for t in tables:
                    t.ledger_probe()
                    t.get("x")
            """})
        edges = g.edges["m.py:scan"]
        assert "m.py:Table.ledger_probe" in edges     # dynamic dispatch
        assert "m.py:Table.get" not in edges          # container-name bound

    def test_defs_under_module_level_guards_are_nodes(self, tmp_path):
        """The optional-dependency-fallback idiom: a def inside a
        module-level try/except or if/else is a top-level graph node —
        dropping it would silently break the never-collective guarantee
        for shimmed collectives."""
        g = self._graph(tmp_path, {"m.py": """\
            try:
                import fastpath
            except ImportError:
                def exchange(b):
                    return [b]

            if 1 == 1:
                class Shim:
                    def relay(self, b):
                        return exchange(b)

            def caller(s, b):
                return s.relay(b)
            """})
        assert "m.py:exchange" in g.edges["m.py:Shim.relay"]
        assert "m.py:Shim.relay" in g.edges["m.py:caller"]

    def test_external_collective_attrs_become_sinks(self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            def reduce_all(x, mhu):
                return mhu.process_allgather(x)
            """})
        assert "<external>:process_allgather" in g.edges["m.py:reduce_all"]


class TestFixtureCatches:
    """Every checker catches its seeded fixture and stays silent on the
    clean twin (the false-positive guard)."""

    EXPECT = {
        "no-bare-print": ("app/printy.py", 5),
        "bounded-blocking": ("app/blocky.py", 14),
        "spmd-stream-guard": ("app/spmd.py", 9),
        "hot-path-flag-cache": ("sync/server.py", 13),
        "never-collective": ("telemetry/watchdog.py", 17),
        # round 18 — the concurrency-domain rules (DESIGN.md §18)
        "thread-domains": ("app/threads.py", 11),
        "cross-domain-state": ("telemetry/export.py", 20),
        "device-work-domain": ("telemetry/watchdog.py", 27),
        "lock-order": ("app/locky.py", 15),
        "blocking-domain": ("telemetry/ops.py", 18),
    }

    @pytest.fixture(scope="class")
    def results(self):
        return (run_analysis(root=BAD), run_analysis(root=CLEAN))

    @pytest.mark.parametrize("rule", sorted(EXPECT))
    def test_rule_catches_seeded_violation_and_passes_clean_twin(
            self, results, rule):
        bad_res, clean_res = results
        path, line = self.EXPECT[rule]
        hits = [f for f in bad_res.findings if f.rule == rule]
        assert any(f.path == path and f.line == line for f in hits), \
            [f.render() for f in bad_res.findings]
        assert not [f for f in clean_res.findings if f.rule == rule], \
            [f.render() for f in clean_res.findings]

    def test_clean_twin_is_fully_clean(self, results):
        _, clean_res = results
        assert clean_res.clean, [f.render() for f in clean_res.findings]

    def test_bad_twin_has_no_unexpected_rules(self, results):
        bad_res, _ = results
        assert {f.rule for f in bad_res.findings} == set(self.EXPECT)

    def test_never_collective_reports_the_full_chain(self, results):
        bad_res, _ = results
        hit = next(f for f in bad_res.findings
                   if f.rule == "never-collective"
                   and f.path == "telemetry/watchdog.py")
        assert "collect_sample" in hit.message
        assert "parallel/multihost.py:host_barrier" in hit.message

    def test_never_collective_catches_replica_roots(self, results):
        """The round-17 roots: a replica serve loop or fan-out thread
        reaching a collective is a finding (seeded in bad/replica/),
        and the clean twins pass (pinned by the clean-twin leg of the
        parametrized test above via the EXPECT machinery's rule
        filter)."""
        bad_res, clean_res = results
        paths = {f.path for f in bad_res.findings
                 if f.rule == "never-collective"}
        assert "replica/replica.py" in paths, sorted(paths)
        assert "replica/publisher.py" in paths, sorted(paths)
        assert not [f for f in clean_res.findings
                    if f.rule == "never-collective"
                    and f.path.startswith("replica/")]

    def test_never_collective_catches_fleet_roots(self, results):
        """The round-22 roots: a fleet rollup build reaching a
        collective (seeded host_barrier in bad/telemetry/fleet.py)
        is a finding — the rollup runs on lease heartbeat daemons,
        where a collective deadlocks the beat against the engine
        stream. The clean twin passes."""
        bad_res, clean_res = results
        hits = [f for f in bad_res.findings
                if f.rule == "never-collective"
                and f.path == "telemetry/fleet.py"]
        assert hits, sorted({f.path for f in bad_res.findings})
        assert any("build_rollup" in f.message
                   and "parallel/multihost.py:host_barrier" in f.message
                   for f in hits), [f.render() for f in hits]
        assert not [f for f in clean_res.findings
                    if f.rule == "never-collective"
                    and f.path == "telemetry/fleet.py"]

    def test_never_collective_catches_standby_takeover(self, results):
        """The round-23 root: a standby takeover reaching a collective
        (seeded host_barrier in bad/elastic/standby.py) is a finding —
        force_takeover runs in a jax-free standby process with no SPMD
        stream, so a collective there hangs the successor forever. The
        clean twin passes."""
        bad_res, clean_res = results
        hits = [f for f in bad_res.findings
                if f.rule == "never-collective"
                and f.path == "elastic/standby.py"]
        assert hits, sorted({f.path for f in bad_res.findings})
        assert any("force_takeover" in f.message
                   and "parallel/multihost.py:host_barrier" in f.message
                   for f in hits), [f.render() for f in hits]
        assert not [f for f in clean_res.findings
                    if f.rule == "never-collective"
                    and f.path == "elastic/standby.py"]

    def test_bounded_blocking_catches_tcp_wire_mesh_join(self, results):
        """Round 24: the tcp wire's mesh bring-up is a bounded-blocking
        scanned surface — the seeded UNBOUNDED accept-loop join in the
        bad twin (a dead dialer would park install forever instead of
        converting to a typed deadline) is a finding, and the clean
        twin's bounded join passes."""
        bad_res, clean_res = results
        hits = [f for f in bad_res.findings
                if f.rule == "bounded-blocking"
                and f.path == "parallel/tcp_wire.py"]
        assert hits and hits[0].line == 13, \
            [f.render() for f in bad_res.findings]
        assert not [f for f in clean_res.findings
                    if f.path == "parallel/tcp_wire.py"], \
            [f.render() for f in clean_res.findings]

    def test_policy_fixture_is_gated_from_day_one(self, results):
        """Round 20: the policy plane's thread is inventoried and its
        domain is blocking-restricted — the seeded UNBOUNDED wait in
        the bad twin's evaluation loop (a parked actuator is a silent
        dead-man switch) is a blocking-domain finding, while the clean
        twin (bounded wake wait, claimed spawn site, collective-free
        roots) passes every checker."""
        bad_res, clean_res = results
        hits = [f for f in bad_res.findings
                if f.rule == "blocking-domain"
                and f.path == "policy/engine.py"]
        assert hits and hits[0].line == 26, \
            [f.render() for f in bad_res.findings]
        assert not [f for f in clean_res.findings
                    if f.path.startswith("policy/")], \
            [f.render() for f in clean_res.findings]

    def test_spmd_catches_all_five_guard_spellings(self, results):
        """Lexical guard (9), guard-clause early return (16, and the
        Get trailing it at 17), short-circuit boolean chain (21),
        comprehension rank filter (25), rank-dependent for iteration
        (30) — while the clean twin's verb-before-rank chain,
        rank-dependent raise, verb-in-first-iterable comprehension,
        and verb-after-rank-loop stay silent (short-circuit/clause
        order means the leading verb runs on every rank; an error
        path fails loudly; a loop does not quietly exit its block)."""
        bad_res, clean_res = results
        lines = {f.line for f in bad_res.findings
                 if f.rule == "spmd-stream-guard"
                 and f.path == "app/spmd.py"}
        assert {9, 16, 17, 21, 25, 30} <= lines, lines
        assert not [f for f in clean_res.findings
                    if f.rule == "spmd-stream-guard"]


class TestSpmdSameLineArms:
    def test_both_ternary_arms_on_one_line_are_distinct_findings(
            self, tmp_path):
        """Dedup is keyed on the call node, not the line: both arms of
        `Add(a) if rank == 0 else Get(b)` are separate violations, so
        both are visible before anyone writes the line-scoped
        suppression that excuses them together."""
        root = _write_pkg(tmp_path / "p", {"app/tern.py": """\
            def step(table, rank, a, b):
                return table.Add(a) if rank == 0 else table.Get(b)
            """})
        res = run_analysis(root=root, rules=["spmd-stream-guard"])
        whats = sorted(f.message.split("(")[0] for f in res.findings)
        assert len(res.findings) == 2, [f.render() for f in res.findings]
        assert "Add" in whats[0] and "Get" in whats[1], whats

    def test_suppression_is_line_scoped_and_excuses_both_arms(
            self, tmp_path):
        """The documented noqa-like contract: one marker excuses every
        same-rule finding on its line (the reason must speak for
        both), and counts as used — not stale."""
        root = _write_pkg(tmp_path / "p", {"app/tern.py": """\
            def step(table, rank, a, b):
                # mv-lint: ok(spmd-stream-guard): both arms single-submitter by design
                return table.Add(a) if rank == 0 else table.Get(b)
            """})
        res = run_analysis(root=root, rules=["spmd-stream-guard"])
        assert res.clean, [f.render() for f in res.findings]
        assert len(res.suppressed) == 2, \
            [f.render() for f in res.suppressed]


class TestBoundedBlockingNoneBound:
    def test_literal_none_bound_is_unbounded(self, tmp_path):
        """t.join(None) / evt.wait(timeout=None) block forever by
        stdlib semantics — the spelled-out-None form needs the same
        justification as the no-argument form, while a real bound
        passes."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            def f(t, evt):
                t.join(None)
                evt.wait(timeout=None)
                evt.wait(0.5)
                t.join(None)  # unbounded-ok: fixture justification
            """})
        res = run_analysis(root=root, rules=["bounded-blocking"])
        lines = sorted(f.line for f in res.findings)
        assert lines == [2, 3], [f.render() for f in res.findings]


class TestHotZoneUnderGuard:
    def test_hot_zone_method_under_module_if_is_scanned(self, tmp_path):
        """_defs_with_quals shares the flat_body guard-flattening: a
        hot-zone class shipped under a module-level if must not dodge
        the hot-path-flag-cache rule."""
        root = _write_pkg(tmp_path / "p", {"sync/server.py": """\
            if 1 == 1:
                class Server:
                    def _mh_pack(self):
                        return GetFlag("window_transport")
            """})
        res = run_analysis(root=root, rules=["hot-path-flag-cache"])
        hits = [f for f in res.findings
                if "inside hot path" in f.message]
        assert len(hits) == 1 and hits[0].path == "sync/server.py", \
            [f.render() for f in res.findings]
        # the rest is module-level rot for the zones this scratch
        # tree does not mirror — the vanished-module law
        assert all("no file matches" in f.message
                   for f in res.findings if f not in hits), \
            [f.render() for f in res.findings]

    def test_hot_zone_missing_module_is_config_rot(self, tmp_path):
        """Renaming a hot-zone module away entirely must fail the
        gate (the module-level form of config rot), not silently
        retire the protection — same law as collective.py's root/sink
        inventory, anchored at the config source."""
        root = _write_pkg(tmp_path / "p", {"other/mod.py": "X = 1\n"})
        res = run_analysis(root=root, rules=["hot-path-flag-cache"])
        assert res.findings, "vanished hot-zone modules must report"
        assert all("no file matches" in f.message
                   for f in res.findings), \
            [f.render() for f in res.findings]


class TestWholePackageBaseline:
    """The frozen baseline: every checker over the whole package, ZERO
    unsuppressed findings and zero stale suppressions. One test owns
    the full-package cost (parse + call graph), so the analysis
    overhead in tier-1 is this test, not a per-test tax."""

    def test_package_is_clean_under_every_checker(self):
        res = run_analysis()
        assert res.clean, "\n".join(f.render() for f in res.findings)
        # the registry really ran all ten laws (plus nothing unknown)
        assert {c.name for c in res.checkers} == {
            "no-bare-print", "bounded-blocking", "hot-path-flag-cache",
            "spmd-stream-guard", "never-collective",
            "thread-domains", "cross-domain-state", "device-work-domain",
            "lock-order", "blocking-domain"}

    def test_never_collective_rederives_the_restricted_root_set(self):
        """The checker's root config must cover (at minimum) every
        surface the runtime conventions already protect: ops HTTP
        handlers, the watchdog tick, the -stats_interval_s reporter,
        the accounting probes and the dashboard render — and each root
        must resolve to a real graph node with a non-trivial closure
        (a typo'd root that matches nothing would be silent)."""
        from multiverso_tpu.analysis.collective import (
            DEFAULT_ROOTS, DEFAULT_SINKS)
        # through run_analysis, not a bare checker.check: the package
        # law is ZERO UNSUPPRESSED findings — the replica fan-out
        # thread's reasoned never-collective suppression (its ring is
        # point-to-point to a non-SPMD reader) is legal, a new
        # unreasoned path is not
        res = run_analysis(rules=["never-collective"])
        assert not res.findings, \
            "\n".join(f.render() for f in res.findings)
        checker = res.checkers[0]
        conventions = {
            "ops HTTP handler": "telemetry/ops.py:_OpsHandler.do_GET",
            "watchdog tick": "telemetry/watchdog.py:Watchdog.tick",
            "stats reporter": "telemetry/export.py:StatsReporter._run",
            "accounting probe": "telemetry/accounting.py:memory_report",
            "dashboard render": "utils/dashboard.py:Dashboard.Display",
            "replica serve loop": "replica/replica.py:_LookupHandler.handle",
            "replica fan-out thread":
                "replica/publisher.py:ReplicaPublisher._run",
            # round 22 — the fleet plane's two legs
            "fleet rollup build": "telemetry/fleet.py:build_rollup",
            "fleet coordinator fold":
                "telemetry/fleet.py:FleetAccumulator.ingest",
        }
        for label, node in conventions.items():
            assert node in DEFAULT_ROOTS, label
            assert node in checker.closures, label
            # the closure walked INTO the root's callees, not just the
            # root itself — vacuous coverage would hide regressions
            assert len(checker.closures[node]) > 5, (label, node)
        # the primitive inventory stays anchored on the real surfaces
        for sink in ("parallel/multihost.py:capped_exchange",
                     "parallel/multihost.py:host_barrier",
                     "parallel/shm_wire.py:ShmWire.exchange",
                     "zoo.py:Zoo._barrier_wait"):
            assert sink in DEFAULT_SINKS

    def test_every_hot_zone_matches_real_defs(self):
        """Each HOT_ZONES entry must still name live code: a rename or
        move of a protected module/class would otherwise retire the
        hot-path-flag-cache rule silently while the zero-findings
        baseline stays green. (The checker itself reports wholesale
        per-module rot as a finding; this pins the finer per-entry
        liveness on the real package.)"""
        from multiverso_tpu.analysis.rules import HotPathFlagCacheChecker
        pkg = core.load_package()
        checker = HotPathFlagCacheChecker()
        checker.check(pkg)
        for zi, zone in enumerate(HotPathFlagCacheChecker.HOT_ZONES):
            assert checker.zone_hits[zi] > 0, zone

    def test_hot_zone_module_rot_is_a_finding(self, tmp_path):
        """A tree holding a hot-zone module whose protected defs are
        all gone (renamed away) must report config rot, not pass."""
        root = _write_pkg(tmp_path / "p", {"sync/server.py": """\
            class RenamedEngine:
                def pack(self):
                    return 1
            """})
        res = run_analysis(root=root, rules=["hot-path-flag-cache"])
        assert all(f.rule == "hot-path-flag-cache"
                   for f in res.findings)
        defrot = [f for f in res.findings
                  if "no def in files matching" in f.message]
        assert defrot and defrot[0].path == "sync/server.py", \
            [f.render() for f in res.findings]

    def test_explicitly_collective_surfaces_are_not_roots(self):
        """DisplayAll / snapshot_all_hosts are collective BY CONTRACT
        (every rank calls them at the same point) — if someone adds
        them as roots the whole pass goes red; pin the exclusion."""
        from multiverso_tpu.analysis.collective import DEFAULT_ROOTS
        assert "utils/dashboard.py:Dashboard.DisplayAll" \
            not in DEFAULT_ROOTS


class TestCLIContract:
    """Exit codes: 0 clean, 1 findings, 2 usage — pinned so the pass
    can gate future PRs from CI."""

    def _main(self, argv):
        from multiverso_tpu.analysis.cli import main
        return main(argv)

    def test_exit_0_on_clean_tree(self, capsys):
        assert self._main(["--root", CLEAN]) == 0
        assert "0 finding(s)" in capsys.readouterr().out

    def test_exit_1_on_findings(self, capsys):
        assert self._main(["--root", BAD]) == 1
        out = capsys.readouterr().out
        assert "[no-bare-print]" in out and "[never-collective]" in out

    def test_exit_2_on_unknown_rule(self, capsys):
        assert self._main(["--rules", "no-such-rule"]) == 2
        assert "usage error" in capsys.readouterr().out

    def test_exit_2_on_empty_rules(self, capsys):
        """--rules that names nothing (an unset CI variable
        interpolated into --rules "$RULES,") must not run zero
        checkers and read as a clean pass — exit 0 means every
        checker ran."""
        assert self._main(["--root", CLEAN, "--rules", ","]) == 2
        assert "names no rules" in capsys.readouterr().out

    def test_exit_2_on_bad_root(self, capsys):
        assert self._main(["--root", "/no/such/dir"]) == 2
        assert "usage error" in capsys.readouterr().out

    def test_list_names_every_rule(self, capsys):
        assert self._main(["--list"]) == 0
        out = capsys.readouterr().out
        for rule in ("no-bare-print", "bounded-blocking",
                     "hot-path-flag-cache", "spmd-stream-guard",
                     "never-collective", "thread-domains",
                     "cross-domain-state", "device-work-domain",
                     "lock-order", "blocking-domain"):
            assert rule in out

    def test_json_output_and_diag_artifact(self, tmp_path, capsys):
        diag = str(tmp_path / "diag")
        assert self._main(["--root", BAD, "--json",
                           "--diag-dir", diag]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["clean"] is False
        rules = {f["rule"] for f in payload["findings"]}
        assert "never-collective" in rules
        # the artifact rides the -mv_diag_dir layout (analysis_rank<R>)
        art = os.path.join(diag, "analysis_rank0.json")
        assert os.path.exists(art)
        with open(art) as f:
            assert json.load(f) == payload

    def test_exit_2_on_unwritable_diag_dir(self, tmp_path, capsys):
        """A diag-dir that cannot hold the artifact is a usage error
        (2) — never a crash, and never exit 1 masquerading as
        'findings present' to a CI gate."""
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("occupied")
        assert self._main(["--root", CLEAN, "--json",
                           "--diag-dir", str(blocker)]) == 2
        assert "cannot write diag artifact" in capsys.readouterr().out

    def test_module_entry_point_subprocess(self):
        """One real `python -m multiverso_tpu.analysis` run (the form
        CI invokes) — over the clean fixture tree to keep it fast."""
        proc = subprocess.run(
            [sys.executable, "-m", "multiverso_tpu.analysis",
             "--root", CLEAN],
            capture_output=True, text=True, timeout=180, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 finding(s)" in proc.stdout


class TestCallGraphPrecision:
    """The round-18 resolution upgrades: instance-attribute types,
    factory return types, super() dispatch, and the thread/handler
    callback cuts — each pinned by the false-edge class it removed."""

    def _graph(self, tmp_path, files):
        from multiverso_tpu.analysis import callgraph
        pkg = core.PackageIndex(_write_pkg(tmp_path / "pkg", files))
        return callgraph.CallGraph(pkg)

    def test_instance_attr_types_resolve_chains(self, tmp_path):
        """self.store = Store() in __init__ types self.store.probe()
        precisely — no dynamic-dispatch fan-out to same-named
        methods."""
        g = self._graph(tmp_path, {"m.py": """\
            class Store:
                def probe(self):
                    return 1

            class Decoy:
                def probe(self):
                    return 2

            class User:
                def __init__(self):
                    self.store = Store()

                def read(self):
                    return self.store.probe()
            """})
        edges = g.edges["m.py:User.read"]
        assert "m.py:Store.probe" in edges
        assert "m.py:Decoy.probe" not in edges

    def test_conflicting_attr_assignment_poisons_the_type(self, tmp_path):
        """An attribute assigned two different classes must not resolve
        through either (the fallback fan-out is the honest answer)."""
        g = self._graph(tmp_path, {"m.py": """\
            class A:
                def probe(self):
                    return 1

            class B:
                def probe(self):
                    return 2

            class User:
                def __init__(self, fast):
                    self.impl = A()
                    if fast:
                        self.impl = B()

                def read(self):
                    return self.impl.probe()
            """})
        edges = g.edges["m.py:User.read"]
        # conflict -> name fallback: BOTH probes are candidates
        assert "m.py:A.probe" in edges and "m.py:B.probe" in edges

    def test_factory_return_annotation_types_locals(self, tmp_path):
        """mon = Registry.get_monitor(...) resolves mon.observe through
        the annotated return class (Optional/forward-ref unwrapped)."""
        g = self._graph(tmp_path, {"m.py": """\
            from typing import Optional

            class Monitor:
                def observe(self):
                    return 1

            class Decoy:
                def observe(self):
                    return 2

            class Registry:
                @classmethod
                def get_monitor(cls, name) -> "Optional[Monitor]":
                    return Monitor()

            def use():
                mon = Registry.get_monitor("x")
                return mon.observe()
            """})
        edges = g.edges["m.py:use"]
        assert "m.py:Monitor.observe" in edges
        assert "m.py:Decoy.observe" not in edges

    def test_nested_def_returns_do_not_type_the_enclosing_def(
            self, tmp_path):
        """A nested callback's `return Worker()` is not the enclosing
        function's return value — return inference walks shallow."""
        g = self._graph(tmp_path, {"m.py": """\
            class Worker:
                def run(self):
                    return 1

            def register(cb):
                return cb

            def spawn():
                def cb():
                    return Worker()
                register(cb)

            def use():
                x = spawn()
                return x.run()
            """})
        assert "m.py:spawn" not in g.ret_types, g.ret_types
        assert "m.py:Worker.run" not in g.edges.get("m.py:use", set())

    def test_super_calls_resolve_through_bases_not_fallback(
            self, tmp_path):
        """super().ProcessX() dispatches to the base class — it used to
        take the name fallback and wire the caller into EVERY
        same-named method in the package."""
        g = self._graph(tmp_path, {"m.py": """\
            class Base:
                def ProcessX(self):
                    return 1

            class Other:
                def ProcessX(self):
                    return 2

            class Child(Base):
                def entry(self):
                    return super().ProcessX()
            """})
        edges = g.edges["m.py:Child.entry"]
        assert "m.py:Base.ProcessX" in edges
        assert "m.py:Other.ProcessX" not in edges

    def test_thread_spawn_target_is_a_cut_edge(self, tmp_path):
        """Thread(target=self._run) runs on the NEW thread: the spawner
        must not inherit the target's closure (the thread inventory
        classifies the target explicitly)."""
        g = self._graph(tmp_path, {"m.py": """\
            import threading

            class Daemon:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    return 0
            """})
        assert "m.py:Daemon._run" not in g.edges.get("m.py:Daemon.start",
                                                     set())

    def test_wrapped_spawn_targets_are_cut_too(self, tmp_path):
        """target=lambda: ... / target=partial(...) run on the new
        thread just like a bare ref — the cut covers the callback's
        whole subtree, not only exact Name/Attribute nodes."""
        g = self._graph(tmp_path, {"m.py": """\
            import functools
            import threading

            class Daemon:
                def start_wrapped(self):
                    threading.Thread(target=lambda: self._run()).start()

                def start_partial(self):
                    threading.Thread(
                        target=functools.partial(self._run)).start()

                def _run(self):
                    return 0
            """})
        assert "m.py:Daemon._run" not in g.edges.get(
            "m.py:Daemon.start_wrapped", set())
        assert "m.py:Daemon._run" not in g.edges.get(
            "m.py:Daemon.start_partial", set())

    def test_positional_thread_target_is_cut_too(self, tmp_path):
        """Thread(group, target, ...) — the stdlib positional spelling
        must get the same boundary cut as target=."""
        g = self._graph(tmp_path, {"m.py": """\
            import threading

            class Daemon:
                def start(self):
                    threading.Thread(None, self._run).start()

                def _run(self):
                    return 0
            """})
        assert "m.py:Daemon._run" not in g.edges.get("m.py:Daemon.start",
                                                     set())

    def test_register_handler_callback_is_a_cut_edge(self, tmp_path):
        """RegisterHandler callbacks run on the actor loop thread, not
        the registrar's — same boundary as a thread spawn."""
        g = self._graph(tmp_path, {"m.py": """\
            class Actor:
                def RegisterHandler(self, mt, fn):
                    self._h = fn

            class Engine(Actor):
                def __init__(self):
                    self.RegisterHandler(1, self._get_entry)

                def _get_entry(self, msg):
                    return msg
            """})
        assert "m.py:Engine._get_entry" not in g.edges.get(
            "m.py:Engine.__init__", set())


class TestThreadInventory:
    """The domain inventory and its config-rot law (DESIGN.md §18)."""

    def test_real_package_inventory_is_live_and_fully_claimed(self):
        """Every INVENTORY root matches a def, every configured spawn
        site still spawns, and every detected spawn is claimed — the
        baseline test pins the zero-findings form of this; this one
        pins the mechanism with its internals exposed."""
        from multiverso_tpu.analysis import threads
        inv = threads.inventory_for(core.load_package())
        assert inv.rot == [], inv.rot
        assert inv.unclaimed == [], inv.unclaimed
        # spawn detection saw the package's real thread spawns
        assert len(inv.spawns) >= 15, inv.spawns

    def test_domain_closures_cover_the_known_thread_bodies(self):
        from multiverso_tpu.analysis import threads
        inv = threads.inventory_for(core.load_package())
        expect = {
            "fanout": "replica/publisher.py:ReplicaPublisher._tick",
            "watchdog": "telemetry/watchdog.py:Watchdog.tick",
            "serving-dispatch":
                "serving/frontend.py:ServingFrontend._serve_batch",
            "replica-hb": "replica/replica.py:Replica._advance_latest",
            "engine-shard": "sync/server.py:Server._local_window",
            "ops-http": "telemetry/accounting.py:memory_report",
        }
        for domain, node in expect.items():
            assert node in inv.closures[domain], (domain, node)

    def test_ticket_fill_is_multi_domain(self):
        """The write surface behind the round-18 LookupTicket fix: the
        dispatcher, the replica serve threads and the worker-side
        inline combiner all reach _fill — exactly why it now locks."""
        from multiverso_tpu.analysis import threads
        inv = threads.inventory_for(core.load_package())
        doms = inv.domains_of("serving/frontend.py:LookupTicket._fill")
        assert {"serving-dispatch", "worker"} <= doms, doms

    def test_scratch_tree_reports_inventory_rot(self, tmp_path):
        """On a tree without the inventoried modules, every entry is
        config rot — vanished code can never silently retire its
        classification (anchored at the config source placeholder)."""
        root = _write_pkg(tmp_path / "p", {"m.py": "X = 1\n"})
        res = run_analysis(root=root, rules=["thread-domains"])
        assert res.findings
        assert all("config rot" in f.message for f in res.findings), \
            [f.render() for f in res.findings]

    def test_unclassified_spawn_is_a_finding(self, tmp_path):
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            import threading

            def go():
                threading.Thread(target=lambda: None).start()
            """})
        res = run_analysis(root=root, rules=["thread-domains"])
        hits = [f for f in res.findings
                if "unclassified thread spawn" in f.message]
        assert len(hits) == 1 and hits[0].path == "m.py", \
            [f.render() for f in res.findings]

    def test_aliased_threading_import_is_still_a_spawn(self, tmp_path):
        """`from threading import Thread as Worker` must not make the
        spawn invisible — the import record keeps the origin symbol."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            from threading import Thread as Worker

            def go():
                Worker(target=lambda: None).start()
            """})
        res = run_analysis(root=root, rules=["thread-domains"])
        hits = [f for f in res.findings
                if "unclassified thread spawn" in f.message]
        assert len(hits) == 1, [f.render() for f in res.findings]

    def test_colocated_surplus_spawn_is_unclassified(self, tmp_path):
        """A def whose spawn site one entry claims cannot smuggle a
        SECOND thread in unclassified — surplus spawns beyond the
        claim count report (count-based claiming)."""
        from multiverso_tpu.analysis import threads
        pkg = core.PackageIndex(_write_pkg(tmp_path / "p", {
            "replica/replica.py": """\
                import threading

                class Replica:
                    def start(self):
                        threading.Thread(target=self._hb_loop).start()
                        threading.Thread(target=self._new_loop).start()

                    def _hb_loop(self):
                        return 0

                    def _new_loop(self):
                        return 0
                """}))
        inv = threads.ThreadInventory(pkg)
        # one claiming entry (replica-hb), two spawns -> one surplus,
        # and it is the LATER one in source order
        surplus = [sp for sp in inv.unclaimed
                   if sp.qual == "Replica.start"]
        assert len(surplus) == 1, inv.unclaimed
        assert "_new_loop" in surplus[0].target, surplus[0]

    def test_in_package_timer_class_is_not_a_spawn(self, tmp_path):
        """utils.timer.Timer (a stopwatch) shares threading.Timer's
        name — only EXTERNAL Thread/Timer constructions count."""
        root = _write_pkg(tmp_path / "p", {
            "timerlib.py": """\
                class Timer:
                    def elapse(self):
                        return 0.0
                """,
            "m.py": """\
                from .timerlib import Timer

                def work():
                    t = Timer()
                    return t.elapse()
                """})
        res = run_analysis(root=root, rules=["thread-domains"])
        assert not [f for f in res.findings
                    if "unclassified" in f.message], \
            [f.render() for f in res.findings]


class TestConcurrencyRuleUnits:
    """Scratch-tree semantics of the four domain rules (the fixture
    trees own the catches; these pin the edge semantics)."""

    #: a minimal two-domain scratch shape: the reporter thread root and
    #: the worker-domain API surface both reach emit()
    SHAPE = {
        "telemetry/export.py": """\
            import threading


            class StatsReporter:
                def __init__(self, interval_s):
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._run,
                                                    daemon=True)

                def _run(self):
                    self.emit()

                def emit(self):
                    {write}
                    return 0
            """,
        "api.py": """\
            from .telemetry.export import StatsReporter


            def MV_Barrier():
                StatsReporter(1.0).emit()
                return 0
            """,
    }

    def _run_shape(self, tmp_path, write):
        files = dict(self.SHAPE)
        files["telemetry/export.py"] = textwrap.dedent(
            files["telemetry/export.py"]).replace("{write}", write)
        root = _write_pkg(tmp_path / "p", files)
        return run_analysis(root=root, rules=["cross-domain-state"])

    def test_unlocked_cross_domain_write_is_a_finding(self, tmp_path):
        res = self._run_shape(tmp_path, "self.last = 1")
        assert [f.rule for f in res.findings] == ["cross-domain-state"]
        msg = res.findings[0].message
        assert "reporter" in msg and "worker" in msg, msg

    def test_common_lock_scope_passes(self, tmp_path):
        res = self._run_shape(
            tmp_path,
            "with self._lock:\n                        self.last = 1")
        assert res.clean, [f.render() for f in res.findings]

    def test_init_writes_are_exempt(self, tmp_path):
        """Construction happens-before thread start — __init__ writes
        never count (every class would be multi-domain otherwise)."""
        res = self._run_shape(tmp_path, "pass")
        assert res.clean, [f.render() for f in res.findings]

    def test_suppression_and_stale_law_cover_the_new_rules(
            self, tmp_path):
        files = dict(self.SHAPE)
        files["telemetry/export.py"] = textwrap.dedent(
            files["telemetry/export.py"]).replace(
            "{write}",
            "self.last = 1  "
            "# mv-lint: ok(cross-domain-state): fixture reason")
        root = _write_pkg(tmp_path / "p", files)
        res = run_analysis(root=root, rules=["cross-domain-state"])
        assert res.clean and len(res.suppressed) == 1, \
            [f.render() for f in res.findings]

    def test_lock_order_self_loop_on_plain_lock_only(self, tmp_path):
        """Re-acquiring threading.Lock under itself is a finding; the
        same shape on RLock is legal re-entrancy."""
        for ctor, bad in (("Lock", True), ("RLock", False)):
            root = _write_pkg(tmp_path / f"p_{ctor}", {"m.py": f"""\
                import threading


                class Box:
                    def __init__(self):
                        self._mu = threading.{ctor}()

                    def outer(self):
                        with self._mu:
                            return self.inner()

                    def inner(self):
                        with self._mu:
                            return 1
                """})
            res = run_analysis(root=root, rules=["lock-order"])
            if bad:
                assert len(res.findings) == 1 \
                    and "re-acquired under itself" \
                        in res.findings[0].message, \
                    [f.render() for f in res.findings]
            else:
                assert res.clean, [f.render() for f in res.findings]

    def test_local_lock_aliases_do_not_merge_into_one_node(
            self, tmp_path):
        """Two methods aliasing DIFFERENT member locks to one local
        name must not merge into a single lock-order node (a spurious
        cycle) — a bare Name keys as a module lock only when it really
        is a module global."""
        root = _write_pkg(tmp_path / "p", {"m.py": """\
            import threading


            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def left(self):
                    lk = self._a
                    with lk:
                        with self._b:
                            return 1

                def right(self):
                    lk = self._b
                    with lk:
                        with self._a:
                            return 2
            """})
        res = run_analysis(root=root, rules=["lock-order"])
        # a module-global-keyed `lk` would read as lk->_b AND lk->_a
        # with call-composed back-edges manufacturing a cycle; the
        # name-only key keeps these out of the order graph entirely
        assert res.clean, [f.render() for f in res.findings]

    def test_blocking_domain_counts_literal_none_bounds(self, tmp_path):
        """wait(timeout=None) is the unbounded wait spelled out — the
        reachability rule treats it exactly like wait()."""
        root = _write_pkg(tmp_path / "p", {"telemetry/ops.py": """\
            import threading


            class _OpsHandler:
                def do_GET(self):
                    evt = threading.Event()
                    # unbounded-ok: fixture (per-line law only)
                    evt.wait(timeout=None)
            """})
        res = run_analysis(root=root, rules=["blocking-domain"])
        assert [f.rule for f in res.findings] == ["blocking-domain"], \
            [f.render() for f in res.findings]

    def test_blocking_domain_recv_honors_module_settimeout(
            self, tmp_path):
        """.recv() in a module that arms a socket timeout is bounded;
        without one it reports."""
        body = """\
            class _OpsHandler:
                def do_GET(self, sock):
                    {extra}
                    return sock.recv(4096)
            """
        for extra, n in (("sock.settimeout(5.0)", 0), ("pass", 1)):
            root = _write_pkg(tmp_path / f"p{n}", {
                "telemetry/ops.py": textwrap.dedent(body).replace(
                    "{extra}", extra)})
            res = run_analysis(root=root, rules=["blocking-domain"])
            assert len(res.findings) == n, \
                (extra, [f.render() for f in res.findings])

    def test_device_zone_module_rot_reports(self, tmp_path):
        """A tree without the device-zone modules reports config rot
        anchored at the config placeholder — the HOT_ZONES law applied
        to the device-sink inventory."""
        root = _write_pkg(tmp_path / "p", {"m.py": "X = 1\n"})
        res = run_analysis(root=root, rules=["device-work-domain"])
        assert res.findings
        assert all("device-zone config rot" in f.message
                   for f in res.findings), \
            [f.render() for f in res.findings]


class TestScannedCoveragePins:
    """The rglob pins (PR 11/12 idiom): the new rules scanned every
    package module — a restructure can't silently drop files from the
    concurrency analyses."""

    def test_new_rules_scan_the_whole_package(self):
        import pathlib
        pkg_root = pathlib.Path(core.default_root())
        all_rels = {p.relative_to(pkg_root).as_posix()
                    for p in pkg_root.rglob("*.py")
                    if "__pycache__" not in p.parts}
        res = run_analysis(rules=["thread-domains", "cross-domain-state",
                                  "device-work-domain", "lock-order",
                                  "blocking-domain"])
        for checker in res.checkers:
            allow = set(getattr(type(checker), "ALLOW", {}))
            missing = all_rels - checker.scanned - allow
            assert not missing, (checker.name, sorted(missing)[:10])
        # the analysis plane's own new modules are part of the scan
        for checker in res.checkers:
            assert "analysis/threads.py" in checker.scanned
            assert "analysis/concurrency.py" in checker.scanned
        # ...and the cross-package mirrors the fixtures exercise exist
        for rel in ("replica/publisher.py", "replica/replica.py",
                    "telemetry/export.py", "telemetry/watchdog.py",
                    "serving/frontend.py", "elastic/coordinator.py"):
            assert rel in all_rels, rel
        # round 19 — the seal/flat codec modules are scanned by every
        # concurrency rule (the batched-verb plane's waiter plumbing
        # and the lazy-init seal globals live exactly there)
        for checker in res.checkers:
            assert "parallel/seal.py" in checker.scanned
            assert "parallel/flat.py" in checker.scanned
        # round 21 — the compression codec module joins the pinned
        # wire-plane set (its enable predicates are hot-zone defs)
        for checker in res.checkers:
            assert "parallel/compress.py" in checker.scanned
        # round 22 — the fleet plane module is scanned (its rollup
        # build/fold run on daemon and RPC threads) and its fixture
        # mirror exists in the package
        for checker in res.checkers:
            assert "telemetry/fleet.py" in checker.scanned
        assert "telemetry/fleet.py" in all_rels
        # round 23 — the coordinator HA modules are scanned (the log
        # shipper/standby threads and the failover dialer are exactly
        # the kind of control-plane concurrency the rules police) and
        # the standby fixture mirror exists in the package
        for checker in res.checkers:
            assert "elastic/standby.py" in checker.scanned
            assert "elastic/dialer.py" in checker.scanned
        assert "elastic/standby.py" in all_rels
        # round 24 — the tcp wire joins the pinned wire-plane set (its
        # install-time accept loop is an inventoried thread and its
        # exchange/accept paths are exactly the bounded-blocking
        # surface the rules police) and its fixture mirror exists;
        # checkers that allow-list the module (cross-domain-state's
        # single-owner wire posture) legitimately skip it
        for checker in res.checkers:
            if "parallel/tcp_wire.py" in getattr(
                    type(checker), "ALLOW", {}):
                continue
            assert "parallel/tcp_wire.py" in checker.scanned, checker.name
        assert "parallel/tcp_wire.py" in all_rels


class TestMvlintEntryPoint:
    """The `mvlint` console script (pyproject [project.scripts]) must
    emit byte-identical --json to `python -m multiverso_tpu.analysis`.
    The script target is resolved from pyproject and exercised the way
    the setuptools wrapper runs it (sys.exit(main())); when a real
    mvlint executable is installed on PATH it is used directly."""

    def _json_of(self, cmd):
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=180, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        return json.loads(proc.stdout)

    def test_declared_and_parity_with_python_m(self):
        import shutil
        with open(os.path.join(REPO, "pyproject.toml")) as f:
            pyproject = f.read()
        assert 'mvlint = "multiverso_tpu.analysis.cli:main"' \
            in pyproject
        mod, _, fn = "multiverso_tpu.analysis.cli:main".partition(":")
        exe = shutil.which("mvlint")
        if exe:
            script_cmd = [exe, "--root", CLEAN, "--json"]
        else:
            script_cmd = [
                sys.executable, "-c",
                f"import sys; from {mod} import {fn} as m; "
                f"sys.exit(m(sys.argv[1:]))",
                "--root", CLEAN, "--json"]
        via_script = self._json_of(script_cmd)
        via_module = self._json_of(
            [sys.executable, "-m", "multiverso_tpu.analysis",
             "--root", CLEAN, "--json"])
        assert via_script == via_module
        assert via_script["clean"] is True


class TestAnalysisRuntimeBudget:
    """The whole-package run (all ten rules, caches cold) must stay
    cheap enough to live in tier-1 forever. Generous wall ceiling +
    the double-measure rule: a loaded box re-measures once, a genuine
    cost regression fails both attempts."""

    CEILING_S = 60.0

    def test_full_cold_run_under_ceiling(self):
        from multiverso_tpu.analysis import (callgraph, concurrency,
                                             threads)
        last = None
        for _attempt in range(2):
            core._INDEX_CACHE.clear()
            callgraph._GRAPH_CACHE.clear()
            threads._INV_CACHE.clear()
            concurrency._FACTS_CACHE.clear()
            t0 = time.perf_counter()
            res = run_analysis()
            took = time.perf_counter() - t0
            assert res.clean, "\n".join(f.render() for f in res.findings)
            if took <= self.CEILING_S:
                return
            last = took
        raise AssertionError(
            f"whole-package analysis took {last:.1f}s twice — over the "
            f"{self.CEILING_S:.0f}s tier-1 ceiling; the lint lane must "
            f"stay cheap (profile the new pass, don't raise the bar "
            f"first)")
