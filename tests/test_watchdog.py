"""Watchdog plane (round 13): typed online alert rules, the process
memory ledger, and their ops surfaces.

* rule units — fire/clear hysteresis semantics (fire only after
  ``fire_after`` consecutive breaches, clear only after
  ``clear_after`` healthy ticks, HOLD freezes the state), and every
  slope rule driven over SYNTHETIC sample series (shard imbalance,
  shm backpressure, apply-pool saturation, mailbox/memory growth,
  snapshot staleness, the straggler proxy);
* eager registration — every ``alert.<rule>`` counter and ``mem.*``
  family gauge scrapes at ZERO from the first /metrics read (the PR 6
  rule);
* /memory — grammar + the acceptance cross-check: the ledger's
  per-table and per-version numbers reconcile with independently
  computed ``nbytes()`` (exact for host-backed state, the documented
  logical-bytes bound for device residence);
* overhead guard — the blocking host round with a fast watchdog tick
  armed must stay within max(2%, 2x noise) of ``-mv_watchdog_s=0``
  (off/on interleaved, failure must reproduce — the established
  double-measure rule for this box's slow patches);
* 2-proc drill — chaos ``apply.delay`` on rank 0 trips the straggler
  alert on rank 0 ONLY (live at /alerts, in the flight ring, and as
  /healthz ``warn``), stable across ticks; a clean run fires nothing.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.telemetry import accounting, flight, metrics, ops
from multiverso_tpu.telemetry import watchdog as twd
from multiverso_tpu.telemetry.watchdog import (
    HOLD, ApplyPoolSaturationRule, MailboxBacklogRule, MemoryGrowthRule,
    ReplicaLagRule, Rule, ShardImbalanceRule, ShmBackpressureRule,
    SnapshotStaleRule, StragglerRule, Watchdog)

from tests.test_multihost import run_two_process


def _scrape(path: str) -> tuple:
    port = ops.port()
    assert port is not None, "ops endpoint not running"
    resp = urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10)
    return resp.status, resp.read().decode()


# -- hysteresis ----------------------------------------------------------


class _ScriptedRule(Rule):
    """Replays a scripted verdict sequence (None / HOLD / str)."""

    name = "scripted"
    fire_after = 2
    clear_after = 3

    def __init__(self, verdicts):
        self.verdicts = list(verdicts)
        self.i = 0

    def check(self, history):
        v = self.verdicts[min(self.i, len(self.verdicts) - 1)]
        self.i += 1
        return v


class TestHysteresis:
    def _wd(self, verdicts):
        return Watchdog(0.0, rules=[_ScriptedRule(verdicts)])

    def test_fires_only_after_consecutive_breaches(self):
        wd = self._wd(["bad", None, "bad", "bad"])
        assert wd.evaluate({"t": 1.0}) == []        # 1 breach: armed
        assert wd.evaluate({"t": 2.0}) == []        # healthy: reset
        assert wd.evaluate({"t": 3.0}) == []        # 1 breach again
        assert wd.evaluate({"t": 4.0}) == ["scripted"]  # 2nd: FIRE
        assert [a["rule"] for a in wd.active_alerts()] == ["scripted"]

    def test_fire_increments_counter_and_flight_event(self):
        flight._reset_for_tests()
        before = metrics.counter("alert.scripted").value
        wd = self._wd(["bad", "bad", "bad"])
        wd.evaluate({"t": 1.0})
        wd.evaluate({"t": 2.0})
        assert metrics.counter("alert.scripted").value == before + 1
        kinds = [e["kind"] for e in flight.events()]
        assert "alert.scripted" in kinds
        # a firing rule stays ONE alert however long it persists
        wd.evaluate({"t": 3.0})
        assert metrics.counter("alert.scripted").value == before + 1

    def test_clears_only_after_consecutive_healthy(self):
        wd = self._wd(["bad", "bad", None, "bad", None, None, None])
        for t in range(2):
            wd.evaluate({"t": float(t)})
        assert wd.active_alerts()                   # fired
        wd.evaluate({"t": 2.0})                     # healthy x1
        wd.evaluate({"t": 3.0})                     # breach: good reset
        wd.evaluate({"t": 4.0})
        wd.evaluate({"t": 5.0})
        assert wd.active_alerts()                   # still active
        wd.evaluate({"t": 6.0})                     # healthy x3: clear
        assert wd.active_alerts() == []

    def test_hold_freezes_state_no_flapping(self):
        wd = self._wd(["bad", "bad"] + [HOLD] * 10)
        wd.evaluate({"t": 1.0})
        wd.evaluate({"t": 2.0})
        assert wd.active_alerts()
        for t in range(10):                 # idle ticks: verdict holds
            wd.evaluate({"t": 3.0 + t})
        assert [a["rule"] for a in wd.active_alerts()] == ["scripted"]

    def test_buggy_rule_is_contained(self):
        class _Boom(Rule):
            name = "boom"

            def check(self, history):
                raise RuntimeError("rule bug")

        wd = Watchdog(0.0, rules=[_Boom()])
        assert wd.evaluate({"t": 1.0}) == []        # no escape
        assert wd.active_alerts() == []


# -- slope rules on synthetic series -------------------------------------


class TestSlopeRules:
    def test_shard_imbalance_fires_on_skewed_streams(self):
        r = ShardImbalanceRule(ratio=1.5, min_busy_s=0.05)
        h = [{"shards": [{"shard": 0, "apply_busy_s": 0.0},
                         {"shard": 1, "apply_busy_s": 0.0}]},
             {"shards": [{"shard": 0, "apply_busy_s": 0.9},
                         {"shard": 1, "apply_busy_s": 0.01}]}]
        breach = r.check(h)
        assert isinstance(breach, str) and "shard 0" in breach

    def test_shard_imbalance_balanced_and_idle(self):
        r = ShardImbalanceRule()
        balanced = [{"shards": [{"shard": 0, "apply_busy_s": 0.0},
                                {"shard": 1, "apply_busy_s": 0.0}]},
                    {"shards": [{"shard": 0, "apply_busy_s": 0.5},
                                {"shard": 1, "apply_busy_s": 0.45}]}]
        assert r.check(balanced) is None
        idle = [{"shards": [{"shard": 0, "apply_busy_s": 1.0},
                            {"shard": 1, "apply_busy_s": 1.0}]}] * 2
        assert r.check(idle) is HOLD        # no new work: no evidence
        single = [{"shards": [{"shard": 0, "apply_busy_s": 0.0}]},
                  {"shards": [{"shard": 0, "apply_busy_s": 9.0}]}]
        assert r.check(single) is None      # one stream can't imbalance

    def test_shm_backpressure_slope(self):
        r = ShmBackpressureRule(stall_frac=0.25)
        h = [{"t": 0.0, "shm_rounds": 0, "shm_writer_stall_s": 0.0},
             {"t": 1.0, "shm_rounds": 50, "shm_writer_stall_s": 0.5}]
        assert isinstance(r.check(h), str)
        ok = [{"t": 0.0, "shm_rounds": 0, "shm_writer_stall_s": 0.0},
              {"t": 1.0, "shm_rounds": 50, "shm_writer_stall_s": 0.01}]
        assert r.check(ok) is None
        norounds = [{"t": 0.0, "shm_rounds": 5,
                     "shm_writer_stall_s": 0.0},
                    {"t": 1.0, "shm_rounds": 5,
                     "shm_writer_stall_s": 0.5}]
        assert r.check(norounds) is HOLD

    def test_apply_pool_saturation(self):
        r = ApplyPoolSaturationRule(busy_frac=0.5, min_dispatches=8)
        sat = [{"pool_inline_busy": 0, "pool_parallel": 0},
               {"pool_inline_busy": 30, "pool_parallel": 10}]
        assert isinstance(r.check(sat), str)
        healthy = [{"pool_inline_busy": 0, "pool_parallel": 0},
                   {"pool_inline_busy": 2, "pool_parallel": 50}]
        assert r.check(healthy) is None
        quiet = [{"pool_inline_busy": 0, "pool_parallel": 0},
                 {"pool_inline_busy": 2, "pool_parallel": 3}]
        assert r.check(quiet) is HOLD       # under the evidence floor

    def test_mailbox_backlog_needs_monotonic_rise(self):
        r = MailboxBacklogRule(window=3, min_depth=64)
        rising = [{"mailbox_depth": d} for d in (80, 120, 200)]
        assert isinstance(r.check(rising), str)
        oscillating = [{"mailbox_depth": d} for d in (80, 200, 150)]
        assert r.check(oscillating) is None
        shallow = [{"mailbox_depth": d} for d in (1, 2, 3)]
        assert r.check(shallow) is None     # under the floor
        assert r.check(rising[:2]) is HOLD  # window not filled

    def test_snapshot_stale_vs_observed_cadence(self):
        r = SnapshotStaleRule(ratio=3.0, min_age_s=1.0)
        # publishes observed every ~2s, newest now 9s old -> stale
        h = [{"t": 0.0, "publishes": 1, "snapshot_age_s": 0.1},
             {"t": 2.0, "publishes": 2, "snapshot_age_s": 0.1},
             {"t": 4.0, "publishes": 3, "snapshot_age_s": 0.1},
             {"t": 13.0, "publishes": 3, "snapshot_age_s": 9.0}]
        assert isinstance(r.check(h), str)
        fresh = h[:3] + [{"t": 5.0, "publishes": 4,
                          "snapshot_age_s": 0.5}]
        assert r.check(fresh) is None
        never = [{"t": 0.0, "publishes": 0}] * 4
        assert r.check(never) is HOLD       # no cadence to violate

    def test_memory_growth_slope(self):
        r = MemoryGrowthRule(window=4, grow_frac=0.10,
                             floor_bytes=1 << 20)
        base = 32 << 20
        grow = [{"mem_total": int(base * f)}
                for f in (1.0, 1.05, 1.10, 1.16)]
        assert isinstance(r.check(grow), str)
        stable = [{"mem_total": base}] * 4
        assert r.check(stable) is None
        oscillating = [{"mem_total": base + d}
                       for d in (0, 1 << 20, 0, 2 << 20)]
        assert r.check(oscillating) is None
        tiny = [{"mem_total": v} for v in (100, 200, 300, 400)]
        assert r.check(tiny) is HOLD        # under the floor

    def test_replica_lag_needs_live_subscribers(self):
        r = ReplicaLagRule(max_lag=3)
        behind = [{"replica_subscribers": 2, "replica_lag_versions": 4}]
        assert isinstance(r.check(behind), str)
        caught_up = [{"replica_subscribers": 2,
                      "replica_lag_versions": 1}]
        assert r.check(caught_up) is None
        # no subscribers (or the plane off): nothing can lag — HOLD,
        # never a spurious clear/fire flap
        nobody = [{"replica_subscribers": 0,
                   "replica_lag_versions": 0}]
        assert r.check(nobody) is HOLD
        assert r.check([{}]) is HOLD

    def test_straggler_proxy(self):
        r = StragglerRule(min_windows=3, min_apply_per_window_s=0.01,
                          xw_ratio=3.0)
        culprit = [{"exchanges": 0, "apply_s": 0.0,
                    "exchange_wait_s": 0.0},
                   {"exchanges": 10, "apply_s": 0.30,
                    "exchange_wait_s": 0.01,
                    "binding_phase": "apply"}]
        assert isinstance(r.check(culprit), str)
        # the HEALTHY peer: waits in the collective instead
        victim = [{"exchanges": 0, "apply_s": 0.0,
                   "exchange_wait_s": 0.0},
                  {"exchanges": 10, "apply_s": 0.05,
                   "exchange_wait_s": 0.30,
                   "binding_phase": "exchange_wait"}]
        assert r.check(victim) is None
        # single-process / idle worlds: no collective stream to gate
        idle = [{"exchanges": 0, "apply_s": 0.0,
                 "exchange_wait_s": 0.0},
                {"exchanges": 0, "apply_s": 5.0,
                 "exchange_wait_s": 0.0, "binding_phase": "apply"}]
        assert r.check(idle) is HOLD
        # fast applies under the floor never alert (clean 2-proc runs)
        fast = [{"exchanges": 0, "apply_s": 0.0,
                 "exchange_wait_s": 0.0},
                {"exchanges": 10, "apply_s": 0.03,
                 "exchange_wait_s": 0.001, "binding_phase": "apply"}]
        assert r.check(fast) is None
        # -mv_phase_stamps=0 / flight off: no stamped binding phase —
        # the plain-attr deltas must still carry the verdict (the rule
        # reads apply_busy_s/xw_busy_s, which accumulate regardless)
        unstamped = [{"exchanges": 0, "apply_s": 0.0,
                      "exchange_wait_s": 0.0},
                     {"exchanges": 10, "apply_s": 0.30,
                      "exchange_wait_s": 0.01}]
        verdict = r.check(unstamped)
        assert isinstance(verdict, str) and "unstamped" in verdict
        # ...but a live stamped verdict naming another phase VETOES
        decode_bound = [{"exchanges": 0, "apply_s": 0.0,
                         "exchange_wait_s": 0.0},
                        {"exchanges": 10, "apply_s": 0.30,
                         "exchange_wait_s": 0.01,
                         "binding_phase": "decode"}]
        assert r.check(decode_bound) is None


# -- eager registration + live surfaces ----------------------------------


class TestEagerRegistrationAndSurfaces:
    def test_alert_and_mem_families_scrape_at_zero(self):
        # "at zero" is a statement about a fresh process: a file that ran
        # earlier in this worker (tests/test_fleet.py rolls up) leaves its
        # counts in the process-wide registry
        metrics._reset_for_tests()
        mv.MV_Init(["-mv_ops_port=0", "-mv_watchdog_s=30"])
        try:
            status, text = _scrape("/metrics")
            assert status == 200
            # the PR 6 rule: every family visible at ZERO before any
            # tick/refresh moved it
            for rule in ("shard_imbalance", "shm_backpressure",
                         "apply_pool_sat", "mailbox_backlog",
                         "snapshot_stale", "memory_growth",
                         "straggler", "fleet_p99_breach",
                         "member_qps_outlier", "rollup_stale"):
                assert f"mv_alert_{rule} 0" in text, rule
            for fam in accounting.MEM_FAMILIES:
                assert ops.prom_name(fam) in text, fam
            assert "mv_watchdog_ticks" in text
            # round 22: the fleet families scrape at zero too, and the
            # digest families render as Prometheus summaries
            assert "mv_fleet_rollups 0" in text
            assert "mv_fleet_rollup_errors 0" in text
            assert "mv_fleet_members 0" in text
            assert 'mv_digest_worker_rtt_s{quantile="0.99"}' in text
            assert "mv_digest_engine_window_s_count" in text
            # the reporter's snapshot carries them too
            snap = metrics.snapshot()
            assert "alert.straggler" in snap
            assert "mem.total_bytes" in snap
        finally:
            mv.MV_ShutDown()

    def test_alerts_endpoint_off_and_armed(self):
        mv.MV_Init(["-mv_ops_port=0"])
        try:
            status, text = _scrape("/alerts")
            body = json.loads(text)
            assert status == 200 and body["enabled"] is False
            assert "mv_watchdog_s" in body["note"]
        finally:
            mv.MV_ShutDown()
        mv.MV_Init(["-mv_ops_port=0", "-mv_watchdog_s=0.05"])
        try:
            deadline = time.time() + 5
            while time.time() < deadline:
                body = json.loads(_scrape("/alerts")[1])
                if body["ticks"] >= 2:
                    break
                time.sleep(0.05)
            assert body["enabled"] is True and body["ticks"] >= 2
            assert sorted(body["rules"]) == [
                "apply_pool_sat", "coordinator_failover",
                "fleet_p99_breach", "mailbox_backlog",
                "member_qps_outlier", "memory_growth", "replica_lag",
                "rollup_stale", "shard_imbalance", "shm_backpressure",
                "snapshot_stale", "straggler"]
            hz = json.loads(_scrape("/healthz")[1])
            assert hz["status"] == "ok" and hz["alerts"] == []
        finally:
            mv.MV_ShutDown()
        # Zoo.Stop joined the tick thread (bounded): no watchdog left
        assert twd.peek() is None

    def test_healthz_warn_is_distinct_and_still_200(self):
        mv.MV_Init(["-mv_ops_port=0", "-mv_watchdog_s=30"])
        try:
            wd = twd.peek()
            assert wd is not None
            wd.rules = [_ScriptedRule(["bad"])]
            wd._state = {"scripted": {"active": False, "bad": 0,
                                      "good": 0, "since": None,
                                      "detail": None}}
            wd.evaluate({"t": 1.0})
            wd.evaluate({"t": 2.0})
            status, text = _scrape("/healthz")
            hz = json.loads(text)
            assert status == 200            # warn is NOT death
            assert hz["status"] == "warn"
            assert hz["alerts"] == ["scripted"]
            assert hz["healthy"] is True
            body = json.loads(_scrape("/alerts")[1])
            assert [a["rule"] for a in body["alerts"]] == ["scripted"]
        finally:
            mv.MV_ShutDown()

    def test_dashboard_mem_and_watchdog_lines(self):
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.utils.dashboard import Dashboard
        mv.MV_Init(["-mv_watchdog_s=30"])
        try:
            mv.MV_CreateTable(MatrixTableOption(num_rows=64, num_cols=4))
            lines = Dashboard._ops_lines()
            assert any(ln.startswith("[Mem]") for ln in lines), lines
            assert any(ln.startswith("[Watchdog]") for ln in lines), \
                lines
        finally:
            mv.MV_ShutDown()


# -- /memory grammar + ledger-vs-nbytes cross-check ----------------------


class TestMemoryLedger:
    def test_memory_reconciles_with_independent_nbytes(self):
        import jax

        from multiverso_tpu.serving import peek_plane
        from multiverso_tpu.tables import KVTableOption, MatrixTableOption
        from multiverso_tpu.zoo import Zoo
        mv.MV_Init(["-mv_ops_port=0"])
        try:
            mt = mv.MV_CreateTable(MatrixTableOption(num_rows=128,
                                                     num_cols=16))
            kv = mv.MV_CreateTable(KVTableOption())
            ids = np.arange(32, dtype=np.int32)
            mt.AddRows(ids, np.ones((32, 16), np.float32))
            mt.GetRows(ids)                 # host verb: mirror live
            kv.Add(np.array([1, 2, 3]), np.array([1.0, 2.0, 3.0]))
            kv.Get(np.array([1, 2, 3]))
            mv.MV_PublishSnapshot()
            mt.AddRows(ids, np.ones((32, 16), np.float32))
            mv.MV_PublishSnapshot()
            # a bare /metrics scrape must refresh the ledger gauges
            # itself — the watchdog is OFF in this world, and a
            # watchdog-gated refresh would leave mem.* frozen at the
            # eager-registration zeros forever
            status, text = _scrape("/metrics")
            assert status == 200
            line = next(ln for ln in text.splitlines()
                        if ln.startswith("mv_mem_tables_device_bytes"))
            assert float(line.split()[-1]) > 0, line
            status, text = _scrape("/memory")
            assert status == 200
            body = json.loads(text)
            # grammar
            assert body["total_bytes"] >= 0
            comps = body["components"]
            for key in ("tables", "snapshots", "flight", "dedup"):
                assert key in comps, sorted(comps)
            # per-table placement vs INDEPENDENT recomputation
            eng = Zoo.Get().server_engine
            per = {rec["table_id"]: rec
                   for rec in comps["tables"]["per_table"]}
            srv0 = eng.store_[0]
            dev0 = sum(int(leaf.nbytes)
                       for leaf in jax.tree.leaves(srv0.state))
            assert per[0]["device_bytes"] == dev0
            assert per[0]["host_mirror_bytes"] == 0
            srv1 = eng.store_[1]
            vals1 = srv1._values_arr
            assert per[1]["device_bytes"] == int(vals1.nbytes)
            if srv1._values_np is not None:
                assert (per[1]["host_mirror_bytes"]
                        == int(srv1._values_np.nbytes))
            # per-version snapshot bytes == the store's own nbytes()
            plane = peek_plane()
            live = plane.store.live_versions()
            assert len(live) == 2           # -mv_serving_keep default
            for v in live:
                assert (comps["snapshots"]["per_version"][str(v)]
                        == plane.store.get(v).nbytes())
            assert comps["snapshots"]["bytes"] == sum(
                comps["snapshots"]["per_version"].values())
            # totals reconcile: the families sum to the quoted total
            t = comps["tables"]["totals"]
            expect = (t["device_bytes"] + t["host_mirror_bytes"]
                      + t["host_bytes"] + comps["snapshots"]["bytes"]
                      + comps["flight"]["bytes_estimate"]
                      + comps["dedup"]["bytes_estimate"]
                      + comps["tables"]["write_combine_bytes"]
                      + comps["tables"]["get_cache_bytes"]
                      + (comps["shm"] or {}).get("segment_bytes", 0))
            assert body["total_bytes"] == expect
            # ...and the mem.* gauges carry the same numbers
            snap = metrics.snapshot()
            assert (snap["mem.tables.device_bytes"]["value"]
                    == t["device_bytes"])
            assert (snap["mem.snapshots.bytes"]["value"]
                    == comps["snapshots"]["bytes"])
        finally:
            mv.MV_ShutDown()


# -- dir-glob CLI satellite ----------------------------------------------


class TestDirGlobCli:
    def test_forensics_accepts_a_directory(self, tmp_path):
        flight._reset_for_tests()
        flight.record("window.exchanged", seq=0, epoch=1, detail="A0")
        flight.dump(str(tmp_path / "flight_rank0.jsonl"))
        flight.dump(str(tmp_path / "flight_rank1.jsonl"))
        flight._reset_for_tests()
        from multiverso_tpu.telemetry import align, forensics
        expanded = align.expand_paths([str(tmp_path)])
        assert [os.path.basename(p) for p in expanded] == [
            "flight_rank0.jsonl", "flight_rank1.jsonl"]
        # files still pass through untouched alongside a directory
        mixed = align.expand_paths(
            [str(tmp_path / "flight_rank0.jsonl")])
        assert len(mixed) == 1
        assert forensics.main([str(tmp_path)]) == 0

    def test_empty_directory_raises_loudly(self, tmp_path):
        from multiverso_tpu.telemetry import align
        d = tmp_path / "empty"
        d.mkdir()
        with pytest.raises(FileNotFoundError):
            align.expand_paths([str(d)])


# -- KV key-skew sketch satellite ----------------------------------------


class TestKvRowSketch:
    def test_kv_gets_feed_the_sketch_when_armed(self):
        from multiverso_tpu.tables import KVTableOption
        from multiverso_tpu.zoo import Zoo
        mv.MV_Init([])
        try:
            kv = mv.MV_CreateTable(KVTableOption())
            kv.Add(np.array([7, 8]), np.array([1.0, 1.0]))
            kv.Get(np.array([7, 8]))
            srv = Zoo.Get().server_engine.store_[0]
            assert srv._row_sketch is None      # off by default
        finally:
            mv.MV_ShutDown()
        mv.MV_Init(["-mv_row_sketch=16"])
        try:
            kv = mv.MV_CreateTable(KVTableOption())
            kv.Add(np.arange(8), np.ones(8))
            for _ in range(3):
                kv.Get(np.array([5, 5, 5, 6]))
            srv = Zoo.Get().server_engine.store_[0]
            assert srv._row_sketch is not None
            assert srv._row_sketch.top()[0][0] == 5
            snap = metrics.snapshot()
            assert snap["table.kv0.row_skew_top_share"]["value"] > 0
            # the /perf row-skew list picks the kv family up through
            # the same _row_sketch attribute the matrix family uses
            rep = ops.perf_report()
            assert any(r.get("table_id") == 0 for r in rep["row_skew"])
            from multiverso_tpu.utils.dashboard import Dashboard
            lines = Dashboard._ops_lines()
            assert any(ln.startswith("[RowSkew]") for ln in lines), \
                lines
        finally:
            mv.MV_ShutDown()


# -- watchdog-tick overhead guard (tier-1) -------------------------------


class TestWatchdogOverheadGuard:
    def test_blocking_round_overhead_within_budget(self):
        """An armed fast watchdog tick (ledger probes + rule sweep on
        its own daemon thread every 50ms) must cost <= max(2%, 2x
        measured baseline noise) on the blocking host round vs
        ``-mv_watchdog_s=0`` — the flight/phase-stamp budget extended
        to the round-13 plane. Off/on worlds interleave with
        best-per-side, and a failure must REPRODUCE on a second
        independent measurement (this box shows whole-world slow
        patches that interleaving cannot launder out)."""
        from multiverso_tpu.tables import MatrixTableOption

        k, rounds = 512, 15
        rng = np.random.default_rng(13)

        def measure(argv):
            mv.MV_Init(list(argv))
            try:
                table = mv.MV_CreateTable(MatrixTableOption(
                    num_rows=8192, num_cols=8))
                ids = rng.choice(8192, size=k,
                                 replace=False).astype(np.int32)
                deltas = rng.standard_normal((k, 8)).astype(np.float32)
                table.AddRows(ids, deltas)      # warm the jit caches
                table.GetRows(ids)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(rounds):
                        table.AddRows(ids, deltas)
                        table.GetRows(ids)
                    best = min(best, time.perf_counter() - t0)
            finally:
                mv.MV_ShutDown()
            return best / rounds

        last = None
        for _attempt in range(2):
            offs, ons = [], []
            for _ in range(3):
                offs.append(measure([]))
                ons.append(measure(["-mv_watchdog_s=0.05"]))
            base, on = min(offs), min(ons)
            noise_pct = 100.0 * (max(offs) - base) / base
            overhead_pct = 100.0 * (on - base) / base
            allowed = max(2.0, 2.0 * noise_pct)
            if overhead_pct <= allowed:
                return
            last = (f"watchdog tick overhead {overhead_pct:.2f}% "
                    f"exceeds {allowed:.2f}% (baseline noise "
                    f"{noise_pct:.2f}%; "
                    f"off={[round(o * 1e6) for o in offs]}us, "
                    f"on={[round(o * 1e6) for o in ons]}us per round)")
        raise AssertionError(last)


# -- 2-proc drill --------------------------------------------------------

_DRILL_CHILD = r'''
import os, sys, json, time, urllib.request
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import KVTableOption
from multiverso_tpu.telemetry import flight, ops

mode = sys.argv[3]
args = [f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
        "-dist_size=2", "-mv_deadline_s=60", "-mv_ops_port=0",
        "-mv_watchdog_s=0.15"]
if mode == "straggle" and rank == 0:
    # THE deliberate straggler: rank 0's every window apply stalls
    # 40ms (a perf fault — the verb stream stays lockstep). The
    # watchdog's straggler proxy must trip HERE and only here.
    args.append("-chaos_spec=apply.delay:1.0@0.04")
mv.MV_Init(args)
# KV tables: the drill is about the watchdog's reading of the engine, and
# a KV table's apply is numpy on the host, cheap and steady (a
# MatrixTable's is a device program, a cross-process collective here)
tab0 = mv.MV_CreateTable(KVTableOption())
tab1 = mv.MV_CreateTable(KVTableOption())
ids = np.arange(2048, dtype=np.int64)
d = np.ones(2048, np.float32)               # ~24KB per add
tab0.Add(ids, d)                                        # warm
tab1.Add(ids, d)
mv.MV_Barrier()

def alerts_body():
    url = f"http://127.0.0.1:{ops.port()}/alerts"
    return json.loads(urllib.request.urlopen(url, timeout=10).read())

# The drill's subject is the burst below. A fresh world's tables being
# made and first filled IS ledger growth (4.2 -> 5.8 MB here), and under
# six test workers' load it spans four watchdog ticks, which is the
# growth rule's whole window: wait, in TICKS and not in seconds, until
# that window holds the finished tables alone, and judge the flight
# ring from there on.
quiet_from = alerts_body()["ticks"]
deadline = time.time() + 30
while alerts_body()["ticks"] < quiet_from + 5 and time.time() < deadline:
    time.sleep(0.05)
assert alerts_body()["ticks"] >= quiet_from + 5
burst_from = time.time()
mv.MV_Barrier()
# sustained lockstep windows: a FIXED iteration count, never a wall-
# time bound — with the chaos delay rank 0 runs ~10x slower per
# window, so a timed loop would let rank 1 admit verbs rank 0 never
# issues (diverged SPMD verb streams deadlock the next exchange);
# burst duration emerges from the slowest rank instead (straggle:
# ~35 windows x ~45ms on rank 0 ~= 1.5s ~= 10 watchdog ticks).
# SMALL payloads keep clean-mode applies far under the straggler
# rule's 20ms/window floor (64KB adds crept to ~22ms/window on a
# loaded 24-core container and fired the rule HONESTLY — a uniformly
# apply-bound world is a straggler everywhere by its contract, so
# the clean drill must stay clearly apply-CHEAP), while the chaos
# delay pushes rank 0 past 40ms/window — margin on BOTH sides
for _ in range(24):
    for _ in range(8):
        tab0.AddFireForget(ids, d)
        tab1.AddFireForget(ids, d)
    tab0.Get(ids[:16])
mv.MV_Barrier()

# on a fast, idle host the burst ends inside two ticks: the verdict is
# about at least three, so wait for the third (idle ticks hold the state)
deadline = time.time() + 5
while alerts_body()["ticks"] < 3 and time.time() < deadline:
    time.sleep(0.05)
body = alerts_body()
assert body["enabled"] and body["ticks"] >= 3, body
active = sorted(a["rule"] for a in body["alerts"])
hz = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{ops.port()}/healthz", timeout=10).read())
ring_kinds = {e["kind"] for e in flight.events() if e["t"] >= burst_from}
if mode == "straggle" and rank == 0:
    assert "straggler" in active, body
    assert hz["status"] == "warn" and "straggler" in hz["alerts"], hz
    assert "alert.straggler" in ring_kinds, sorted(ring_kinds)
    # NO FLAPPING: the verdict holds across further ticks (idle
    # ticks HOLD the state rather than clearing it)
    t0 = body["ticks"]
    deadline = time.time() + 5
    while alerts_body()["ticks"] < t0 + 3 and time.time() < deadline:
        time.sleep(0.1)
    later = alerts_body()
    assert later["ticks"] >= t0 + 3, later
    assert "straggler" in [a["rule"] for a in later["alerts"]], later
else:
    # the healthy rank (and BOTH ranks of a clean run) fire NOTHING
    assert active == [], (rank, mode, body)
    assert hz["status"] == "ok", hz
    assert not any(k.startswith("alert.") for k in ring_kinds), \
        sorted(ring_kinds)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} WATCHDOG DRILL OK", flush=True)
'''


class TestWatchdogDrill:
    def test_chaos_straggler_alerts_on_injected_rank_only(self,
                                                          tmp_path):
        """Acceptance (round 13): chaos ``apply.delay`` on rank 0's
        apply path trips the straggler alert on rank 0 ONLY — live at
        /alerts, in the flight ring, and as the /healthz ``warn``
        status — and holds without flapping across >= 3 further
        ticks; rank 1 (which merely WAITS for rank 0 in the
        collective) stays silent."""
        run_two_process(_DRILL_CHILD, tmp_path, "straggle",
                        expect="WATCHDOG DRILL OK")

    def test_clean_run_fires_nothing(self, tmp_path):
        """Acceptance (round 13): the same burst without chaos fires
        no alert on either rank across >= 3 watchdog ticks."""
        run_two_process(_DRILL_CHILD, tmp_path, "clean",
                        expect="WATCHDOG DRILL OK")
