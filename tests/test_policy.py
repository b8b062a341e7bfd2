"""Policy plane (round 20): the self-driving runtime.

* guard units — sustain hysteresis, install cooldown, rolling window
  budget, min/max rails, per-rule enables, the runtime kill switch —
  driven over synthetic watchdog tick records with a fake applier;
* chaos ``policy.flap`` — an alert verdict oscillating around its
  threshold at the policy's observation point yields AT MOST one
  action per cooldown window (no alert-storm -> action-storm
  amplification), and strict alternation under the sustain hysteresis
  yields none;
* revert contract — an installed action whose triggering alert fails
  to improve within ``-mv_policy_revert_after`` evaluations stages its
  inverse and BURNS the rule until the alert clears;
* ``rebalance.plan_routing`` — the pure hot-table/cool-slot decision
  math (deterministic tie-breaks, the one-table-cannot-split guard);
* live single-process loop — a synthetic shard_imbalance drives a REAL
  routing-map install at a fenced cross-stream cut; verbs re-route,
  the ``policy.*`` flight events round-trip with (mepoch, seq) stamps
  aligned to the triggering alert, and forensics.correlate reads the
  ring as stream-clean;
* adaptive flags (satellite) — ``-mv_apply_workers`` /
  ``-mv_pipeline_depth`` reach the hot paths through listener caches
  and the apply pool rebuilds at the next window;
* 2-proc drills (acceptance) — an injected hot-table skew (two hot
  tables hashed onto one engine shard) is detected AND corrected live
  (routing override installed at the lockstep MV_PolicySync, the
  post-action load balanced, the alert gone), bit-exact vs the
  ``-mv_policy=false`` oracle world; a clean soak fires zero actions.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu import policy
from multiverso_tpu.elastic import rebalance
from multiverso_tpu.policy import engine as pengine
from multiverso_tpu.telemetry import flight, metrics, ops
from multiverso_tpu.utils.configure import (ResetFlagsToDefaults,
                                            SetCMDFlag)

from tests.test_multihost import run_two_process


@pytest.fixture()
def flags():
    """Set policy/chaos flags for one offline test; restore defaults
    after (the registries persist across tests in one process)."""
    yield SetCMDFlag
    ResetFlagsToDefaults()


class FakeApplier:
    """Offline stand-in for EngineApplier: records installs, applies
    route overrides to its own routing report, echoes tune results."""

    def __init__(self, live_slots=(0, 1), routing=None):
        self.calls = []
        self.routing = {"shard_cap": len(live_slots),
                        "live_slots": list(live_slots),
                        "installs": 0, "overrides": {},
                        "routing": dict(routing or {})}

    def routing_report(self):
        return self.routing

    def install_actions(self, actions):
        out = []
        for a in actions:
            self.calls.append(dict(a))
            if a["kind"] == "route":
                prev = self.routing["routing"].get(a["table"], a["src"])
                self.routing["routing"][a["table"]] = a["dst"]
                res = {"applied": [(a["table"], prev, a["dst"])]}
            else:
                res = {"frm": a.get("frm"), "to": a["to"]}
            out.append((dict(a), res))
        return out


def _rec(n, active=(), shards=None):
    sample = {"t": float(n)}
    if shards is not None:
        sample["shards"] = shards
    return {"ticks": n, "sample": sample, "fired": [],
            "active": list(active)}


def _mk(flags, applier=None, **kw):
    flags("mv_policy", "true")
    for k, v in kw.items():
        flags(k, v)
    return pengine.PolicyEngine(pengine.LocalStager(), me=0, world=1,
                                applier=applier or FakeApplier())


# -- guard units ---------------------------------------------------------


class TestGuards:
    def test_sustain_then_cooldown_bound_one_action(self, flags):
        eng = _mk(flags, mv_policy_sustain="2",
                  mv_policy_cooldown_s="3600",
                  mv_policy_revert_after="100")
        assert eng.step(_rec(1, ["apply_pool_sat"])) == []   # sustain 1
        staged = eng.step(_rec(2, ["apply_pool_sat"]))       # sustain 2
        assert [a["kind"] for a in staged] == ["tune"]
        assert staged[0]["flag"] == "mv_apply_workers"
        assert len(eng.applier.calls) == 1                   # installed
        # the alert persists: cooldown holds every further proposal
        for n in range(3, 10):
            assert eng.step(_rec(n, ["apply_pool_sat"])) == []
        assert len(eng.applier.calls) == 1

    def test_kill_switch_watches_but_never_acts(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0")
        flags("mv_policy", "false")                          # kill
        for n in range(1, 5):
            assert eng.step(_rec(n, ["apply_pool_sat"])) == []
        assert eng.applier.calls == []
        flags("mv_policy", "true")                           # re-arm
        assert eng.step(_rec(5, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == 1

    def test_per_rule_enable_flags(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0",
                  mv_policy_rules="shard_imbalance")
        for n in range(1, 4):
            assert eng.step(_rec(n, ["apply_pool_sat"])) == []
        assert eng.applier.calls == []

    def test_rails_stop_tuning_at_the_edge(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0")
        SetCMDFlag("mv_apply_workers", 16)                  # at max rail
        assert eng.step(_rec(1, ["apply_pool_sat"])) == []
        SetCMDFlag("mv_pipeline_depth", 8)
        assert eng.step(_rec(2, ["mailbox_backlog"])) == []
        assert eng.applier.calls == []

    def test_window_budget_caps_one_evaluation_too(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0",
                  mv_policy_max_actions="1",
                  mv_policy_window_s="3600")
        staged = eng.step(_rec(1, ["apply_pool_sat",
                                   "mailbox_backlog"]))
        assert len(staged) == 1                 # budget holds in-step
        assert len(eng.applier.calls) == 1
        assert eng.step(_rec(2, ["apply_pool_sat",
                                 "mailbox_backlog"])) == []

    def test_kill_switch_vetoes_already_staged_actions(self, flags):
        """Review fix: the kill switch must stop ALREADY-STAGED actions
        at the actuation point too (the pull carries the armed state;
        a disarmed rank discards the agreed batch), not just future
        staging."""
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0")
        eng.world = 2               # stage only — no self-actuation
        eng.step(_rec(1, ["apply_pool_sat"]))
        assert eng.applier.calls == []              # staged, not applied
        flags("mv_policy", "false")                 # kill before sync
        eng.world = 1
        assert eng.actuate() == []
        assert eng.applier.calls == []              # veto: discarded
        assert "discarded-killed" in [h["status"] for h in eng.history]
        # the discard must NOT wedge the correction: re-arming lets
        # the same content stage and install again (dedup keys and the
        # proposal window both forgot the vetoed batch)
        flags("mv_policy", "true")
        eng.step(_rec(2, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == 1, eng.applier.calls

    def test_drain_requires_elastic_and_double_sustain(self, flags):
        # single-process engine: drains are structurally impossible
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0")
        for n in range(1, 6):
            assert eng.step(_rec(n, ["straggler"])) == []
        assert eng.applier.calls == []


# -- chaos policy.flap (satellite): no alert-storm amplification ---------


class TestFlapChaos:
    def _armed(self, flags, period):
        flags("chaos_spec", f"policy.flap:1.0@{period}")
        flags("chaos_seed", "7")

    def test_strict_alternation_is_absorbed_by_sustain(self, flags):
        eng = _mk(flags, mv_policy_sustain="2",
                  mv_policy_cooldown_s="0")
        self._armed(flags, 1)           # breach, heal, breach, heal...
        for n in range(1, 13):
            assert eng.step(_rec(n)) == []
        assert eng.applier.calls == []  # hysteresis absorbs the flap
        assert metrics.snapshot().get("chaos.policy.flap",
                                      {}).get("value", 0) > 0

    def test_at_most_one_action_per_cooldown_window(self, flags):
        eng = _mk(flags, mv_policy_sustain="2",
                  mv_policy_cooldown_s="3600")
        self._armed(flags, 2)           # 2 breaching, 2 healthy, ...
        for n in range(1, 17):
            eng.step(_rec(n))
        # 16 oscillating evaluations, 4 full breach phases — exactly
        # ONE install lands in the cooldown window
        assert len(eng.applier.calls) == 1, eng.applier.calls


# -- revert contract -----------------------------------------------------


class TestRevert:
    def test_unimproved_tune_reverts_and_burns(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0",
                  mv_policy_revert_after="3")
        SetCMDFlag("mv_apply_workers", 4)
        eng.step(_rec(1, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == 1
        # the alert never improves: 3 evaluations later the inverse
        # action installs and the rule burns
        for n in range(2, 6):
            eng.step(_rec(n, ["apply_pool_sat"]))
        reverts = [a for a in eng.applier.calls if a.get("revert_of")]
        assert len(reverts) == 1
        assert reverts[0]["flag"] == "mv_apply_workers"
        assert reverts[0]["to"] == 4            # back to the original
        # burned: still-active alert proposes nothing more
        n_calls = len(eng.applier.calls)
        for n in range(6, 10):
            eng.step(_rec(n, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == n_calls
        # the alert clears -> the burn lifts -> acting resumes
        eng.step(_rec(10))
        eng.step(_rec(11, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == n_calls + 1

    def test_improved_action_is_not_reverted(self, flags):
        eng = _mk(flags, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0",
                  mv_policy_revert_after="3")
        eng.step(_rec(1, ["apply_pool_sat"]))
        assert len(eng.applier.calls) == 1
        for n in range(2, 10):          # alert gone: action stands
            eng.step(_rec(n))
        assert not [a for a in eng.applier.calls
                    if a.get("revert_of")]
        assert "improved" in [h["status"] for h in eng.history]

    def test_route_revert_restores_previous_slot(self, flags):
        applier = FakeApplier(routing={0: 0, 1: 1, 2: 0, 3: 1})
        eng = _mk(flags, applier=applier, mv_policy_sustain="1",
                  mv_policy_cooldown_s="0",
                  mv_policy_revert_after="2")
        shards0 = [{"shard": 0, "apply_busy_s": 0.0,
                    "table_verbs": {0: 0, 2: 0}},
                   {"shard": 1, "apply_busy_s": 0.0,
                    "table_verbs": {1: 0, 3: 0}}]
        shards1 = [{"shard": 0, "apply_busy_s": 1.0,
                    "table_verbs": {0: 500, 2: 40}},
                   {"shard": 1, "apply_busy_s": 0.02,
                    "table_verbs": {1: 3, 3: 2}}]
        eng.step(_rec(1, ["shard_imbalance"], shards0))
        eng.step(_rec(2, ["shard_imbalance"], shards1))
        routes = [a for a in eng.applier.calls if a["kind"] == "route"]
        assert routes and routes[0]["table"] == 0
        assert routes[0]["src"] == 0 and routes[0]["dst"] == 1
        # never improves -> revert puts table 0 back on slot 0
        for n in range(3, 6):
            eng.step(_rec(n, ["shard_imbalance"], shards1))
        reverts = [a for a in eng.applier.calls if a.get("revert_of")]
        assert reverts and reverts[0]["table"] == 0
        assert reverts[0]["dst"] == 0
        assert applier.routing["routing"][0] == 0


# -- pure routing math ---------------------------------------------------


class TestPlanRouting:
    def test_moves_hottest_table_to_coolest_slot(self):
        plan = rebalance.plan_routing(
            {0: 1.0, 1: 0.1, 2: 0.4},
            {0: {0: 100, 3: 900}, 1: {1: 5}, 2: {2: 40}},
            {0: 0, 1: 1, 2: 2, 3: 0}, [0, 1, 2])
        assert plan == (3, 0, 1)

    def test_tie_breaks_are_deterministic(self):
        plan = rebalance.plan_routing(
            {0: 1.0, 1: 0.0, 2: 0.0},
            {0: {0: 10, 2: 10}}, {0: 0, 2: 0}, [0, 1, 2])
        assert plan == (0, 0, 1)        # smallest tid, smallest slot

    def test_single_table_hot_slot_cannot_split(self):
        assert rebalance.plan_routing(
            {0: 1.0, 1: 0.0}, {0: {0: 99}}, {0: 0, 1: 1},
            [0, 1]) is None

    def test_under_ratio_or_one_slot_is_no_move(self):
        assert rebalance.plan_routing(
            {0: 0.5, 1: 0.45}, {0: {0: 9, 2: 9}},
            {0: 0, 1: 1, 2: 0}, [0, 1]) is None
        assert rebalance.plan_routing(
            {0: 9.0}, {0: {0: 9, 2: 9}}, {0: 0, 2: 0}, [0]) is None


# -- live single-process loop + flight round-trip ------------------------


class TestLiveRouteInstall:
    def test_route_installs_at_cut_verbs_follow_flight_aligns(
            self, tmp_path):
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.telemetry import watchdog as twd
        from multiverso_tpu.zoo import Zoo
        flight._reset_for_tests()
        mv.MV_Init(["-mv_engine_shards=2", "-mv_watchdog_s=30",
                    "-mv_policy=true", "-mv_policy_sustain=1",
                    "-mv_policy_cooldown_s=0"])
        try:
            tabs = [mv.MV_CreateTable(MatrixTableOption(
                num_rows=64, num_cols=4)) for _ in range(4)]
            ids = np.arange(64, dtype=np.int32)
            d = np.ones((64, 4), np.float32)
            for t in tabs:
                t.AddRows(ids, d)       # warm every shard stream
            se = Zoo.Get().server_engine
            assert se.routing_report()["routing"] == {0: 0, 1: 1,
                                                      2: 0, 3: 1}
            # a FIRING alert through the real watchdog machinery (so
            # the alert flight event carries the (mepoch, seq) stamp
            # the action event must align with)
            wd = twd.peek()
            assert wd is not None
            wd.evaluate({"t": 1.0})     # history only — no rule fires
            flight.record("alert.shard_imbalance",
                          seq=twd.stream_pos()[1],
                          mepoch=twd.stream_pos()[0],
                          detail="synthetic drill alert")
            eng = policy.peek()
            shards0 = [{"shard": 0, "apply_busy_s": 0.0,
                        "table_verbs": {0: 0, 2: 0}},
                       {"shard": 1, "apply_busy_s": 0.0,
                        "table_verbs": {1: 0, 3: 0}}]
            shards1 = [{"shard": 0, "apply_busy_s": 0.8,
                        "table_verbs": {0: 120, 2: 20}},
                       {"shard": 1, "apply_busy_s": 0.01,
                        "table_verbs": {1: 2, 3: 2}}]
            eng.step(_rec(1, ["shard_imbalance"], shards0))
            eng.step(_rec(2, ["shard_imbalance"], shards1))
            rr = se.routing_report()
            assert rr["overrides"] == {0: 1}, rr
            assert rr["routing"][0] == 1
            assert rr["installs"] == 1
            # verbs follow the new map: table 0 now rides stream 1
            before = se._subs[1].table_verbs.get(0, 0)
            tabs[0].AddRows(ids, d)
            tabs[0].GetRows(ids)
            assert se._subs[1].table_verbs.get(0, 0) > before
            # flight round-trip: staged + route events, stamped
            evs = flight.events()
            kinds = [e["kind"] for e in evs]
            assert "policy.staged" in kinds and "policy.route" in kinds
            act = next(e for e in evs if e["kind"] == "policy.route")
            assert "rule=shard_imbalance" in act["detail"]
            assert "id=route:t0:s0>s1:g0" in act["detail"]
            alert = next(e for e in evs
                         if e["kind"] == "alert.shard_imbalance")
            # the alignment satellite: action and alert share the
            # membership epoch and the alert's stream position bounds
            # the action's (the action installs at/after the alert)
            assert act["mepoch"] == alert["mepoch"] == 0
            assert alert["seq"] <= act["seq"]
            # forensics: rings carrying policy/alert events still
            # align stream-clean (the PR 12 rule for control events)
            from multiverso_tpu.telemetry import forensics
            p0 = str(tmp_path / "flight_rank0.jsonl")
            p1 = str(tmp_path / "flight_rank1.jsonl")
            flight.dump(p0)
            flight.dump(p1)
            assert forensics.correlate([p0, p1])["diverged"] is False
            # /actions surfaces the install
            rep = mv.MV_PolicyReport()
            assert rep["installed"] == 1
            assert any(r["status"] == "installed"
                       for r in rep["actions"])
        finally:
            mv.MV_ShutDown()

    def test_tune_round_trips_and_healthz_names_policy(self):
        mv.MV_Init(["-mv_ops_port=0", "-mv_watchdog_s=30",
                    "-mv_policy=true", "-mv_policy_sustain=1",
                    "-mv_policy_cooldown_s=0"])
        try:
            from multiverso_tpu.utils.configure import GetFlag
            eng = policy.peek()
            depth0 = int(GetFlag("mv_pipeline_depth"))
            eng.step(_rec(1, ["mailbox_backlog"]))
            assert int(GetFlag("mv_pipeline_depth")) == depth0 + 1
            kinds = [e["kind"] for e in flight.events()]
            assert "policy.tune" in kinds
            port = ops.port()
            hz = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert hz["policy"]["installed"] >= 1, hz["policy"]
            assert hz["policy"]["armed"] is True
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/actions", timeout=10).read())
            assert body["enabled"] and body["installed"] >= 1
        finally:
            mv.MV_ShutDown()

    def test_actions_endpoint_off_world_says_so(self):
        mv.MV_Init(["-mv_ops_port=0"])
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{ops.port()}/actions",
                timeout=10).read())
            assert body["enabled"] is False
            assert "mv_policy" in body["note"]
        finally:
            mv.MV_ShutDown()


# -- adaptive flags reach the hot paths (satellite) ----------------------


class TestAdaptiveFlags:
    def test_cached_helpers_track_flag_updates(self, flags):
        from multiverso_tpu.sync.server import (_apply_workers_flag,
                                                _pipeline_depth_flag)
        flags("mv_apply_workers", 6)
        flags("mv_pipeline_depth", 5)
        assert _apply_workers_flag() == 6
        assert _pipeline_depth_flag() == 5

    def test_apply_pool_rebuilds_at_next_window(self, flags):
        from multiverso_tpu.sync.server import Server
        srv = Server(name="pooltest")
        try:
            flags("mv_apply_workers", 4)
            p1 = srv._ensure_apply_pool()
            assert p1.workers == 4
            assert srv._ensure_apply_pool() is p1    # unchanged: kept
            flags("mv_apply_workers", 8)
            p2 = srv._ensure_apply_pool()
            assert p2 is not p1 and p2.workers == 8
            flags("mv_apply_workers", 1)             # clamped floor 2
            assert srv._ensure_apply_pool().workers == 2
        finally:
            pool = srv._apply_pool
            if pool is not None:
                pool.shutdown()


# -- 2-proc acceptance drills --------------------------------------------

_SKEW_CHILD = r'''
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
from multiverso_tpu.zoo import Zoo

mode = sys.argv[3]
# KV tables: the drill is about the engine's streams, and two streams in
# a multi-process world need tables whose apply is host-local
# (tests/test_sharded.py test_matrix_table_over_two_streams_is_refused).
# The watchdog's rule reads apply seconds a tick and holds under 0.05 s:
# Adds of N keys keep a balanced stream's tick over that after the
# correction has halved it, and ITERS leaves the loop the three healthy
# ticks that clear the alert
N, ITERS = 16384, 96
base = int(port)

def alerts():
    """(names of the active alerts, watchdog ticks so far)."""
    url = f"http://127.0.0.1:{ops.port()}/alerts"
    body = json.loads(urllib.request.urlopen(url, timeout=10).read())
    return sorted(a["rule"] for a in body["alerts"]), body["ticks"]

def stream_verbs(eng):
    """Verbs each engine stream has applied, over all its tables: a
    COUNT (apply seconds are a clock: one preempted stream thread read
    0.013 s against 0.045 s for equal work under six test workers)."""
    return {s["shard"]: sum(s["table_verbs"].values())
            for s in eng.shard_states()}

def world(policy_on, coord_port, policy_port):
    args = [f"-dist_coordinator=127.0.0.1:{coord_port}",
            f"-dist_rank={rank}", "-dist_size=2",
            "-mv_engine_shards=2", "-mv_deadline_s=90",
            "-mv_watchdog_s=0.3", "-mv_ops_port=0"]
    if policy_on:
        # skew: only the routing loop may act (parity stays about the
        # one correction under test); clean: EVERY loop armed — the
        # zero-action claim must hold over the full rule set
        rules = "shard_imbalance" if mode == "skew" else "all"
        args += ["-mv_policy=true",
                 f"-mv_policy_addr=127.0.0.1:{policy_port}",
                 f"-mv_policy_rules={rules}",
                 "-mv_policy_sustain=2", "-mv_policy_cooldown_s=2.0",
                 "-mv_policy_window_s=30", "-mv_policy_max_actions=2"]
        if mode == "skew":
            # the alert clears after three healthy ticks, and a tick
            # under 0.05 s of applies is no evidence either way: on a
            # loaded box that can outlast the default six evaluations,
            # and the revert would undo the correction under test
            args += ["-mv_policy_revert_after=40"]
    flight._reset_for_tests()   # the ring is process-global: scope it
    mv.MV_Init(args)            # to THIS world's events
    eng = Zoo.Get().server_engine
    assert type(eng).__name__ == "ShardedServer", type(eng)
    tabs = [mv.MV_CreateTable(KVTableOption()) for _ in range(4)]
    ids = np.arange(N, dtype=np.int64)
    # THE SKEW (mode=skew): tables 0 and 2 are both HOT and both hash
    # to engine shard 0 (table_id % 2) — the modulo-routing pathology
    # the routing map exists to fix. mode=clean spreads the same load
    # over all four tables (balanced streams, nothing to correct).
    rng = np.random.default_rng(11 + rank)
    hot = [tabs[0], tabs[2]] if mode == "skew" else tabs
    burst = 16 if mode == "skew" else 8
    for i in range(ITERS):
        d = rng.integers(-3, 4, N).astype(np.float32)
        for _ in range(burst):
            for t in hot:
                t.AddFireForget(ids, d)
        if i % 7 == 3:
            tabs[1].AddFireForget(ids[:128], np.ones(128, np.float32))
            tabs[3].AddFireForget(ids[:128], np.ones(128, np.float32))
        for t in hot:           # pace: every hot stream drained
            t.Get(ids[:8])
        # and table 0 strictly the hottest, by verbs, on every rank:
        # a tie would let two ranks each propose a move of their own
        tabs[0].Get(ids[:8])
        if policy_on and i % 4 == 3:
            # the app-paced LOCKSTEP actuation point (both ranks, same
            # loop position — the MV_SaveCheckpoint discipline)
            mv.MV_PolicySync()
    mv.MV_Barrier()
    report = mv.MV_PolicyReport() if policy_on else None
    rr = eng.routing_report()
    # the PARITY capture happens BEFORE the post-action probe: the
    # probe's extra verbs are policy-world-only traffic the oracle
    # world never issues
    final = [t.Get(ids) for t in tabs]
    post, cleared = None, None
    if policy_on and mode == "skew":
        # post-action probe: a fixed hot burst must now land BALANCED
        # across the two streams (each hosts one hot table)
        d = np.ones(N, np.float32)
        s0 = stream_verbs(eng)
        for _ in range(30):
            tabs[0].AddFireForget(ids, d)
            tabs[2].AddFireForget(ids, d)
        tabs[0].Get(ids)                # tracked: t0 stream drained
        tabs[2].Get(ids)                # tracked: t2 stream drained
        s1 = stream_verbs(eng)
        post = {k: s1[k] - s0.get(k, 0) for k in s1}
        # ...and the watchdog agrees the imbalance is GONE: the alert
        # clears (clear_after healthy ticks over the balanced stream).
        # Waited for in watchdog TICKS, not seconds: a starved sampler
        # thread ticks late, and it is the ticks that clear an alert
        active, tick0 = alerts()
        tick = tick0
        while "shard_imbalance" in active and tick - tick0 < 60:
            time.sleep(0.05)
            active, tick = alerts()
        cleared = "shard_imbalance" not in active
    ring = {e["kind"] for e in flight.events()}
    mv.MV_Barrier()
    mv.MV_ShutDown()
    return final, report, rr, post, cleared, ring

def main():
  if mode == "skew":
    f1, rep, rr, post, cleared, ring = world(True, base, base + 10)
    # DETECTED and CORRECTED live: >= 1 routing install, one hot table
    # moved off shard 0, the policy events in the ring
    assert rep["installed"] >= 1, rep
    assert rr["overrides"], rr
    moved = sorted(rr["overrides"])
    assert set(moved) <= {0, 2} and rr["overrides"][moved[0]] == 1, rr
    # the INSTALL is agreed on every rank; STAGING is per-rank
    # opportunistic (under scheduler load one rank's sustain can lag
    # and the other's content-identical proposal wins the dedup) — so
    # the staged event is asserted only where this rank staged
    assert "policy.route" in ring, ring
    assert rep["staged"] == 0 or "policy.staged" in ring, (rep, ring)
    # the post-action evidence: the binding imbalance is gone — the
    # fixed hot burst lands balanced across the two streams, in verbs
    # applied (each now hosts exactly one hot table; both on one stream
    # would read 2.0)
    d0, d1 = post.get(0, 0), post.get(1, 0)
    assert d0 > 0 and d1 > 0, post
    ratio = max(d0, d1) / (0.5 * (d0 + d1))
    assert ratio < 1.5, (post, rr)
    assert cleared, "shard_imbalance never cleared post-action"
    # the no-policy ORACLE world in the same processes: identical verb
    # schedule, fixed modulo routing — final state must be BIT-EXACT
    f2, rep2, rr2, _, _, ring2 = world(False, base + 1, base + 11)
    assert rr2["overrides"] == {}, rr2
    assert not any(k.startswith("policy.") for k in ring2), ring2
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(a, b)
  else:
    # CLEAN CONTROL: balanced traffic, policy armed — zero actions
    f1, rep, rr, _, _, ring = world(True, base, base + 10)
    assert rep["installed"] == 0 and rep["drains"] == 0, rep
    assert rr["overrides"] == {}, rr
    assert not any(k in ("policy.route", "policy.tune", "policy.drain",
                         "policy.revert") for k in ring), sorted(ring)

try:
    main()
except BaseException:
    # fail FAST: an asserting rank that unwinds into interpreter
    # teardown parks in the PJRT shutdown barrier and converts a clear
    # assertion into a 280s 2-proc timeout on both ranks (the
    # established crash-drill rule)
    import traceback
    traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(1)
print(f"child {rank} POLICY-{mode.upper()} OK", flush=True)
'''


class TestPolicyDrill:
    def test_hot_table_skew_detected_corrected_bit_exact(self,
                                                         tmp_path):
        """Acceptance (round 20): two hot tables hashed onto one engine
        shard trip shard_imbalance; the policy re-routes one of them at
        a lockstep MV_PolicySync cut; the post-action load is balanced,
        the alert clears, and the final state is bit-exact vs the
        ``-mv_policy=false`` oracle world run in the same processes."""
        run_two_process(_SKEW_CHILD, tmp_path, "skew",
                        expect="POLICY-SKEW OK")

    def test_clean_soak_fires_zero_actions(self, tmp_path):
        """Acceptance (round 20): the same soak with balanced traffic
        and the policy armed installs NOTHING (zero-false-positive
        floor; the -mv_policy=false leg of the skew drill covers the
        disarmed control)."""
        run_two_process(_SKEW_CHILD, tmp_path, "clean",
                        expect="POLICY-CLEAN OK")


_DRAIN_CHILD = r'''
import os, sys, json, time
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu import elastic
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.telemetry import flight

base = int(port)
args = [f"-dist_coordinator=127.0.0.1:{base}", f"-dist_rank={rank}",
        "-dist_size=2", "-mv_deadline_s=60",
        "-mv_elastic=true", f"-mv_elastic_addr=127.0.0.1:{base + 10}",
        "-mv_watchdog_s=0.15", "-mv_policy=true",
        "-mv_policy_rules=straggler", "-mv_policy_sustain=1",
        "-mv_policy_cooldown_s=5.0"]
if rank == 1:
    # the deliberate straggler: rank 1 (rank 0 hosts the authority and
    # can never drain) stalls 40ms per window apply
    args.append("-chaos_spec=apply.delay:1.0@0.04")
def main():
  mv.MV_Init(args)
  tab = mv.MV_CreateTable(MatrixTableOption(num_rows=256, num_cols=16))
  ids = np.arange(256, dtype=np.int32)
  d = np.ones((256, 16), np.float32)
  tab.AddRows(ids, d)
  mv.MV_Barrier()
  drained = False
  # FIXED iteration count (never wall-time bounded: the chaos delay
  # makes rank 1 ~10x slower per window — a timed loop would diverge
  # the SPMD verb streams). Sync every 6 iterations, same position.
  for i in range(48):
    for _ in range(4):
        tab.AddFireForget(d, row_ids=ids)
    tab.Wait(tab.GetAsyncHandle(row_ids=ids[:16]))
    if i % 6 == 5:
        acts = mv.MV_PolicySync()
        if any(a.get("kind") == "drain" for a in acts):
            drained = True
            break
  assert drained, "the straggler drain never actuated"
  assert elastic.epoch() == 1, elastic.epoch()
  assert "policy.drain" in {e["kind"] for e in flight.events()}
  if rank == 1:
    assert elastic.is_departed()
  else:
    assert tuple(elastic.members()) == (0,), elastic.members()
    # the survivor keeps training on the shrunk world
    for _ in range(4):
        tab.AddFireForget(d, row_ids=ids)
    got = tab.GetRows(ids)
    assert np.isfinite(got).all()
    rep = mv.MV_PolicyReport()
    assert rep["drains"] == 1, rep
  mv.MV_ShutDown()

try:
    main()
except BaseException:
    # fail FAST (the crash-drill rule): an asserting rank unwinding
    # into teardown parks in the PJRT shutdown barrier and turns one
    # clear assertion into a 280s two-rank timeout
    import traceback
    traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(1)
print(f"child {rank} POLICY-DRAIN OK", flush=True)
'''


class TestPolicyDrainDrill:
    def test_straggler_escalates_to_guarded_drain(self, tmp_path):
        """Loop 3: sustained chaos-injected straggling on
        rank 1 escalates to a policy-staged elastic drain — actuated at
        the lockstep MV_PolicySync as rank 1's MV_ElasticLeave against
        rank 0's MV_ElasticSync — and the survivor continues on the
        shrunk world."""
        run_two_process(_DRAIN_CHILD, tmp_path,
                        expect="POLICY-DRAIN OK")
