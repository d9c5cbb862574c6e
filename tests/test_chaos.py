"""Chaos harness: scenario DSL, global invariant checkers (each with a
deliberately-broken fixture proving it fires), the scripted scenario
library, differential replay, settle-order determinism, engine/KV
failure paths, and migration rollback under same-quantum pool failure.

Fast structural tests run in tier-1; full scenario soaks, the replay
sweep and the random-scenario sweep carry ``@pytest.mark.chaos`` and
run in the CI chaos job (``pytest -m chaos``).

NOTE: the broken fixtures poke private columns ON PURPOSE — that is
how each checker is proven live.  The ``chaos-public-api`` analysis
pass bans such reach-ins from ``src/repro/chaos/`` itself, not from
tests.
"""
import dataclasses
import json
import random

import numpy as np
import pytest

from repro.chaos import (
    SCENARIOS,
    Scenario,
    ScenarioEvent,
    build_sim,
    by_name,
    checker_catalog,
    default_checkers,
    install_checkers,
    run_replay,
    run_scenario,
    seeded_backoff,
)
from repro.chaos.invariants import (
    Capacity,
    DebtBounds,
    GuaranteedP99,
    MirrorCoherence,
    RowLeaks,
    TokenConservation,
)
from repro.core import (
    AdmissionController,
    AdmissionRequest,
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
)
from repro.core.fleet import FleetPlan, RebalanceProposal
from repro.serving.request import Request, RequestState
from repro.serving.simulation import Workload

# -- shared fixtures ---------------------------------------------------------

MINI = Scenario(
    name="mini", seed=5, duration_s=3.0, p99_bound_s=6.0,
    sites=(
        dict(name="east", n_replicas=1, replica_slots=8,
             replica_tps=160.0),
        dict(name="west", n_replicas=1, replica_slots=8,
             replica_tps=160.0),
    ),
    workloads=(
        dict(name="gold", service_class=ServiceClass.GUARANTEED,
             slots=4, slo_ms=800.0, rate_rps=2.0, in_tokens=32,
             out_tokens=32, max_retries=1, pools=("east", "west")),
        dict(name="flex", service_class=ServiceClass.ELASTIC,
             slots=3, slo_ms=2000.0, rate_rps=6.0, in_tokens=32,
             out_tokens=32, max_retries=1, pools=("east", "west")),
    ),
)

MINI_SINGLE = dataclasses.replace(
    MINI, name="mini_single",
    sites=(dict(name="core", n_replicas=1, replica_slots=8,
                replica_tps=160.0),),
    workloads=tuple(dict(w, pools=("core",)) for w in MINI.workloads))


def run_with_sabotage(checker, sabotage, scenario=MINI,
                      sabotage_at=None):
    """Run ``scenario`` under one checker, corrupting state through
    ``sabotage(sim)`` near the end of the run (so later sanctioned
    row-ops cannot launder the damage before the checker sees it)."""
    sim = build_sim(scenario)
    for pool in sim.manager.pools.values():
        pool.ledger.enable_level_audit()
    t_sab = (scenario.duration_s - 3 * scenario.dt
             if sabotage_at is None else sabotage_at)
    done = []

    def sab(sim, now):
        if now >= t_sab and not done:
            sabotage(sim)
            done.append(now)

    violations = []
    sim.step_hooks.append(sab)          # runs before the checker hook
    install_checkers(sim, [checker], violations, scenario)
    sim.run(scenario.duration_s)
    assert done, "sabotage never fired"
    return violations


# -- scenario DSL ------------------------------------------------------------

class TestScenarioDSL:
    def test_build_sim_isolates_workload_state(self):
        """set_rate mutates Workload objects in place; scenarios store
        kwargs so each build starts pristine."""
        sc = dataclasses.replace(MINI_SINGLE, events=(
            ScenarioEvent(0.5, "set_rate",
                          dict(workload="flex", rate=50.0)),))
        sim1 = build_sim(sc)
        sim1.run(1.0)
        assert sim1.workloads["flex"].rate_rps == 50.0
        sim2 = build_sim(sc)
        assert sim2.workloads["flex"].rate_rps == 6.0
        assert sc.workloads[1]["rate_rps"] == 6.0

    def test_unknown_event_kind_rejected(self):
        sc = dataclasses.replace(
            MINI_SINGLE, events=(ScenarioEvent(0.1, "meteor", {}),))
        with pytest.raises(ValueError, match="meteor"):
            build_sim(sc)

    def test_library_lookup(self):
        assert by_name("retry_storm").name == "retry_storm"
        with pytest.raises(KeyError):
            by_name("nope")
        assert len(SCENARIOS) >= 5

    def test_seeded_backoff_is_deterministic(self):
        fn = seeded_backoff(MINI)
        w = Workload(name="gold",
                     service_class=ServiceClass.GUARANTEED, slots=4,
                     slo_ms=800.0, rate_rps=1.0)
        vals = [fn(w, None, a, None) for a in range(4)]
        assert vals == [fn(w, None, a, None) for a in range(4)]
        for v in vals:
            assert MINI.retry_base_s <= v \
                <= MINI.retry_base_s + MINI.retry_jitter_s
        # attempts draw different jitter (crc32, not a constant)
        assert len(set(vals)) > 1

    def test_churn_events_use_public_entry_points(self):
        """add/remove/migrate events round-trip an entitlement through
        the public pool surface while the sim runs."""
        sc = dataclasses.replace(MINI, name="churn", events=(
            ScenarioEvent(0.5, "add_entitlement", dict(
                pool="east", name="standby",
                service_class=ServiceClass.GUARANTEED,
                slo_ms=1000.0, tokens_per_second=20.0, slots=1.0)),
            ScenarioEvent(1.0, "migrate", dict(
                entitlement="standby", src="east", dst="west")),
            ScenarioEvent(1.5, "remove_entitlement", dict(
                pool="west", name="standby")),
        ))
        sim = build_sim(sc)
        sim.run(2.0)
        assert "standby" not in sim.manager.pool("east").entitlements
        assert "standby" not in sim.manager.pool("west").entitlements


# -- every checker fires on a deliberately-broken fixture --------------------

class TestCheckersFire:
    def test_registry_has_at_least_six(self):
        checkers = default_checkers()
        assert len(checkers) >= 6
        names = {c.name for c in checkers}
        assert {"token-conservation", "row-leaks", "debt-bounds",
                "capacity", "mirror-coherence",
                "guaranteed-p99"} <= names
        assert len(checker_catalog()) == len(checkers)

    def test_clean_run_is_quiet(self):
        rep = run_scenario(MINI)
        assert rep["passed"], rep["violations"]

    def test_token_conservation_fires_on_level_poke(self):
        def sabotage(sim):
            pool = sim.manager.pool("east")
            slot = pool.store.slot_of["gold@east"]
            pool.store.col["bucket_level"][slot] += 123.0
        vs = run_with_sabotage(TokenConservation(), sabotage)
        assert any(v.checker == "token-conservation" for v in vs), vs

    def test_row_leaks_fires_on_free_list_corruption(self):
        def sabotage(sim):
            store = sim.manager.pool("east").store
            store._free.append(store.slot_of["gold@east"])
        vs = run_with_sabotage(RowLeaks(), sabotage)
        assert any("row leak" in v.message for v in vs), vs

    def test_row_leaks_fires_on_unknown_settle(self):
        def sabotage(sim):
            # a settle with no outstanding charge is a counted no-op
            sim.manager.pool("east").ledger.settle(
                "never-admitted", 1, 1.0)
        vs = run_with_sabotage(RowLeaks(), sabotage)
        assert any("no outstanding charge" in v.message for v in vs), vs

    def test_debt_bounds_fires_on_out_of_range_debt(self):
        def sabotage(sim):
            pool = sim.manager.pool("east")
            coeff = pool.spec.coefficients
            pool.status["flex@east"].debt = coeff.debt_max + 1.0
        vs = run_with_sabotage(DebtBounds(), sabotage)
        assert any("outside" in v.message for v in vs), vs

    def test_debt_bounds_fires_on_guaranteed_debt_growth(self):
        """Debt-free classes must only drain: raising a guaranteed
        tenant's debt (in range!) trips drain-monotonicity."""
        def sabotage(sim):
            sim.manager.pool("east").status["gold@east"].debt = 0.5
        vs = run_with_sabotage(DebtBounds(), sabotage)
        assert any("debt-free class" in v.message for v in vs), vs

    def test_capacity_fires_on_in_flight_poke(self):
        def sabotage(sim):
            pool = sim.manager.pool("east")
            slot = pool.store.slot_of["gold@east"]
            pool.store.col["in_flight"][slot] += 3
        vs = run_with_sabotage(Capacity(), sabotage)
        assert any("table recount" in v.message for v in vs), vs

    def test_capacity_fires_on_overloaded_backend_lane(self):
        def sabotage(sim):
            replica = sim.replicas["east"][0]
            for i in range(replica.slots + 2):
                rid = f"ghost-{i}"
                sim.requests[rid] = Request(
                    request_id=rid, entitlement="gold",
                    prompt_tokens=[1], max_tokens=1, arrival_s=0.0)
                replica.active.setdefault(rid, [1e9, 0.0])
        vs = run_with_sabotage(Capacity(), sabotage)
        assert any("over its" in v.message for v in vs), vs

    def test_mirror_coherence_fires_on_dirty_host_write(self):
        def sabotage(sim):
            pool = sim.manager.pool("east")
            pool.store.device_state()      # build + cache the mirror
            slot = pool.store.slot_of["gold@east"]
            # host write WITHOUT mark_dirty: the cached mirror goes
            # stale, which is exactly what the checker must observe
            pool.store.col["burst"][slot] += 1.0
        vs = run_with_sabotage(MirrorCoherence(), sabotage)
        assert any("mark_dirty" in v.message for v in vs), vs

    def test_guaranteed_p99_fires_on_absurd_bound(self):
        sc = dataclasses.replace(MINI, p99_bound_s=1e-6)
        rep = run_scenario(sc, checkers=[GuaranteedP99()])
        assert any(v["checker"] == "guaranteed-p99"
                   for v in rep["violations"]), rep


# -- scripted scenario library ----------------------------------------------

class TestScenarioLibrary:
    @pytest.mark.parametrize("scenario", SCENARIOS,
                             ids=[s.name for s in SCENARIOS])
    def test_scenario_passes_all_invariants(self, scenario):
        rep = run_scenario(scenario)
        assert rep["passed"], rep["violations"]
        tier = rep["slo"].get("guaranteed") or {}
        assert tier.get("completions", 0) > 0
        assert tier["p99_s"] <= scenario.p99_bound_s

    def test_failure_scenarios_record_incident_windows(self):
        rep = run_scenario(by_name("correlated_failure"))
        windows = rep["incident_windows"]
        assert len(windows) >= 2
        for key, start, end in windows:
            assert key.startswith("east/")
            assert end is not None and end > start

    def test_report_is_json_serializable(self):
        rep = run_scenario(MINI)
        text = json.dumps(rep, default=str)
        back = json.loads(text)
        assert back["scenario"] == "mini"
        assert len(back["checkers"]) >= 6
        assert back["requests_total"] > 0


# -- differential replay -----------------------------------------------------

class TestDifferentialReplay:
    def test_mini_replay_identical(self):
        res = run_replay(MINI)
        assert res.identical, res.mismatches[:10]
        assert set(res.traces) == {"scalar", "quantum"}
        # the run produced real decisions, not an empty diff
        assert len(res.traces["scalar"].outcomes) > 10
        assert res.traces["scalar"].flight_legs

    @pytest.mark.chaos
    @pytest.mark.parametrize("scenario", SCENARIOS,
                             ids=[s.name for s in SCENARIOS])
    def test_library_replay_identical(self, scenario):
        res = run_replay(scenario)
        assert res.identical, res.mismatches[:10]

    def test_replay_detects_divergence(self):
        """The diff engine itself must fire when decisions differ —
        compare two different seeds of the same scenario."""
        from repro.chaos.replay import capture_trace, diff_traces
        sim_a = build_sim(MINI_SINGLE)
        sim_a.run(2.0)
        sim_b = build_sim(dataclasses.replace(MINI_SINGLE, seed=99))
        sim_b.run(2.0)
        diffs = diff_traces(capture_trace(sim_a, "a"),
                            capture_trace(sim_b, "b"))
        assert diffs


# -- satellite 1: settle-order determinism ----------------------------------

class TestSettleDeterminism:
    @pytest.mark.parametrize("order", [
        ["gold-3", "gold-1", "gold-2"],
        ["gold-2", "gold-3", "gold-1"],
    ])
    def test_same_step_completions_settle_in_rid_order(self, order):
        """Completions landing on one dt step must settle sorted by
        (finished_s, rid), not by ``replica.active`` dict insertion
        order — the insertion permutation simulates what
        PYTHONHASHSEED/dispatch history variation used to leak into
        the settle (and retry re-submission) sequence."""
        sim = build_sim(MINI_SINGLE, telemetry=False)
        captured = []
        sim.gateway.on_complete_batch = \
            lambda completions, now: captured.extend(
                rid for rid, _, _ in completions)
        replica = sim.replicas["core"][0]
        for rid in order:
            req = Request(request_id=rid, entitlement="gold",
                          prompt_tokens=[1], max_tokens=1,
                          arrival_s=0.0)
            req.state = RequestState.DECODING
            sim.requests[rid] = req
            replica.active[rid] = [1e-6, 0.0]   # finishes this step
        sim._advance_replicas(0.0)
        assert captured == sorted(order)


# -- satellite 3: migration rollback & same-quantum pool failure -------------

def _two_pools():
    manager = PoolManager()
    for name in ("src", "dst"):
        pool = manager.add_pool(PoolSpec(
            name=name, model="m", scaling=ScalingBounds(1, 2),
            per_replica=Resources(1000.0, 0.0, 8.0)))
        pool.set_replicas(1)
    manager.pool("src").add_entitlement(EntitlementSpec(
        name="ent", tenant_id="t", pool="src",
        qos=QoS(service_class=ServiceClass.ELASTIC,
                slo_target_ms=1000),
        baseline=Resources(200.0, 0.0, 4.0)))
    return manager


class TestMigrationRollback:
    def test_attach_failure_rolls_back_to_source(self):
        manager = _two_pools()
        src = manager.pool("src")
        # live traffic: one outstanding charge + in-flight record
        dec = AdmissionController(src).decide(AdmissionRequest(
            entitlement="ent", input_tokens=10, max_tokens=10,
            arrival_s=0.0, request_id="r1"))
        assert dec.admitted
        src.status["ent"].debt = 0.25
        level_before = src.ledger.bucket("ent").level
        # destination already owns the name → attach raises
        manager.pool("dst").add_entitlement(EntitlementSpec(
            name="ent", tenant_id="other", pool="dst",
            qos=QoS(service_class=ServiceClass.ELASTIC,
                    slo_target_ms=1000),
            baseline=Resources(100.0, 0.0, 2.0)))
        # now=0.0 so bucket refill can't mask the level comparison
        with pytest.raises(ValueError):
            manager.migrate_entitlement("ent", "src", "dst", now=0.0)
        # everything restored on the source: spec, bucket level, debt,
        # in-flight record (settling it still works)
        assert "ent" in src.entitlements
        assert src.ledger.bucket("ent").level \
            == pytest.approx(level_before)
        assert src.status["ent"].debt == pytest.approx(0.25)
        assert src.pool_in_flight() == 1
        assert src.on_complete("r1", 10, now=1.0) is not None
        assert src.pool_in_flight() == 0
        assert src.ledger.unknown_settles == 0

    def test_plan_quantum_skips_migration_into_failed_pool(self):
        """A rebalance proposed before an outage must not execute into
        the dead pool in the same quantum — it lands in
        ``plan.skipped`` and the entitlement stays put."""
        manager = _two_pools()
        prop = RebalanceProposal(entitlement="ent", src="src",
                                 dst="dst", debt=0.5,
                                 baseline_tps=200.0, reason="debt")

        class StubPlanner:
            def plan(self, pools, records, now):
                return FleetPlan(decisions={}, migrations=[prop],
                                 unmet_replicas={})

        manager.planner = StubPlanner()
        manager.pool("dst").set_replicas(0)      # fails this quantum
        plan = manager.plan_quantum(now=1.0)
        assert plan.skipped == [prop]
        assert plan.applied == []
        assert "ent" in manager.pool("src").entitlements
        assert "ent" not in manager.pool("dst").entitlements
        # destination recovers → the same proposal applies next round
        manager.pool("dst").set_replicas(1)
        plan2 = manager.plan_quantum(now=2.0)
        assert [p.entitlement for p in plan2.applied] == ["ent"]
        assert "ent" in manager.pool("dst").entitlements

    def test_rollback_under_seeded_chaos_scenario(self):
        """Pin the rollback with a live scenario: a migrate event whose
        destination already owns the name fails mid-run; the control
        plane must carry on with every invariant intact."""
        sc = dataclasses.replace(MINI, name="clash", events=(
            ScenarioEvent(0.5, "add_entitlement", dict(
                pool="east", name="clash",
                service_class=ServiceClass.GUARANTEED,
                slo_ms=1000.0, tokens_per_second=20.0, slots=1.0)),
            ScenarioEvent(0.6, "add_entitlement", dict(
                pool="west", name="clash",
                service_class=ServiceClass.GUARANTEED,
                slo_ms=1000.0, tokens_per_second=20.0, slots=1.0)),
        ))
        sim = build_sim(sc)
        for pool in sim.manager.pools.values():
            pool.ledger.enable_level_audit()
        errors = []

        def attempt(sim, now):
            try:
                sim.manager.migrate_entitlement(
                    "clash", "east", "west", now)
            except ValueError as e:
                errors.append(e)

        sim.at(1.5, "call", fn=attempt)
        violations = []
        install_checkers(sim, default_checkers(), violations, sc)
        sim.run(sc.duration_s)
        assert errors, "migration clash never raised"
        assert "clash" in sim.manager.pool("east").entitlements
        assert not violations, violations[:5]


# -- satellite 2 lives in test_engine_failures.py ----------------------------
# (KV reclamation after mid-stream eviction, double-free rejection,
#  zero-live engine steps — needs the real-model fixture)


# -- random scenario sweep ---------------------------------------------------

def random_scenario(seed: int) -> Scenario:
    """Property-style scenario generator (stdlib ``random`` — the
    container has no hypothesis; the sweep is seeded instead).  All
    workloads share one pool order so the replay-parity contract
    holds by construction."""
    rng = random.Random(seed)
    n_pools = rng.randint(1, 2)
    pools = tuple(f"p{i}" for i in range(n_pools))
    sites = tuple(
        dict(name=p, n_replicas=rng.randint(1, 2), replica_slots=8,
             replica_tps=160.0)
        for p in pools)
    workloads = [dict(
        name="gold", service_class=ServiceClass.GUARANTEED,
        slots=4, slo_ms=800.0, rate_rps=rng.uniform(1.0, 3.0),
        in_tokens=32, out_tokens=32, max_retries=rng.randint(0, 2),
        pools=pools)]
    for i in range(rng.randint(1, 2)):
        workloads.append(dict(
            name=f"fl{i}",
            service_class=rng.choice(
                [ServiceClass.ELASTIC, ServiceClass.DEDICATED]),
            slots=rng.randint(2, 4), slo_ms=2000.0,
            rate_rps=rng.uniform(2.0, 10.0), in_tokens=32,
            out_tokens=32, max_retries=rng.randint(0, 3),
            pools=pools))
    duration = rng.uniform(4.0, 6.0)
    events = []
    if rng.random() < 0.8:       # one failure/recovery window
        p = rng.choice(pools)
        idx = rng.randrange(
            next(s["n_replicas"] for s in sites if s["name"] == p))
        t = rng.uniform(1.0, duration / 2)
        events.append(ScenarioEvent(
            t, "fail_replica", dict(pool=p, idx=idx)))
        events.append(ScenarioEvent(
            t + rng.uniform(0.5, 2.0), "recover_replica",
            dict(pool=p, idx=idx)))
    if rng.random() < 0.6:       # one demand step
        w = rng.choice(workloads[1:])["name"] if len(workloads) > 1 \
            else "gold"
        events.append(ScenarioEvent(
            rng.uniform(1.0, duration - 1.0), "set_rate",
            dict(workload=w, rate=rng.uniform(0.5, 20.0))))
    return Scenario(
        name=f"random_{seed}", seed=seed, duration_s=duration,
        sites=sites, workloads=tuple(workloads),
        events=tuple(sorted(events, key=lambda e: e.t)))


@pytest.mark.chaos
class TestRandomScenarioSweep:
    @pytest.mark.parametrize("seed", [101, 202, 303, 404, 505, 606])
    def test_random_scenario_holds_all_invariants(self, seed):
        rep = run_scenario(random_scenario(seed))
        assert rep["passed"], rep["violations"][:5]

    @pytest.mark.parametrize("seed", [101, 404])
    def test_random_scenario_replays_identically(self, seed):
        res = run_replay(random_scenario(seed))
        assert res.identical, res.mismatches[:10]
