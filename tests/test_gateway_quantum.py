"""``Gateway.handle_quantum`` — the batched admission path must be
decision-identical to the per-request scalar pipeline, and the denial
attribution / spill-hop fixes must hold on both paths."""
import random

import pytest

from repro.core import (
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    RouteEntry,
    ScalingBounds,
    ServiceClass,
    TokenPool,
)
from repro.gateway import Gateway, QuantumRequest


def mkpool(name, tps=1000.0, slots=4.0, default_max_tokens=64,
           window=1.0):
    return TokenPool(PoolSpec(
        name=name, model="m", scaling=ScalingBounds(1, 1),
        per_replica=Resources(tps, float(1 << 30), slots),
        default_max_tokens=default_max_tokens, bucket_window_s=window))


def ent(name, pool, klass=ServiceClass.GUARANTEED, tps=500.0, conc=4.0,
        slo=500.0):
    return EntitlementSpec(
        name=name, tenant_id="t", pool=pool,
        qos=QoS(service_class=klass, slo_target_ms=slo),
        baseline=Resources(tps, 0.0, conc))


def _resp_key(r):
    return (r.status, r.pool, r.entitlement, r.spill_hops, r.reason)


class TestQuantumScalarParity:
    """Randomized multi-pool workloads: ``handle_quantum`` must make
    the decisions the sequential ``handle`` loop makes, request for
    request.  Routes are drawn as prefixes of one pool order, the
    regime where leg-round batching provably replays the sequential
    interleaving."""

    def _build(self, seed):
        rng = random.Random(seed)
        mgr = PoolManager([
            mkpool("a", tps=rng.choice([300.0, 600.0]),
                   slots=rng.choice([2.0, 4.0])),
            mkpool("b", tps=600.0, slots=4.0),
            mkpool("c", tps=1000.0, slots=8.0),
        ])
        classes = [ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
                   ServiceClass.SPOT]
        gw = Gateway(mgr)
        for k in range(4):
            klass = classes[k % 3]
            depth = rng.randint(1, 3)
            route = []
            for pname in ["a", "b", "c"][:depth]:
                ename = f"t{k}@{pname}"
                mgr.pool(pname).add_entitlement(ent(
                    ename, pname, klass=klass,
                    tps=rng.choice([80.0, 200.0]),
                    conc=rng.choice([1.0, 2.0]),
                    slo=rng.choice([250.0, 1000.0, 30000.0])))
                if klass is ServiceClass.SPOT:
                    mgr.pool(pname).ledger.set_rate(ename, 200.0, 0.0)
                    mgr.pool(pname).ledger.bucket(ename).level = 200.0
                route.append((pname, ename))
            gw.register_route(f"k{k}", route)
        reqs = [QuantumRequest(api_key=f"k{rng.randint(0, 4)}"
                               if rng.random() < 0.9 else "nokey",
                               request_id=f"r{i}",
                               input_tokens=rng.choice([16, 48]),
                               max_tokens=rng.choice([None, 32, 96]))
                for i in range(24)]
        return gw, reqs

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_decision_identical(self, seed):
        gw_q, reqs = self._build(seed)
        gw_s, _ = self._build(seed)            # identical fresh state

        quantum = gw_q.handle_quantum(reqs, now=0.0)
        scalar = [gw_s.handle(q.api_key, q.request_id, q.input_tokens,
                              q.max_tokens, now=0.0) for q in reqs]
        assert [_resp_key(r) for r in quantum] == \
            [_resp_key(r) for r in scalar]
        for rq, rs in zip(quantum, scalar):
            assert rq.priority == pytest.approx(rs.priority, rel=1e-5)
        # bookkeeping converges too: same in-flight sets per pool
        for pname in ["a", "b", "c"]:
            assert (sorted(gw_q.manager.pool(pname).in_flight)
                    == sorted(gw_s.manager.pool(pname).in_flight))
            # and the same bucket levels (charges identical)
            pq, ps = gw_q.manager.pool(pname), gw_s.manager.pool(pname)
            for ename, bucket in pq.ledger._buckets.items():
                assert bucket.level == pytest.approx(
                    ps.ledger.bucket(ename).level)

    def test_counters_match_scalar(self, ):
        gw_q, reqs = self._build(7)
        gw_s, _ = self._build(7)
        gw_q.handle_quantum(reqs, now=0.0)
        for q in reqs:
            gw_s.handle(q.api_key, q.request_id, q.input_tokens,
                        q.max_tokens, now=0.0)
        keys = set(gw_q.store.keys()) | set(gw_s.store.keys())
        for key in keys:
            if key.startswith(("admits:", "denials:", "spills:",
                               "unroutable:")):
                assert gw_q.store.get(key) == gw_s.store.get(key), key


class TestQuantumPath:
    def test_empty_quantum(self):
        mgr = PoolManager([mkpool("a")])
        assert Gateway(mgr).handle_quantum([], now=0.0) == []

    def test_unknown_key_401(self):
        mgr = PoolManager([mkpool("a")])
        gw = Gateway(mgr)
        [r] = gw.handle_quantum(
            [QuantumRequest("nope", "r1", 16, 16)], now=0.0)
        assert r.status == 401 and r.reason == "unknown_key"

    def test_per_leg_default_max_tokens(self):
        """A request omitting max_tokens must be charged each LEG'S own
        pool default — pool a's large default exhausts its budget, pool
        b's small default fits."""
        mgr = PoolManager([
            mkpool("a", default_max_tokens=512, tps=100.0),
            mkpool("b", default_max_tokens=32, tps=100.0),
        ])
        mgr.pool("a").add_entitlement(ent("e@a", "a", tps=100.0))
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=100.0))
        gw = Gateway(mgr)
        gw.register_route("k", [("a", "e@a"), ("b", "e@b")])
        [r] = gw.handle_quantum(
            [QuantumRequest("k", "r1", 16, None)], now=0.0)
        assert (r.status, r.pool, r.spill_hops) == (200, "b", 1)
        # charged 16 + 32 on b (not 16 + 512, not a's default)
        assert mgr.pool("b").ledger.bucket("e@b").level == \
            pytest.approx(100.0 - 48.0)

    def test_spill_reenters_next_leg_in_order(self):
        """Requests denied on the preferred pool re-enter the next
        leg's batch ahead of nothing — arrival order is preserved
        within the spill batch."""
        mgr = PoolManager([mkpool("a", tps=100.0), mkpool("b", tps=150.0)])
        mgr.pool("a").add_entitlement(ent("e@a", "a", tps=100.0))
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=150.0))
        gw = Gateway(mgr)
        gw.register_route("k", [("a", "e@a"), ("b", "e@b")])
        # each request charges 96; a affords one, b affords one more
        resps = gw.handle_quantum(
            [QuantumRequest("k", f"r{i}", 32, 64) for i in range(3)],
            now=0.0)
        assert [(r.status, r.pool) for r in resps] == \
            [(200, "a"), (200, "b"), (429, None)]
        assert resps[1].spill_hops == 1
        assert resps[2].reason == "token_budget"
        assert resps[2].retry_after_s > 0


class TestSpillOrdering:
    def test_mixed_skip_and_deny_spills_keep_arrival_order(self):
        """A leg naming a missing entitlement (espec-miss skip) and a
        kernel denial spill out of round 0 by different code paths —
        the next round's batch must still replay in ARRIVAL order, or
        pool b would give r2's budget to r1."""
        mgr = PoolManager([mkpool("a", tps=1000.0), mkpool("b", tps=150.0)])
        mgr.pool("a").add_entitlement(ent("e1@a", "a", tps=30.0))
        # e2@a is routed but never created on pool a → espec-miss skip
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=150.0))
        gw = Gateway(mgr)
        gw.register_route("k1", [("a", "e1@a"), ("b", "e@b")])
        gw.register_route("k2", [("a", "e2@a"), ("b", "e@b")])
        # r1 (kernel budget denial on a) arrives BEFORE r2 (skip on a);
        # b's bucket affords exactly one 96-token charge
        resps = gw.handle_quantum(
            [QuantumRequest("k1", "r1", 32, 64),
             QuantumRequest("k2", "r2", 32, 64)], now=0.0)
        assert [(r.status, r.pool) for r in resps] == \
            [(200, "b"), (429, None)]

        # and the scalar loop agrees
        gw2 = Gateway(PoolManager([mkpool("a", tps=1000.0),
                                   mkpool("b", tps=150.0)]))
        gw2.manager.pool("a").add_entitlement(ent("e1@a", "a", tps=30.0))
        gw2.manager.pool("b").add_entitlement(ent("e@b", "b", tps=150.0))
        gw2.register_route("k1", [("a", "e1@a"), ("b", "e@b")])
        gw2.register_route("k2", [("a", "e2@a"), ("b", "e@b")])
        scalar = [gw2.handle("k1", "r1", 32, 64, now=0.0),
                  gw2.handle("k2", "r2", 32, 64, now=0.0)]
        assert [_resp_key(r) for r in resps] == \
            [_resp_key(r) for r in scalar]


class TestQuantumHeadroomPolicy:
    def test_headroom_reorder_reports_declared_position(self):
        """Under the budget-aware policy the quantum path follows the
        reordered legs but still reports declared-route positions."""
        mgr = PoolManager([mkpool("a", tps=50.0), mkpool("b", tps=1000.0)])
        mgr.pool("a").add_entitlement(ent("e@a", "a", tps=50.0))
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=500.0))
        gw = Gateway(mgr, spill_policy="headroom")
        gw.register_route("k", [("a", "e@a"), ("b", "e@b")])
        # a's bucket (50) cannot afford 96; headroom ranks b first
        [r] = gw.handle_quantum(
            [QuantumRequest("k", "r1", 32, 64)], now=0.0)
        assert (r.status, r.pool, r.spill_hops) == (200, "b", 1)
        # and a was never charged
        assert mgr.pool("a").ledger.bucket("e@a").level == \
            pytest.approx(50.0)


class TestDenialAttribution:
    """Satellite fix: the denial counter goes to the first leg actually
    TRIED, and spill_hops carries the declared-route position through
    ``route_order`` instead of re-searching."""

    def _gw(self, a_up=True):
        mgr = PoolManager([mkpool("a", tps=100.0), mkpool("b", tps=10.0)])
        mgr.pool("a").add_entitlement(ent("e@a", "a", tps=100.0))
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=10.0))
        if not a_up:
            mgr.pool("a").set_replicas(0)
        gw = Gateway(mgr)
        gw.register_route("k", [("a", "e@a"), ("b", "e@b")])
        return gw

    @pytest.mark.parametrize("batched", [False, True])
    def test_denial_attributed_to_first_tried_leg(self, batched):
        """With the preferred leg UNAVAILABLE, a denial on the spill
        target must be charged to the spill target — the old code
        charged route[0], a pool that never saw the request."""
        gw = self._gw(a_up=False)
        if batched:
            [r] = gw.handle_quantum(
                [QuantumRequest("k", "r1", 32, 64)], now=0.0)
        else:
            r = gw.handle("k", "r1", 32, 64, now=0.0)
        assert r.status == 429 and r.reason == "token_budget"
        assert gw.store.get("denials:e@b") == 1.0
        assert gw.store.get("denials:e@a") is None     # never tried

    @pytest.mark.parametrize("batched", [False, True])
    def test_unroutable_key_not_charged_to_any_leg(self, batched):
        gw = self._gw()
        gw.manager.pool("a").set_replicas(0)
        gw.manager.pool("b").set_replicas(0)
        if batched:
            [r] = gw.handle_quantum(
                [QuantumRequest("k", "r1", 32, 64)], now=0.0)
        else:
            r = gw.handle("k", "r1", 32, 64, now=0.0)
        assert r.status == 429 and r.reason == "pool_unavailable"
        assert gw.store.get("unroutable:k") == 1.0
        assert gw.store.keys("denials:") == []

    @pytest.mark.parametrize("batched", [False, True])
    def test_spill_hops_is_declared_position(self, batched):
        """spill_hops must report the admitting leg's position in the
        DECLARED route even when a route repeats a leg before it."""
        mgr = PoolManager([mkpool("a", tps=10.0), mkpool("b", tps=150.0)])
        mgr.pool("a").add_entitlement(ent("e@a", "a", tps=10.0))
        mgr.pool("b").add_entitlement(ent("e@b", "b", tps=150.0))
        gw = Gateway(mgr)
        # leg (a, e@a) is declared twice ahead of the admitting leg
        gw.register_route("k", [RouteEntry("a", "e@a"),
                                RouteEntry("a", "e@a"),
                                RouteEntry("b", "e@b")])
        if batched:
            [r] = gw.handle_quantum(
                [QuantumRequest("k", "r1", 32, 64)], now=0.0)
        else:
            r = gw.handle("k", "r1", 32, 64, now=0.0)
        assert r.status == 200 and r.pool == "b"
        assert r.spill_hops == 2


class TestFastPathParity:
    """The quantum path against the sequential scalar loop on route
    sets the old single-leg fast path took (even seeds: one leg per
    key, on any pool) and on mixed quanta (odd seeds: routes drawn as
    prefixes of one pool order, one to three legs).  Both kinds hold a
    dead pool, a leg naming an entitlement its pool never heard of
    (on a non-last leg where routes have several), a route whose only
    pool does not exist and an unknown key.  Retry-After is not
    compared: the quantum's hints describe the pool after the quantum
    (``Gateway._deny_hint``)."""

    def _build(self, seed):
        rng = random.Random(seed)
        mgr = PoolManager([
            mkpool("a", tps=rng.choice([300.0, 600.0]),
                   slots=rng.choice([2.0, 4.0])),
            mkpool("b", tps=600.0, slots=4.0),
            mkpool("c", tps=1000.0, slots=8.0),
        ])
        mgr.pool("c").set_replicas(0)
        classes = [ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
                   ServiceClass.SPOT]
        gw = Gateway(mgr)
        order = ["a", "b", "c"]
        rng.shuffle(order)
        prefix = seed % 2 == 1

        def add(pname, ename, klass):
            mgr.pool(pname).add_entitlement(ent(
                ename, pname, klass=klass,
                tps=rng.choice([80.0, 200.0]),
                conc=rng.choice([1.0, 2.0]),
                slo=rng.choice([250.0, 1000.0, 30000.0])))
            if klass is ServiceClass.SPOT:
                mgr.pool(pname).ledger.set_rate(ename, 200.0, 0.0)
                mgr.pool(pname).ledger.bucket(ename).level = 200.0

        for k in range(5):
            klass = classes[k % 3]
            pools = (order[:rng.randint(1, 3)] if prefix else
                     rng.choices(["a", "b", "c"], weights=[2, 2, 1]))
            for pname in pools:
                add(pname, f"t{k}@{pname}", klass)
            gw.register_route(f"k{k}", [(p, f"t{k}@{p}") for p in pools])
        # espec-miss → NOT_BOUND, then the next leg where there is one
        miss = [(order[0] if prefix else "a", "ghost")]
        if prefix:
            add(order[1], f"g@{order[1]}", ServiceClass.ELASTIC)
            miss.append((order[1], f"g@{order[1]}"))
        gw.register_route("kmiss", miss)
        gw.register_route("kdead", [("zpool", "ez")])
        keys = [f"k{i}" for i in range(5)] + ["kmiss", "kdead", "nokey"]
        reqs = [QuantumRequest(api_key=rng.choice(keys),
                               request_id=f"r{i}",
                               input_tokens=rng.choice([16, 48]),
                               max_tokens=rng.choice([None, 32, 96]))
                for i in range(32)]
        return gw, reqs

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6])
    def test_fast_matches_generic(self, seed):
        gw_q, reqs = self._build(seed)
        gw_s, _ = self._build(seed)            # identical fresh state

        quantum = gw_q.handle_quantum(reqs, now=0.0)
        scalar = [gw_s.handle(q.api_key, q.request_id, q.input_tokens,
                              q.max_tokens, now=0.0) for q in reqs]
        for rq, rs in zip(quantum, scalar):
            assert _resp_key(rq) == _resp_key(rs)
            assert rq.request_id == rs.request_id
            assert rq.priority == pytest.approx(rs.priority, rel=1e-5)
        for pname in ["a", "b", "c"]:
            pq, ps = gw_q.manager.pool(pname), gw_s.manager.pool(pname)
            assert sorted(pq.in_flight) == sorted(ps.in_flight)
            for ename, bucket in pq.ledger._buckets.items():
                assert bucket.level == pytest.approx(
                    ps.ledger.bucket(ename).level)
            assert list(pq.store.col["demand_window"][
                pq.store.live_slots()]) == pytest.approx(
                list(ps.store.col["demand_window"][
                    ps.store.live_slots()]))
        keys = set(gw_q.store.keys()) | set(gw_s.store.keys())
        for key in keys:
            if key.startswith(("admits:", "denials:", "spills:",
                               "unroutable:")):
                assert gw_q.store.get(key) == gw_s.store.get(key), key

    def test_mixed_single_and_multi_leg_quantum_matches_scalar(self):
        """Single-leg keys on ``a`` and ``c`` and a two-leg key a → b
        in one quantum: every decision is the scalar loop's, and each
        pool is dispatched once in each round that reaches it, pools
        in the order of their earliest request."""

        def build(telemetry):
            mgr = PoolManager([mkpool("a", tps=200.0), mkpool("b"),
                               mkpool("c", tps=150.0)])
            for pname, tps in (("a", 200.0), ("b", 500.0), ("c", 150.0)):
                mgr.pool(pname).add_entitlement(ent(f"e@{pname}", pname,
                                                    tps=tps))
            gw = Gateway(mgr, telemetry=telemetry)
            gw.register_route("sa", [("a", "e@a")])
            gw.register_route("sc", [("c", "e@c")])
            gw.register_route("m", [("a", "e@a"), ("b", "e@b")])
            return gw

        keys = ["sc", "m", "sa", "m", "sc", "m", "sa"]
        reqs = [QuantumRequest(k, f"r{i}", 32, 64)
                for i, k in enumerate(keys)]
        gw_q, gw_s = build(True), build(False)
        quantum = gw_q.handle_quantum(reqs, now=0.0)
        scalar = [gw_s.handle(q.api_key, q.request_id, q.input_tokens,
                              q.max_tokens, now=0.0) for q in reqs]
        assert [_resp_key(r) for r in quantum] == \
            [_resp_key(r) for r in scalar]
        assert {(r.status, r.pool) for r in quantum} >= {
            (200, "a"), (200, "b"), (200, "c"), (429, None)}
        for rq, rs in zip(quantum, scalar):
            assert rq.priority == pytest.approx(rs.priority, rel=1e-5)
        for key in set(gw_q.store.keys()) | set(gw_s.store.keys()):
            if key.startswith(("admits:", "denials:", "spills:")):
                assert gw_q.store.get(key) == gw_s.store.get(key), key
        r = gw_q.telemetry.spans.rows()
        rounds = [i for i in range(r["id"].size)
                  if r["name"][i] == "gateway.round"]
        dispatched = [[r["pool"][i] for i in range(r["id"].size)
                       if r["name"][i] == "gateway.admit"
                       and r["parent"][i] == r["id"][rnd]]
                      for rnd in rounds]
        assert dispatched == [["c", "a"], ["b"]]
