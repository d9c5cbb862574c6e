"""Telemetry plane (``repro.telemetry``): batch-recorder == scalar-
oracle parity for histograms/counters, flight-recorder wraparound and
``explain()`` == ``GatewayResponse`` parity sweeps on the scalar AND
quantum gateway paths, a no-retrace pin with telemetry on, the
StateStore TTL regression, ``pool.stats()``-as-registry-view, SLO
attainment math, exporter well-formedness (Prometheus text + Chrome
trace JSON), and the ``telemetry-hot-path`` sanitizer pass."""
import json
import random
import re
import textwrap

import numpy as np
import pytest

from repro.analysis import analyze
from repro.core import (
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
    StateStore,
    TokenPool,
)
from repro.core.control_plane import TRACE_COUNTS
from repro.gateway import Gateway, QuantumRequest
from repro.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    Telemetry,
    prometheus_text,
)
from repro.telemetry import flight as fl

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# registry: batch row-ops == scalar oracles
# ---------------------------------------------------------------------------

def _hist_pair(n_series=5, lo=1e-3, hi=1e3, buckets=24):
    a = MetricsRegistry().histogram("h", labels=("s",), lo=lo, hi=hi,
                                    buckets=buckets)
    b = MetricsRegistry().histogram("h", labels=("s",), lo=lo, hi=hi,
                                    buckets=buckets)
    for i in range(n_series):
        assert a.series((f"s{i}",)) == b.series((f"s{i}",))
    return a, b


def _assert_hist_equal(a, b):
    np.testing.assert_array_equal(a.counts, b.counts)
    np.testing.assert_allclose(a.sums, b.sums, rtol=1e-12)
    np.testing.assert_array_equal(a.totals, b.totals)


class TestHistogramParity:
    def test_random_batches_match_scalar_oracle(self):
        rng = np.random.RandomState(7)
        batched, oracle = _hist_pair()
        for _ in range(50):
            m = rng.randint(0, 40)
            # span under-range, in-range, over-range and exact edges
            vals = rng.choice(
                [1e-5, 1e-3, 0.37, 42.0, 999.0, 1e3, 5e6],
                size=m) * rng.uniform(0.5, 2.0, size=m)
            sids = rng.randint(0, 5, size=m)
            batched.observe_rows(vals, sids)
            for v, s in zip(vals, sids):
                oracle.observe(int(s), float(v))
            _assert_hist_equal(batched, oracle)

    def test_edge_values_land_consistently(self):
        batched, oracle = _hist_pair()
        edges = batched.edges
        vals = np.concatenate([edges, edges * (1 + 1e-12), [0.0]])
        sids = np.zeros(len(vals), np.int64)
        batched.observe_rows(vals, sids)
        for v in vals:
            oracle.observe(0, float(v))
        _assert_hist_equal(batched, oracle)

    def test_quantile_bounds(self):
        h = MetricsRegistry().histogram("h", lo=0.01, hi=10.0)
        sid = h.series(())
        assert h.quantile(sid, 0.99) == 0.0           # empty
        h.observe_rows(np.full(100, 0.5), np.full(100, sid))
        q = h.quantile(sid, 0.5)
        # bucket-interpolated: within the bucket containing 0.5
        b = int(np.searchsorted(h.edges, 0.5))
        lo_edge = h.edges[b - 1] if b else 0.0
        assert lo_edge <= q <= h.edges[b]
        h.observe(sid, 1e9)                            # overflow clamps
        assert h.quantile(sid, 1.0) == pytest.approx(float(h.edges[-1]))


class TestCounterGauge:
    def test_inc_rows_matches_scalar(self):
        rng = np.random.RandomState(3)
        a = MetricsRegistry().counter("c", labels=("s",))
        b = MetricsRegistry().counter("c", labels=("s",))
        for i in range(4):
            a.series((f"s{i}",)), b.series((f"s{i}",))
        for _ in range(30):
            m = rng.randint(0, 20)
            sids = rng.randint(0, 4, size=m)
            by = rng.uniform(0, 5, size=m)
            a.inc_rows(sids, by)
            for s, v in zip(sids, by):
                b.inc(int(s), float(v))
        np.testing.assert_allclose(a.values, b.values, rtol=1e-12)

    def test_counters_reject_negative(self):
        c = MetricsRegistry().counter("c")
        sid = c.series(())
        with pytest.raises(ValueError):
            c.inc(sid, -1.0)
        with pytest.raises(ValueError):
            c.inc_rows(np.array([sid]), np.array([-0.5]))
        c.inc_rows(np.array([], np.int64), np.array([]))  # empty ok

    def test_gauge_callback_binding(self):
        g = MetricsRegistry().gauge("g", labels=("p",))
        state = {"v": 1.0}
        sid = g.bind(("x",), lambda: state["v"])
        assert g.read(sid) == 1.0
        state["v"] = 7.5
        assert g.read(sid) == 7.5                     # live view

    def test_kind_conflict(self):
        r = MetricsRegistry()
        r.counter("m")
        with pytest.raises(TypeError):
            r.gauge("m")


if HAVE_HYPOTHESIS:

    class TestHistogramParityHypothesis:
        @given(data=st.data())
        @settings(max_examples=25, deadline=None, derandomize=True)
        def test_observe_rows_matches_oracle(self, data):
            batched, oracle = _hist_pair(n_series=3)
            batches = data.draw(st.lists(
                st.lists(st.tuples(
                    st.floats(min_value=0.0, max_value=1e6,
                              allow_nan=False),
                    st.integers(min_value=0, max_value=2)),
                    max_size=20),
                max_size=8))
            for batch in batches:
                if batch:
                    vals = np.array([v for v, _ in batch])
                    sids = np.array([s for _, s in batch])
                    batched.observe_rows(vals, sids)
                    for v, s in batch:
                        oracle.observe(s, v)
            _assert_hist_equal(batched, oracle)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def _record_n(self, rec, n, start=0):
        for k in range(start, start + n):
            rec.record(f"r{k}", float(k), "p", 0, k % 4,
                       fl.VERDICT_ADMIT if k % 2 else fl.VERDICT_DENY,
                       0 if k % 2 else 3, 1.0, 0.5, 10.0, 0.1, 0.2,
                       100.0)

    def test_wraparound(self):
        rec = FlightRecorder(capacity=8)
        self._record_n(rec, 20)
        assert rec.head == 20
        assert len(rec) == 8
        # only the 8 newest survive; older rids are evicted
        assert rec.explain("r5") is None
        tr = rec.explain("r19")
        assert tr is not None and tr.legs[0].seq == 20
        recent = rec.recent(n=100)
        assert [r.seq for r in recent] == list(range(20, 12, -1))

    def test_batch_matches_scalar_rings(self):
        rng = np.random.RandomState(11)
        a = FlightRecorder(capacity=16)
        b = FlightRecorder(capacity=16)
        assert a.pool_id("p") == b.pool_id("p")
        total = 0
        for _ in range(10):
            m = int(rng.randint(0, 12))
            rids = [f"q{total + k}" for k in range(m)]
            rows = rng.randint(-1, 6, size=m)
            verd = rng.randint(0, 2, size=m).astype(np.int16)
            reas = rng.randint(0, 5, size=m).astype(np.int16)
            prio = rng.uniform(0, 5, size=m)
            a.record_batch(rids, 1.5, 0, 0, rows, verd,
                           reas, prio, 0.9, 3.0, 0.1, 0.2, 64.0)
            for k in range(m):
                b.record(rids[k], 1.5, "p", 0, int(rows[k]),
                         int(verd[k]), int(reas[k]), float(prio[k]),
                         0.9, 3.0, 0.1, 0.2, 64.0)
            total += m
        assert a.head == b.head
        a._materialize(), b._materialize()   # rid hashes are lazy
        for name in a.col:
            np.testing.assert_array_equal(a.col[name], b.col[name],
                                          err_msg=name)

    def test_oversize_batch_keeps_tail(self):
        rec = FlightRecorder(capacity=4)
        rids = [f"r{k}" for k in range(10)]
        rec.record_batch(rids, 0.0, -1,
                         np.arange(10), -1,
                         np.zeros(10, np.int16), np.zeros(10, np.int16),
                         0.0, 0.0, 0.0, 0.0, 0.0, 0.0)
        assert rec.head == 10
        assert rec.explain("r0") is None
        assert rec.explain("r9").legs[0].leg == 9

    def test_filters(self):
        rec = FlightRecorder(capacity=32)
        self._record_n(rec, 10)
        denies = rec.recent(verdict=fl.VERDICT_DENY)
        assert denies and all(
            r.verdict == fl.VERDICT_DENY for r in denies)
        assert rec.recent(pool="nope") == []


# ---------------------------------------------------------------------------
# explain() == GatewayResponse parity (scalar + quantum paths)
# ---------------------------------------------------------------------------

def mkpool(name, tps=1000.0, slots=4.0, default_max_tokens=64):
    return TokenPool(PoolSpec(
        name=name, model="m", scaling=ScalingBounds(1, 1),
        per_replica=Resources(tps, float(1 << 30), slots),
        default_max_tokens=default_max_tokens, bucket_window_s=1.0))


def ent(name, pool, klass=ServiceClass.GUARANTEED, tps=500.0,
        conc=4.0):
    return EntitlementSpec(
        name=name, tenant_id="t", pool=pool,
        qos=QoS(service_class=klass, slo_target_ms=500.0),
        baseline=Resources(tps, 0.0, conc))


def _build_gateway(seed):
    """Multi-pool gateway with prefix routes (the regime where the
    quantum path replays the scalar interleaving exactly)."""
    rng = random.Random(seed)
    mgr = PoolManager([
        mkpool("a", tps=rng.choice([300.0, 600.0]),
               slots=rng.choice([2.0, 4.0])),
        mkpool("b", tps=600.0, slots=4.0),
        mkpool("c", tps=1000.0, slots=8.0),
    ])
    classes = [ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
               ServiceClass.SPOT]
    gw = Gateway(mgr, telemetry=True)
    order = ["a", "b", "c"]
    routes = {}
    for k in range(6):
        depth = rng.randint(1, 3)
        legs = []
        for pname in order[:depth]:
            ename = f"e{k}@{pname}"
            mgr.pool(pname).add_entitlement(
                ent(ename, pname, klass=rng.choice(classes),
                    tps=rng.choice([120.0, 400.0]),
                    conc=rng.choice([1.0, 3.0])))
            legs.append((pname, ename))
        gw.register_route(f"k{k}", legs)
        routes[f"k{k}"] = legs
    return gw, routes, rng


def _requests(rng, n, prefix):
    reqs = []
    for i in range(n):
        key = (f"k{rng.randrange(6)}" if rng.random() > 0.1
               else "unknown")
        reqs.append(QuantumRequest(
            api_key=key, request_id=f"{prefix}{i}",
            input_tokens=rng.choice([16, 64]),
            max_tokens=rng.choice([None, 32])))
    return reqs


def _assert_trace_matches(tel, resp, routes, key):
    tr = tel.flight.explain(resp.request_id)
    assert tr is not None, resp.request_id
    assert tr.status == resp.status
    assert tr.reason == resp.reason
    assert tr.pool == resp.pool
    assert tr.spill_hops == resp.spill_hops
    assert tr.priority == pytest.approx(resp.priority, abs=1e-9)
    # leg order: rows walk the DECLARED route positions in order
    hops = [r.leg for r in tr.legs]
    assert hops == sorted(hops)
    for row in tr.legs:
        if row.pool is not None:
            assert routes[key][row.leg][0] == row.pool


class TestExplainParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_quantum_path(self, seed):
        gw, routes, rng = _build_gateway(seed)
        for rep in range(3):
            reqs = _requests(rng, 40, f"q{rep}-")
            resps = gw.handle_quantum(reqs, now=float(rep))
            for q, resp in zip(reqs, resps):
                if q.api_key == "unknown":
                    tr = gw.telemetry.flight.explain(q.request_id)
                    assert tr.status == 401
                    assert tr.reason == "unknown_key"
                else:
                    _assert_trace_matches(gw.telemetry, resp, routes,
                                          q.api_key)

    @pytest.mark.parametrize("seed", [10, 11])
    def test_scalar_path(self, seed):
        gw, routes, rng = _build_gateway(seed)
        for rep in range(2):
            for q in _requests(rng, 30, f"s{rep}-"):
                resp = gw.handle(q.api_key, q.request_id,
                                 q.input_tokens, q.max_tokens,
                                 now=float(rep))
                if q.api_key == "unknown":
                    tr = gw.telemetry.flight.explain(q.request_id)
                    assert tr.status == 401
                else:
                    _assert_trace_matches(gw.telemetry, resp, routes,
                                          q.api_key)

    def test_pool_unavailable_terminal(self):
        pool = mkpool("a")
        gw = Gateway(pool, telemetry=True)
        pool.add_entitlement(ent("e", "a"))
        gw.register_route("k", [("ghost", "e@ghost")])
        # route names only a pool the manager doesn't have → no live
        # leg → POOL_UNAVAILABLE; verify on both paths
        r1 = gw.handle("k", "r1", 8, 8, now=0.0)
        resp = gw.handle_quantum(
            [QuantumRequest("k", "r2", 8, 8),
             QuantumRequest("k", "r3", 8, 8)], now=0.0)
        for r in [r1] + list(resp):
            assert r.status == 429
            assert r.reason == "pool_unavailable"
            tr = gw.telemetry.flight.explain(r.request_id)
            assert tr.status == 429
            assert tr.reason == "pool_unavailable"


class TestNoRetrace:
    def test_telemetry_on_does_not_retrace_admit_quantum(self):
        # fixed batch shape (sizes 5..8 share one pow2 pad bucket);
        # the flight scatter + counter row-ops must stay host-side
        pool = mkpool("p", tps=10_000.0, slots=64.0)
        gw = Gateway(pool, telemetry=True)
        for i in range(3):
            pool.add_entitlement(ent(f"e{i}", "p", conc=16.0))
            gw.register_key(f"k{i}", f"e{i}", pool="p")

        def quantum(n, tag, now):
            return gw.handle_quantum(
                [QuantumRequest(f"k{i % 3}", f"{tag}-{i}", 16, 16)
                 for i in range(n)], now=now)

        quantum(8, "warm", 0.0)                   # warm-up compiles
        before = TRACE_COUNTS["admit_quantum"]
        for step, size in enumerate([5, 8, 6, 7], start=1):
            quantum(size, f"n{step}", float(step))
        assert TRACE_COUNTS["admit_quantum"] == before
        assert len(gw.telemetry.flight) > 0       # telemetry did record


# ---------------------------------------------------------------------------
# StateStore: INCRBY preserves TTL (Redis contract)
# ---------------------------------------------------------------------------

class TestStateStoreIncrTTL:
    def test_incr_preserves_ttl(self):
        s = StateStore()
        s.set("hits", 1.0, now=0.0, ttl_s=10.0)
        assert s.incr("hits", 2.0, now=5.0) == 3.0
        assert s.get("hits", now=9.9) == 3.0
        assert s.get("hits", now=10.0) is None    # TTL still enforced

    def test_incr_on_expired_key_restarts(self):
        s = StateStore()
        s.set("hits", 5.0, now=0.0, ttl_s=1.0)
        assert s.incr("hits", 1.0, now=2.0) == 1.0
        assert s.get("hits", now=100.0) == 1.0    # fresh key: no TTL

    def test_incr_bumps_version(self):
        s = StateStore()
        s.set("k", 1.0, now=0.0)
        _, v1 = s.get_versioned("k")
        s.incr("k", 1.0, now=0.0)
        _, v2 = s.get_versioned("k")
        assert v2 == v1 + 1

    def test_incr_many(self):
        s = StateStore()
        s.set("a", 1.0, now=0.0, ttl_s=50.0)
        s.incr_many({"a": 2.0, "b": 3.0}, now=0.0)
        assert s.get("a", now=49.0) == 3.0
        assert s.get("a", now=50.0) is None
        assert s.get("b", now=1e9) == 3.0


# ---------------------------------------------------------------------------
# stats()-as-view + SLO tracking
# ---------------------------------------------------------------------------

class TestRegistryViews:
    def test_pool_stats_is_registry_view(self):
        pool = mkpool("a")
        pool.add_entitlement(ent("e", "a"))
        gw = Gateway(pool, telemetry=True)
        gw.register_key("k", "e")
        gw.handle_quantum(
            [QuantumRequest("k", f"r{i}", 8, 8) for i in range(4)],
            now=0.0)
        g = gw.telemetry.registry.get("repro_pool_in_flight")
        sid = g.series(("a",))
        assert g.read(sid) == pool.stats()["in_flight"] > 0
        g2 = gw.telemetry.registry.get("repro_pool_unknown_settles")
        assert g2.read(g2.series(("a",))) == 0

    def test_slo_attainment(self):
        tel = Telemetry()
        tr = tel.slo
        lats = np.array([0.1, 0.2, 0.4, 2.0])
        tr.observe_rows(lats, np.full(4, 1, np.int64),
                        np.full(4, 0.5))          # guaranteed, 500 ms
        assert tr.attainment("guaranteed") == pytest.approx(0.75)
        assert tr.attainment("spot") == 1.0       # idle tier
        assert 0.05 < tr.p50("guaranteed") < 0.5
        assert tr.p99("guaranteed") > 0.5
        # scalar oracle agrees
        tel2 = Telemetry()
        for v in lats:
            tel2.slo.observe(float(v), 1, 0.5)
        assert tel2.slo.attainment("guaranteed") == pytest.approx(0.75)
        assert tel2.slo.snapshot() == tr.snapshot()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+inf-]+)$")


class TestExporters:
    def _telemetry_with_traffic(self):
        gw, routes, rng = _build_gateway(5)
        resps = gw.handle_quantum(_requests(rng, 40, "t"), now=0.0)
        gw.on_complete_batch(
            [(r.request_id, 16, 0.05) for r in resps
             if r.status == 200], now=1.0)
        for p in gw.manager.pools.values():
            p.tick(2.0)
        return gw.telemetry

    def test_prometheus_text_parses(self):
        tel = self._telemetry_with_traffic()
        text = tel.prometheus()
        assert text.endswith("\n")
        for line in text.splitlines():
            assert _PROM_LINE.match(line), line

    def test_prometheus_histogram_shape(self):
        tel = self._telemetry_with_traffic()
        text = tel.prometheus()
        # cumulative buckets are monotone and close at +Inf == _count
        buckets = {}
        counts = {}
        for line in text.splitlines():
            m = re.match(
                r'repro_request_latency_seconds_bucket'
                r'\{tier="([^"]+)",le="([^"]+)"\} (\d+)', line)
            if m:
                buckets.setdefault(m.group(1), []).append(
                    int(m.group(3)))
            m = re.match(
                r'repro_request_latency_seconds_count'
                r'\{tier="([^"]+)"\} (\d+)', line)
            if m:
                counts[m.group(1)] = int(m.group(2))
        assert buckets
        for tier, cum in buckets.items():
            assert cum == sorted(cum)
            assert cum[-1] == counts[tier]

    def test_chrome_trace_round_trips(self):
        tel = self._telemetry_with_traffic()
        doc = json.loads(tel.chrome_trace())
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert events
        names = set()
        for ev in events:
            assert {"name", "ph", "pid", "tid"} <= set(ev)
            if ev["ph"] != "M":
                assert ev["ts"] >= 0.0
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0
            names.add(ev["name"])
        assert "pool.tick" in names
        assert "gateway.quantum" in names

    def test_json_snapshot(self):
        tel = self._telemetry_with_traffic()
        snap = tel.snapshot()
        json.dumps(snap)                           # serializable
        assert snap["flight_rows"] > 0
        dec = snap["metrics"]["repro_admission_decisions_total"]
        assert dec["kind"] == "counter"
        assert sum(dec["series"].values()) > 0


# ---------------------------------------------------------------------------
# sanitizer pass: telemetry-hot-path
# ---------------------------------------------------------------------------

def _run_pass(tmp_path, src):
    from repro.analysis import Manifest
    src = textwrap.dedent(src)
    p = tmp_path / "repro" / "core" / "mod.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    report = analyze([str(p)], manifest=Manifest.from_exports([]),
                     rules=["telemetry-hot-path"])
    return report, src


class TestTelemetryHotPathPass:
    VIOLATING = """
    from repro.core.markers import hot_path

    class Gw:
        @hot_path
        def admit(self, batch, now):
            for ent in batch:
                self.store.incr(f"admits:{ent}", 1.0, now)
            self.hist.observe(0, 0.5)

        def cold(self, now):
            self.store.incr("fine-here", 1.0, now)
    """

    CLEAN = """
    from repro.core.markers import hot_path

    class Gw:
        @hot_path
        def admit(self, sids, vals, now):
            self.hist.observe_rows(vals, sids)
            self.count.inc_rows(sids, 1.0)
            self.flight.record_batch(sids, now)
            self.store.incr_many({"admits:a": 2.0}, now)

        def oracle(self, now):
            self.hist.observe(0, 0.5)
            self.store.incr("admits:a", 1.0, now)
    """

    def test_violating(self, tmp_path):
        report, src = _run_pass(tmp_path, self.VIOLATING)
        assert [f.rule for f in report.unwaived] \
            == ["telemetry-hot-path"] * 2
        lines = sorted(f.line for f in report.unwaived)
        exp = sorted([
            next(i for i, ln in enumerate(src.splitlines(), 1)
                 if "store.incr(f" in ln),
            next(i for i, ln in enumerate(src.splitlines(), 1)
                 if "hist.observe(0" in ln)])
        assert lines == exp

    def test_clean(self, tmp_path):
        report, _ = _run_pass(tmp_path, self.CLEAN)
        assert report.unwaived == []

    def test_src_tree_is_clean(self):
        """The shipped tree itself holds the invariant."""
        from pathlib import Path
        from repro.analysis import default_manifest
        repo = Path(__file__).resolve().parent.parent
        files = [str(p) for p in
                 (repo / "src" / "repro").rglob("*.py")]
        report = analyze(files, manifest=default_manifest(),
                         rules=["telemetry-hot-path"])
        assert report.unwaived == []

    def test_flight_columns_in_manifest(self):
        from repro.analysis import default_manifest
        man = default_manifest()
        assert "level_at" in man.f64_columns
        assert "rid_hash" not in man.f64_columns
        stores = {s["store"] for s in man.stores}
        assert "FlightRecorder" in stores
