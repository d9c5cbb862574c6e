"""Program spans (``repro.telemetry.spans``): nesting, parent and root
ids and the bounded ring; the spans of a quantum, a tick and a plan;
compiles and transfers charged to the span that caused them; one
process-wide compile listener; nothing recorded without a
``Telemetry``; the span families that replaced the per-call duration
histograms, and the Chrome timeline drawn from the same table."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import (
    ControlState,
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
    TokenPool,
)
from repro.core.resident import ShardedResidentStore
from repro.gateway import Gateway, QuantumRequest
from repro.telemetry import SPAN_NAMES, SpanTable, Telemetry
from repro.telemetry import spans as sp


def mkpool(name):
    return TokenPool(PoolSpec(
        name=name, model="m", scaling=ScalingBounds(1, 1),
        per_replica=Resources(1000.0, float(1 << 30), 4.0),
        default_max_tokens=64, bucket_window_s=1.0))


def mkgateway(telemetry=True, pools=("p",)):
    mgr = PoolManager([mkpool(name) for name in pools])
    gw = Gateway(mgr, telemetry=telemetry)
    for name in pools:
        for i in range(4):
            ent = f"e{i}@{name}"
            mgr.pool(name).add_entitlement(EntitlementSpec(
                name=ent, tenant_id="t", pool=name,
                qos=QoS(service_class=ServiceClass.GUARANTEED,
                        slo_target_ms=500.0),
                baseline=Resources(300.0, 0.0, 2.0)))
            gw.register_key(f"k{i}@{name}", ent, pool=name)
    return gw


def quantum(gw, n, tag, now, pool="p"):
    return gw.handle_quantum(
        [QuantumRequest(f"k{i % 4}@{pool}", f"{tag}{i}", 64, 64)
         for i in range(n)], now=now)


def children(rows, sid, with_compiles=False):
    return [n for n, p in zip(rows["name"], rows["parent"])
            if p == sid and (with_compiles or n != "compile")]


def only(rows, name):
    (i,) = np.flatnonzero(rows["name"] == name)
    return i


class TestSpanTable:
    def test_nesting_parent_and_root_ids(self):
        tel = Telemetry()
        with sp.span(tel, "gateway.quantum", now=3.0):
            with sp.child("gateway.route"):
                pass
            with sp.child("gateway.snapshot", "p"):
                with sp.child("gateway.admit"):
                    pass
        with sp.span(tel, "pool.tick", pool="q", now=4.0):
            with sp.child("pool.kernel"):
                pass
        assert sp._OPEN == []
        r = tel.spans.rows()
        assert list(r["name"]) == [
            "gateway.quantum", "gateway.route", "gateway.snapshot",
            "gateway.admit", "pool.tick", "pool.kernel"]
        assert list(r["parent"]) == [-1, 0, 0, 2, -1, 4]
        assert list(r["root"]) == [0, 0, 0, 0, 4, 4]
        assert list(r["pool"]) == ["", "", "p", "p", "q", "q"]
        assert r["now"][0] == 3.0 and r["now"][4] == 4.0
        assert np.isnan(r["now"][1])
        # every child lies inside its parent, on the one clock
        for i in np.flatnonzero(r["parent"] >= 0):
            p = int(r["parent"][i])
            assert r["start"][p] <= r["start"][i] <= r["end"][i] \
                <= r["end"][p]

    def test_ring_keeps_the_newest_and_folds_every_root(self):
        folded = []
        table = SpanTable(8, on_root=folded.append)
        for k in range(20):
            with table.open("fleet.plan", now=float(k)):
                with sp.child("fleet.kernel"):
                    pass
        r = table.rows()
        assert list(r["id"]) == list(range(32, 40))
        assert list(r["name"]) == ["fleet.plan", "fleet.kernel"] * 4
        assert list(r["now"][::2]) == [float(k) for k in range(16, 20)]
        assert folded == list(range(0, 40, 2))

    def test_capacity_is_a_power_of_two(self):
        with pytest.raises(ValueError):
            SpanTable(12, on_root=print)

    def test_a_raising_call_closes_its_spans(self):
        tel = Telemetry()
        with pytest.raises(RuntimeError):
            with sp.span(tel, "gateway.quantum"):
                with sp.child("gateway.route"):
                    raise RuntimeError("boom")
        assert sp._OPEN == []
        r = tel.spans.rows()
        assert list(r["name"]) == ["gateway.quantum", "gateway.route"]

    def test_names_are_one_fixed_tuple(self):
        assert len(set(SPAN_NAMES)) == len(SPAN_NAMES) == 15
        tel = Telemetry()
        with pytest.raises(KeyError):
            sp.span(tel, "gateway.something_else")


class TestProgramSpans:
    def test_quantum_tick_and_plan(self):
        gw = mkgateway()
        resp = quantum(gw, 12, "a", 0.0)
        assert {r.status for r in resp} == {200, 429}
        records = gw.manager.tick(1.0)
        gw.plan_quantum(1.0, records=records)
        r = gw.telemetry.spans.rows()
        roots = np.flatnonzero(r["parent"] < 0)
        assert [r["name"][i] for i in roots] == [
            "gateway.quantum", "pool.tick", "fleet.plan"]
        q, t, p = (int(r["id"][i]) for i in roots)
        assert children(r, q) == [
            "gateway.route", "gateway.snapshot", "gateway.admit",
            "gateway.charge", "gateway.deny", "gateway.record"]
        assert children(r, t) == ["pool.measure", "pool.kernel",
                                  "pool.absorb"]
        assert children(r, p) == ["fleet.kernel", "fleet.rebalance"]
        # each span carries the id of its root call
        for i in range(r["id"].size):
            root = int(r["root"][i])
            assert root in (q, t, p)
            assert r["start"][root] <= r["start"][i] <= r["end"][root]
        assert r["now"][only(r, "pool.tick")] == 1.0
        assert r["pool"][only(r, "gateway.snapshot")] == "p"
        assert r["pool"][only(r, "pool.kernel")] == "p"

    def test_generic_path_and_group_tick_share_the_names(self):
        gw = mkgateway(pools=("a", "b"))
        # k2's home entitlement is not bound on a, so it spills to b:
        # the quantum runs in leg rounds
        gw.register_route("k2@a", [("a", "e2@a"), ("b", "e0@b")])
        quantum(gw, 8, "x", 0.0, pool="a")
        gw.manager.tick(1.0)                  # one group of two pools
        r = gw.telemetry.spans.rows()
        q = int(r["id"][only(r, "gateway.quantum")])
        # round 0 on a answers k3's exhausted route in its own deny
        # span; round 1 admits k2's spill on b
        assert children(r, q) == [
            "gateway.route", "gateway.round", "gateway.round"]
        rnd = np.flatnonzero(r["name"] == "gateway.round")
        assert list(r["index"][rnd]) == [0, 1]
        assert children(r, int(r["id"][rnd[0]])) == [
            "gateway.snapshot", "gateway.admit", "gateway.charge",
            "gateway.deny", "gateway.record"]
        assert children(r, int(r["id"][rnd[1]])) == [
            "gateway.snapshot", "gateway.admit", "gateway.charge",
            "gateway.record"]
        tick = only(r, "pool.tick")
        assert r["pool"][tick] == ""
        assert children(r, int(r["id"][tick])) == [
            "pool.measure", "pool.kernel", "pool.absorb"]

    def test_a_recompile_is_a_child_of_the_span_that_caused_it(self):
        tel = Telemetry()
        with sp.span(tel, "pool.tick"):
            with sp.child("pool.kernel") as kernel:
                jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(7, np.float32))
        r = tel.spans.rows()
        comp = np.flatnonzero(r["name"] == "compile")
        assert comp.size >= 1
        k = only(r, "pool.kernel")
        for i in comp:
            assert r["parent"][i] == kernel.sid
            assert r["root"][i] == r["root"][k]
            assert r["start"][k] <= r["start"][i] < r["end"][i] \
                <= r["end"][k]
            assert r["cache_hit"][i] in (0, 1)
        assert (r["cache_hit"][r["name"] != "compile"] == -1).all()

    def test_the_quantums_compiles_land_in_its_snapshot_and_admit(self):
        gw = mkgateway()
        quantum(gw, 6, "w", 0.0)
        jax.clear_caches()                    # force every program anew
        quantum(gw, 6, "c", 0.5)
        r = gw.telemetry.spans.rows()
        last = int(r["id"][np.flatnonzero(
            r["name"] == "gateway.quantum")[-1]])
        inside = r["root"] == last
        parents = {r["name"][int(p) - int(r["id"][0])]
                   for p in r["parent"][inside & (r["name"] == "compile")]}
        assert {"gateway.snapshot", "gateway.admit"} <= parents

    def test_transfer_bytes_match_the_arrays(self):
        tel = Telemetry()
        pool = mkpool("p")
        a = np.arange(64, dtype=np.float32)
        with sp.span(tel, "pool.tick"):
            x = pool.store.put_rows(a)
            b = sp.readback(x * 2.0)
        r = tel.spans.rows()
        assert r["h2d"][0] == a.nbytes
        assert r["d2h"][0] == b.nbytes == a.nbytes
        c = tel.transfer_bytes
        assert c.read(c.series(("h2d", "pool.tick"))) == a.nbytes
        assert c.read(c.series(("d2h", "pool.tick"))) == a.nbytes

    def test_tick_transfers_by_phase(self):
        gw = mkgateway()
        pool = gw.manager.pool("p")
        cap = pool.store.capacity
        mirror = sum(pool.store.col[f.name].nbytes
                     for f in dataclasses.fields(ControlState))
        pool.tick(1.0)                        # mirror dirty: re-upload
        pool.tick(2.0)                        # kernel output adopted
        r = gw.telemetry.spans.rows()
        meas = np.flatnonzero(r["name"] == "pool.measure")
        kern = np.flatnonzero(r["name"] == "pool.kernel")
        absorb = np.flatnonzero(r["name"] == "pool.absorb")
        assert list(r["h2d"][meas]) == [4 * cap * 4] * 2
        assert list(r["h2d"][kern]) == [mirror, 0]
        assert list(r["d2h"][kern]) == [2 * cap * 4] * 2
        assert list(r["d2h"][absorb]) == [2 * cap * 4] * 2

    def test_sharded_block_upload_is_counted(self):
        tel = Telemetry()
        store = ShardedResidentStore(capacity=64, n_shards=4)
        for i in range(40):
            store.allocate(f"e{i}")
        store.device_state()
        store.view("e10").burst = 3.0
        with sp.span(tel, "pool.tick"):
            store.device_state()
        row = sum(store.col[f.name].itemsize
                  for f in dataclasses.fields(ControlState))
        assert tel.spans.rows()["h2d"][0] == store.shard_rows * row

    def test_without_telemetry_nothing_records(self):
        other = Telemetry()
        gw = mkgateway(telemetry=None)
        quantum(gw, 12, "n", 0.0)
        records = gw.manager.tick(1.0)
        gw.plan_quantum(1.0, records=records)
        assert other.spans.next_id == 0
        assert sp._OPEN == []

    def test_one_compile_listener_per_process(self):
        from jax._src import monitoring
        for _ in range(3):
            Telemetry()
        assert monitoring._event_duration_secs_listeners.count(
            sp._on_duration) == 1
        assert monitoring._event_listeners.count(sp._on_event) == 1


class TestExports:
    def test_span_families_replace_the_call_timers(self):
        gw = mkgateway()
        quantum(gw, 12, "a", 0.0)
        gw.manager.tick(1.0)
        tel = gw.telemetry
        reg = tel.registry
        assert reg.get("repro_gateway_quantum_duration_seconds") is None
        assert reg.get("repro_pool_tick_duration_seconds") is None
        assert not hasattr(tel, "clock")
        h = reg.get("repro_span_duration_seconds")
        r = tel.spans.rows()
        q = only(r, "gateway.quantum")
        sid = h.series(("gateway.quantum", ""))
        assert h.totals[sid] == 1
        assert h.sums[sid] == pytest.approx(r["end"][q] - r["start"][q])
        assert h.totals[h.series(("pool.kernel", "p"))] == 1
        text = tel.prometheus()
        assert 'repro_transfer_bytes_total{direction="h2d",' \
               'span="gateway.admit"}' in text
        assert 'repro_transfer_bytes_total{direction="d2h",' \
               'span="pool.kernel"}' in text

    def test_chrome_trace_is_drawn_from_the_spans(self):
        gw = mkgateway()
        quantum(gw, 12, "a", 7.5)
        tel = gw.telemetry
        doc = json.loads(tel.chrome_trace())
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        r = tel.spans.rows()
        assert [e["name"] for e in slices] == list(r["name"])
        for e, start, end in zip(slices, r["start"], r["end"]):
            assert e["ts"] == pytest.approx((start - tel.spans.t0) * 1e6)
            assert e["dur"] == pytest.approx((end - start) * 1e6)
        root = slices[0]
        assert root["name"] == "gateway.quantum"
        assert root["args"]["now"] == 7.5
        assert all("now" not in e["args"] for e in slices[1:])
