"""Spans and counters of spill routing, on a two-pool fleet whose keys
each have a home leg on pool ``a`` and a spill leg on pool ``b``:
``gateway.round`` per leg round of a quantum, ``gateway.settle``
per batch of completions with a ``pool.spill_debt`` child where it
completes a spill-served request, and ``repro_spill_admits_total`` /
``repro_spill_debt_moved_total``."""
import numpy as np
import pytest

from repro.core import (
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
)
from repro.gateway import Gateway, QuantumRequest
from repro.telemetry import LEG_SPAN_NAMES, SPAN_NAMES


def spill_fleet():
    """Elastic keys ``hot`` (home bucket drained and in debt, so every
    request spills to ``b`` and its completion moves debt) and ``cold``
    (served at home), each routed a → b."""
    mgr = PoolManager()
    pools = {}
    for name in ("a", "b"):
        pools[name] = mgr.add_pool(PoolSpec(
            name=name, model="m", scaling=ScalingBounds(1, 1),
            per_replica=Resources(1000.0, 1 << 30, 64.0),
            bucket_window_s=1.0))
    gw = Gateway(mgr, telemetry=True)
    for key in ("hot", "cold"):
        for name in ("a", "b"):
            pools[name].add_entitlement(EntitlementSpec(
                name=f"{key}@{name}", tenant_id=key, pool=name,
                qos=QoS(service_class=ServiceClass.ELASTIC,
                        slo_target_ms=1000.0),
                baseline=Resources(100.0, 0.0, 16.0)))
            bucket = pools[name].ledger.ensure(f"{key}@{name}", 1000.0, 0.0)
            bucket.rate_tps, bucket.level = 1000.0, 1e4
        gw.register_route(key, [("a", f"{key}@a"), ("b", f"{key}@b")])
    home = pools["a"].ledger.bucket("hot@a")
    home.level = home.rate_tps = 0.0
    pools["a"].status["hot@a"].debt = 0.5
    return gw, pools


def quantum(gw, keys, tag, now):
    return gw.handle_quantum([QuantumRequest(k, f"{tag}{i}", 64, 64)
                              for i, k in enumerate(keys)], now=now)


def children(rows, sid):
    return [i for i in range(rows["id"].size)
            if rows["parent"][i] == sid and rows["name"][i] != "compile"]


def named(rows, name):
    return [i for i in range(rows["id"].size) if rows["name"][i] == name]


def test_leg_span_names_are_their_own_tuple():
    assert LEG_SPAN_NAMES == ("gateway.round", "gateway.settle",
                              "pool.spill_debt")
    assert not set(LEG_SPAN_NAMES) & set(SPAN_NAMES)


def test_round_nests_under_the_quantum_with_its_index_and_batches():
    gw, _ = spill_fleet()
    resp = quantum(gw, ["hot", "cold", "hot", "cold"], "q", 0.0)
    assert [(r.status, r.pool, r.spill_hops) for r in resp] == [
        (200, "b", 1), (200, "a", 0), (200, "b", 1), (200, "a", 0)]
    r = gw.telemetry.spans.rows()
    (q,) = named(r, "gateway.quantum")
    rounds = named(r, "gateway.round")
    assert [int(r["parent"][i]) for i in rounds] == [int(r["id"][q])] * 2
    assert [int(r["index"][i]) for i in rounds] == [0, 1]
    for k, pool in ((0, "a"), (1, "b")):
        kids = children(r, int(r["id"][rounds[k]]))
        names = [r["name"][i] for i in kids]
        assert names[:2] == ["gateway.snapshot", "gateway.admit"]
        assert "gateway.charge" in names and "gateway.record" in names
        assert {r["pool"][i] for i in kids} == {pool}
        lo, hi = r["start"][rounds[k]], r["end"][rounds[k]]
        assert all(lo <= r["start"][i] <= r["end"][i] <= hi for i in kids)
    # every other span has no index
    others = np.setdiff1d(np.arange(r["id"].size), rounds)
    assert (r["index"][others] == -1).all()


def test_a_quantum_with_no_spill_has_one_round():
    gw, _ = spill_fleet()
    resp = quantum(gw, ["cold"] * 5, "q", 0.0)
    assert {(x.status, x.pool) for x in resp} == {(200, "a")}
    r = gw.telemetry.spans.rows()
    rounds = named(r, "gateway.round")
    assert [int(r["index"][i]) for i in rounds] == [0]
    (q,) = named(r, "gateway.quantum")
    assert int(r["parent"][rounds[0]]) == int(r["id"][q])


def test_settle_spans_every_batch_and_debt_only_for_spill_legs():
    gw, pools = spill_fleet()
    quantum(gw, ["cold", "cold", "hot"], "q", 0.0)
    gw.on_complete_batch([("q0", 16, 0.1), ("q1", 16, 0.1)], 0.5)
    r = gw.telemetry.spans.rows()
    (s,) = named(r, "gateway.settle")
    assert r["parent"][s] == -1 and r["now"][s] == 0.5
    assert named(r, "pool.spill_debt") == []

    debt0 = pools["a"].status["hot@a"].debt
    gw.on_complete_batch([("q2", 16, 0.1)], 0.6)
    assert pools["a"].status["hot@a"].debt < debt0
    r = gw.telemetry.spans.rows()
    s = named(r, "gateway.settle")[-1]
    (d,) = named(r, "pool.spill_debt")
    assert r["parent"][s] == -1 and r["parent"][d] == r["id"][s]
    assert r["root"][d] == r["id"][s] and r["now"][s] == 0.6
    assert r["pool"][d] == "b"
    assert r["start"][s] <= r["start"][d] <= r["end"][d] <= r["end"][s]


def _counter(tel, name):
    fam = tel.registry.get(name)
    return {labels: fam.read(sid)
            for labels, sid in fam._index.items()}


def test_spill_admits_count_the_200s_with_spill_hops():
    gw, _ = spill_fleet()
    resps = quantum(gw, ["hot", "cold", "hot", "hot", "cold"], "q", 0.0)
    resps += quantum(gw, ["hot"], "one", 0.1)   # the scalar path
    resps += quantum(gw, ["cold", "cold"], "c", 0.2)
    spilled = sum(r.status == 200 and r.spill_hops > 0 for r in resps)
    assert spilled == 4
    assert _counter(gw.telemetry, "repro_spill_admits_total") == {
        ("a", "b"): 4.0}


def test_spill_admits_count_a_single_live_leg_past_a_down_home():
    """With ``a`` down, ``cold`` has one live leg, on ``b``, at declared
    position 1: its admits are spill admits, in a quantum as in the
    scalar ``handle``."""
    counts = {}
    for batched in (True, False):
        gw, pools = spill_fleet()
        pools["a"].set_replicas(0)
        if batched:
            resps = quantum(gw, ["cold", "cold"], "q", 0.0)
        else:
            resps = [gw.handle("cold", f"q{i}", 64, 64, 0.0)
                     for i in range(2)]
        assert [(r.status, r.pool, r.spill_hops) for r in resps] == [
            (200, "b", 1)] * 2
        assert gw.store.get("spills:cold") == 2.0
        counts[batched] = _counter(gw.telemetry, "repro_spill_admits_total")
    assert counts[True] == counts[False] == {("b", "b"): 2.0}


def test_spill_debt_moved_sums_what_each_transfer_returned(monkeypatch):
    gw, pools = spill_fleet()
    returned = []
    transfer = gw.manager.transfer_spill_debt

    def spy(rec, serving_pool, now):
        returned.append(transfer(rec, serving_pool, now))
        return returned[-1]

    monkeypatch.setattr(gw.manager, "transfer_spill_debt", spy)
    quantum(gw, ["hot", "cold", "hot", "hot"], "q", 0.0)
    gw.on_complete_batch([("q0", 64, 0.2), ("q1", 64, 0.2)], 0.5)
    gw.on_complete_batch([("q2", 64, 0.2), ("q3", 64, 0.2)], 0.7)
    assert len(returned) == 3 and sum(returned) > 0.0
    moved = _counter(gw.telemetry, "repro_spill_debt_moved_total")
    assert list(moved) == [("a", "b")]
    assert moved[("a", "b")] == pytest.approx(sum(returned), rel=1e-12)
