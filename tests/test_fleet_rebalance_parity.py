"""The fleet planner's rebalance pass over store columns and tick arrays
against the per-entitlement loop it replaced
(``ReferenceFleetPlanner``): the same proposals, field for field, and
the same starvation and cooldown state after every plan — across
outages, pools without a record, entitlements added and removed
between the tick and the plan, debt ties and debts one float32 ulp
either side of the threshold.  Plus the pieces that keep the pass
cheap: ``pool_demand`` keeps the bits of the dict sum, a plan builds
no record dict and reads no status view, and the starved-rows gauge
counts what the oracle counts."""
import numpy as np
import pytest

from repro.core import (
    EntitlementSpec,
    FleetPlanner,
    FleetPlannerConfig,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
    TickRecord,
    TokenPool,
)
from repro.core import resident
from repro.core.fleet import ReferenceFleetPlanner
from repro.gateway import Gateway

CLASSES = list(ServiceClass)
MIGRATABLE = (ServiceClass.ELASTIC, ServiceClass.SPOT)


def mkpool(name, hi=4, per_tps=240.0):
    return TokenPool(PoolSpec(
        name=name, model="m", scaling=ScalingBounds(1, hi),
        per_replica=Resources(per_tps, 0.0, 8.0), default_max_tokens=64,
        bucket_window_s=4.0))


def ent(name, pool, klass, tps=120.0):
    return EntitlementSpec(
        name=name, tenant_id="t", pool=pool,
        qos=QoS(service_class=klass, slo_target_ms=1000.0),
        baseline=Resources(tps, 0.0, 1.0))


def twin_plan(new, ref, pools, records, now):
    """Plan with both planners on the same fleet and records; pin
    everything they return and keep equal.  Returns the new plan."""
    want = ref.plan(pools, records, now)
    got = new.plan(pools, records, now)
    assert got.migrations == want.migrations
    assert got.starved_rows == want.starved_rows
    assert got.decisions == want.decisions
    assert got.unmet_replicas == want.unmet_replicas
    assert new._starved == ref._starved
    assert new._cooldown == ref._cooldown
    assert new._plans == ref._plans
    assert new._state == ref._state
    return got


def apply(mgr, plan, now):
    for prop in plan.migrations:
        if mgr.available(prop.dst):
            mgr.migrate_entitlement(prop.entitlement, prop.src, prop.dst,
                                    now)


def set_debt(pool, name, value):
    slot = pool.store.slot_of[name]
    pool.store.col["debt"][slot] = value
    pool.store.mark_dirty()


# -- fleets under outages, driven by real ticks -------------------------------

def outage_fleet(n_pools, rng):
    """The first half of the pools (at least one) are small and will
    fail; the rest have room."""
    pools = [mkpool(f"p{i}", hi=4 if i < max(1, n_pools // 2) else 64)
             for i in range(n_pools)]
    mgr = PoolManager(pools)
    k = 0
    for p in pools:
        for klass in CLASSES:
            for _ in range(int(rng.integers(1, 4))):
                p.add_entitlement(ent(f"e{k}", p.spec.name, klass,
                                      float(rng.choice([60.0, 120.0,
                                                        240.0]))))
                k += 1
    return mgr, k


@pytest.mark.parametrize("n_pools,seed", [(2, 0), (3, 1), (4, 2), (5, 3),
                                          (6, 4), (3, 5)])
def test_outage_fleet_matches_reference(n_pools, seed):
    """Ticks under outages (``set_replicas``), migrations applied as
    proposed, entitlements churned between tick and plan, some pools'
    records withheld: every plan equal to the oracle's."""
    rng = np.random.default_rng(seed)
    mgr, k = outage_fleet(n_pools, rng)
    cfg = FleetPlannerConfig(debt_migrate_threshold=0.05,
                             starve_persistence_ticks=2,
                             migrate_cooldown_ticks=3,
                             max_migrations_per_pool=2)
    new, ref = FleetPlanner(cfg), ReferenceFleetPlanner(cfg)
    names = sorted(mgr.pools)
    down = set(names[:max(1, n_pools // 2)])
    moved = starved = 0
    for t in range(1, 25):
        now = float(t)
        if t == 18:
            down = set()                              # recovery
        for name, pool in mgr.pools.items():
            pool.set_replicas(1 if name in down else 8)
            # the failed pools' tenants want more than their pool's
            # maxReplicas; the others' leave it slack
            want = [0.0, 400.0, 2000.0] if name in down else [0.0, 10.0]
            for e in list(pool.entitlements):
                pool.register_deny(e, float(rng.choice(want)),
                                   low_priority=True)
        records = mgr.tick(now)
        if t % 4 == 0:                                # churn after the tick
            pool = mgr.pools[names[t % n_pools]]
            gone = [e for e, s in pool.entitlements.items()
                    if s.qos.service_class in MIGRATABLE]
            if gone:
                pool.remove_entitlement(gone[0], now)
            pool.add_entitlement(ent(f"e{k}", pool.spec.name,
                                     ServiceClass.ELASTIC))
            set_debt(pool, f"e{k}", 0.5)
            k += 1
        if t % 5 == 0:                                # a pool unrecorded
            records = dict(records)
            records.pop(names[t % n_pools])
        plan = twin_plan(new, ref, mgr.pools, records, now)
        starved += sum(plan.starved_rows.values())
        moved += len(plan.migrations)
        apply(mgr, plan, now)
    assert starved > 0 and moved > 0


# -- debt edges ----------------------------------------------------------------

@pytest.mark.parametrize("threshold", [0.25, 0.3, 0.7])
def test_debt_ties_and_threshold_ulps(threshold):
    """Debts at float32(threshold) and one ulp either side: the flag is
    float64's ``float(debt) >= threshold``, not float32's.  Equal debts
    order by name.  The oracle agrees on every plan."""
    cfg = FleetPlannerConfig(debt_migrate_threshold=threshold,
                             starve_persistence_ticks=1,
                             migrate_cooldown_ticks=2,
                             max_migrations_per_pool=3)
    src, dst = mkpool("a", hi=1), mkpool("b", hi=64)
    src.add_entitlement(ent("g", "a", ServiceClass.GUARANTEED, 480.0))
    at = np.float32(threshold)
    edges = {"lo": np.nextafter(at, np.float32(0)), "at": at,
             "hi": np.nextafter(at, np.float32(1))}
    for tag in ("z", "y", "x", "w"):                   # ties, by name
        edges[f"tie_{tag}"] = np.float32(0.9)
    for name in edges:
        src.add_entitlement(ent(name, "a", ServiceClass.ELASTIC))
    src.add_entitlement(ent("spot", "a", ServiceClass.SPOT))
    pools = {"a": src, "b": dst}
    new, ref = FleetPlanner(cfg), ReferenceFleetPlanner(cfg)
    order = []
    for t in range(1, 6):
        for name, value in edges.items():
            if name in src.entitlements:
                set_debt(src, name, value)
        set_debt(src, "spot", 5.0)                    # spot: never "debt"
        plan = twin_plan(new, ref, pools, {}, float(t))
        order += [p.entitlement for p in plan.migrations]
        for prop in plan.migrations:
            dst.attach_entitlement(src.detach_entitlement(prop.entitlement))
    # float32(0.7) lies below 0.7: a float32 comparison would flag it
    at_flagged = {0.25: True, 0.3: True, 0.7: False}[threshold]
    assert order[:4] == ["tie_w", "tie_x", "tie_y", "tie_z"]
    assert set(order[4:]) == ({"hi", "at"} if at_flagged else {"hi"})


def test_cooldown_pins_a_migrated_entitlement():
    """An indebted elastic tenant moves off whichever pool is scarce;
    when scarcity flips to its new pool it stays pinned there for
    ``migrate_cooldown_ticks`` plans before it may move back."""
    cfg = FleetPlannerConfig(demand_ewma=0.0, debt_migrate_threshold=0.25,
                             starve_persistence_ticks=1,
                             migrate_cooldown_ticks=3)
    pools = {"a": mkpool("a", hi=64), "b": mkpool("b", hi=64)}
    pools["a"].add_entitlement(ent("el", "a", ServiceClass.ELASTIC))
    new, ref = FleetPlanner(cfg), ReferenceFleetPlanner(cfg)
    moves = []
    for t in range(1, 9):
        home = next(n for n, p in pools.items() if "el" in p.entitlements)
        set_debt(pools[home], "el", 1.0)
        # the pool holding the tenant is the scarce one
        records = {n: TickRecord(float(t), 0.0, demand_tps={
            "load": 1e6 if n == home else 0.0}) for n in pools}
        plan = twin_plan(new, ref, pools, records, float(t))
        for prop in plan.migrations:
            moves.append((t, prop.src, prop.dst))
            pools[prop.dst].attach_entitlement(
                pools[prop.src].detach_entitlement(prop.entitlement))
    assert moves == [(1, "a", "b"), (4, "b", "a"), (7, "a", "b")]


# -- a randomized sweep over records of every kind -----------------------------

def synthetic_record(pool, rng, kind, t):
    """A record over ``pool``: ``fast`` carries the store's own live-name
    list; ``slow`` a reordered copy with a ghost row and a row missing;
    ``dict`` is built from dicts."""
    names = pool.store.live_names()
    if kind == "slow":
        names = [n for n in rng.permutation(names).tolist()][1:] + ["ghost"]
    n = len(names)
    demand = rng.choice([0.0, 1e-10, 3.0, 480.0, 1e6], n)
    alloc = demand * rng.choice([0.0, 0.25, 0.5, 0.75, 1.0], n)
    if kind == "dict":
        return TickRecord(t, 0.0,
                          allocations=dict(zip(names, alloc.tolist())),
                          demand_tps=dict(zip(names, demand.tolist())))
    z = np.zeros(n)
    return TickRecord.from_arrays(t, 0.0, names, alloc, z, z, z,
                                  np.zeros(n, np.int64), demand)


def run_sweep(seed, n_pools, rounds):
    rng = np.random.default_rng(seed)
    thr = float(rng.choice([0.25, 0.3, 0.7]))
    cfg = FleetPlannerConfig(
        debt_migrate_threshold=thr,
        starve_persistence_ticks=int(rng.integers(1, 4)),
        migrate_cooldown_ticks=int(rng.integers(1, 5)),
        max_migrations_per_pool=int(rng.integers(1, 4)))
    pools = {f"p{i}": mkpool(f"p{i}", hi=int(rng.choice([1, 2, 64])))
             for i in range(n_pools)}
    debts = np.array([0.0, 0.1, 0.9, 0.9, 2.0, np.float32(thr),
                      np.nextafter(np.float32(thr), np.float32(0)),
                      np.nextafter(np.float32(thr), np.float32(1))],
                     np.float32)
    k = 0
    for name, pool in pools.items():
        for _ in range(int(rng.integers(1, 12))):
            pool.add_entitlement(ent(f"e{k}", name,
                                     CLASSES[rng.integers(len(CLASSES))]))
            k += 1
    new, ref = FleetPlanner(cfg), ReferenceFleetPlanner(cfg)
    for t in range(1, rounds + 1):
        for name, pool in pools.items():
            for e in pool.store.live_names():
                set_debt(pool, e, rng.choice(debts))
        records = {}
        for name, pool in pools.items():
            kind = rng.choice(["fast", "fast", "slow", "dict", "none"])
            if kind != "none":
                records[name] = synthetic_record(pool, rng, kind, float(t))
        if rng.random() < 0.3:                        # churn after records
            pool = pools[rng.choice(sorted(pools))]
            live = pool.store.live_names()
            if live:
                pool.remove_entitlement(live[int(rng.integers(len(live)))])
            pool.add_entitlement(ent(f"e{k}", pool.spec.name,
                                     ServiceClass.ELASTIC))
            set_debt(pool, f"e{k}", rng.choice(debts))
            k += 1
        plan = twin_plan(new, ref, pools, records, float(t))
        for prop in plan.migrations:
            pools[prop.dst].attach_entitlement(
                pools[prop.src].detach_entitlement(prop.entitlement))


def test_hypothesis_sweep():
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(0, 2**32 - 1), n_pools=st.integers(2, 6),
           rounds=st.integers(1, 8))
    @settings(max_examples=40, deadline=None, derandomize=True)
    def sweep(seed, n_pools, rounds):
        run_sweep(seed, n_pools, rounds)

    sweep()


# -- pool_demand keeps the bits of the dict sum --------------------------------

def test_pool_demand_bits_match_dict_sum():
    rng = np.random.default_rng(7)
    n = 1 << 13
    demand = rng.choice([1e-6, 1.0, 3e3, 1e9], n) * rng.random(n)
    names = [f"e{i}" for i in range(n)]
    z = np.zeros(n)
    rec = TickRecord.from_arrays(0.0, 0.0, names, z, z, z, z,
                                 np.zeros(n, np.int64), demand)
    got = FleetPlanner.pool_demand(None, rec)
    assert rec._cache == {}                     # no dict on the way
    want = float(sum(rec.demand_tps.values()))
    assert got.hex() == want.hex()
    assert float(np.sum(demand)).hex() != want.hex()   # the fixture bites
    assert ReferenceFleetPlanner.pool_demand(None, rec).hex() == want.hex()


# -- no per-row Python on a pool's plan ------------------------------------------

def big_pool():
    mgr = PoolManager([mkpool("big", hi=8), mkpool("spare", hi=64)])
    pool = mgr.pools["big"]
    for i in range(1 << 12):
        pool.add_entitlement(ent(f"e{i}", "big", CLASSES[i % len(CLASSES)],
                                 1.0))
    for i in range(0, 1 << 12, 97):                   # a few starved rows
        pool.register_deny(f"e{i}", 1e5, low_priority=True)
    records = mgr.tick(1.0)
    return mgr, records


class _CountingStatus(dict):
    reads = 0

    def __getitem__(self, key):
        type(self).reads += 1
        return super().__getitem__(key)


@pytest.mark.parametrize("planner", [FleetPlanner, ReferenceFleetPlanner])
def test_plan_builds_no_record_dict_nor_status_view(planner, monkeypatch):
    """The array pass leaves the record's dict cache empty and touches
    no ``ResidentStatus``; the per-name loop (the oracle) trips both, so
    the guard fails if that loop comes back."""
    mgr, records = big_pool()
    pool = mgr.pools["big"]
    built = []
    init = resident.ResidentStatus.__init__

    def counting_init(self, store, slot):
        built.append(slot)
        init(self, store, slot)

    monkeypatch.setattr(resident.ResidentStatus, "__init__", counting_init)
    _CountingStatus.reads = 0
    pool.status = _CountingStatus(pool.status)
    plan = planner(FleetPlannerConfig()).plan(mgr.pools, records, 1.0)
    rows = sum(plan.starved_rows.values())
    touched = len(built) + _CountingStatus.reads
    if planner is FleetPlanner:
        assert rows > 0
        assert records["big"]._cache == {}
        assert touched == 0
    else:
        assert records["big"]._cache != {} and touched > 1000


# -- the starved-rows gauge --------------------------------------------------------

def oracle_counts(mgr, records, cfg):
    ref = ReferenceFleetPlanner(cfg)
    counts = {}
    for pname, pool in mgr.pools.items():
        for reason in ("debt", "starved_demand"):
            counts[(pname, reason)] = 0
        for e, espec in pool.entitlements.items():
            if espec.qos.service_class in MIGRATABLE:
                why = ref._starvation(pool, e, records.get(pname))
                if why is not None:
                    counts[(pname, why)] += 1
    return counts


@pytest.mark.parametrize("scarce", [True, False])
def test_starved_rows_gauge_counts_the_oracles_rows(scarce):
    cfg = FleetPlannerConfig(debt_migrate_threshold=0.05)
    mgr = PoolManager([mkpool("a", hi=2), mkpool("b", hi=8)])
    a = mgr.pools["a"]
    for i, klass in enumerate(CLASSES * 3):
        a.add_entitlement(ent(f"e{i}", "a", klass, 120.0))
    mgr.planner = FleetPlanner(cfg)
    mgr.provision_hook = lambda *args: None
    gw = Gateway(mgr, telemetry=True)
    tel = gw.telemetry
    a.set_replicas(1 if scarce else 8)
    for t in range(1, 5):
        for e in list(a.entitlements):
            a.register_deny(e, 4000.0 if scarce else 10.0,
                            low_priority=True)
        records = mgr.tick(float(t))
        want = oracle_counts(mgr, records, cfg)
        gw.plan_quantum(float(t), records=records)
        for (pname, reason), n in want.items():
            sid = tel.plan_starved.series((pname, reason))
            assert tel.plan_starved.read(sid) == float(n), (t, pname,
                                                            reason)
    starved = sum(want.values())
    assert (starved > 0) if scarce else (starved == 0)
    assert "repro_plan_starved_rows" in tel.prometheus()
