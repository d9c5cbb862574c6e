"""The owner-min seed of ``admit_quantum``: ``owner_min`` over a
store-width owner mask equals, bit for bit, the min of the Eq. 1
weights gathered at ``inflight_owner_slots()``, and the scalar
``running_min_live`` oracle within float32 — for empty, single, full,
churned and grown owner sets, on a flat and a sharded store.  It
compiles once per store capacity: no snapshot after the first
retraces it or opens a ``compile`` span, whatever the owner count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import assert_no_retrace
from repro.core import (
    EntitlementSpec,
    PoolManager,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
    TokenPool,
)
from repro.core.control_plane import TRACE_COUNTS
from repro.core.pool import InFlight
from repro.core.resident import ShardedResidentStore
from repro.core.vectorized import owner_min, quantum_snapshot, running_min_live
from repro.gateway import Gateway, QuantumRequest

CLASSES = (ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
           ServiceClass.SPOT, ServiceClass.PREEMPTIBLE)


def mkpool(n_ents, shards=None):
    pool = TokenPool(PoolSpec(
        name="p", model="m", scaling=ScalingBounds(1, 1),
        per_replica=Resources(1e6, float(1 << 40), 1e6),
        default_max_tokens=64, bucket_window_s=1.0, shards=shards))
    for i in range(n_ents):
        add(pool, i)
    return pool


def add(pool, i):
    pool.add_entitlement(EntitlementSpec(
        name=f"e{i}", tenant_id=f"t{i}", pool="p",
        qos=QoS(service_class=CLASSES[i % 4],
                slo_target_ms=100.0 + 37.0 * i),
        baseline=Resources(500.0 + i, 0.0, 4.0)))


def give_records(pool, names, tag="r"):
    for name in names:
        pool.register_admit(InFlight(f"{tag}-{name}", name, 0.0, 0.0, 64,
                                     0.0), 64.0)


def gather_min(pool, weights, eager=True):
    """The eager seed this program replaced: ``jnp.min`` over the
    weights gathered at the distinct in-flight owner rows (``eager``
    False: the same min in numpy, which compiles nothing)."""
    rows = pool.inflight_owner_slots()
    if not rows.size:
        return float("inf")
    if not eager:
        return float(np.asarray(weights)[rows].min())
    return float(np.asarray(jnp.min(weights[jnp.asarray(rows, jnp.int32)])))


def check(pool, oracle=True, eager=True):
    snap = quantum_snapshot(pool, 0.0)
    seed = snap.running_min_priority
    assert np.float32(seed) == np.float32(
        gather_min(pool, snap.weights, eager))
    if oracle:
        live = running_min_live(pool)
        if np.isinf(live):
            assert np.isinf(seed) and seed > 0
        else:
            assert seed == pytest.approx(live, rel=2e-7)
    return seed


@pytest.fixture(params=[None, 4], ids=["flat", "sharded"])
def shards(request):
    return request.param


class TestParity:
    def test_empty_owner_set_is_inf(self, shards):
        pool = mkpool(12, shards)
        assert check(pool) == float("inf")

    def test_one_owner(self, shards):
        pool = mkpool(12, shards)
        give_records(pool, ["e5"])
        assert check(pool) == np.float32(pool.priority("e5"))

    def test_every_row(self, shards):
        pool = mkpool(16, shards)
        assert pool.store.capacity == 16       # every row of the store
        give_records(pool, sorted(pool.entitlements))
        check(pool)

    @pytest.mark.parametrize("seed", range(6))
    def test_random_owner_sets(self, shards, seed):
        rng = np.random.default_rng(seed)
        pool = mkpool(40, shards)
        for step in range(4):
            k = int(rng.integers(0, 41))
            names = [f"e{i}" for i in rng.choice(40, k, replace=False)]
            give_records(pool, names, tag=f"s{step}")
            check(pool)

    def test_owners_on_freed_and_recycled_rows(self, shards):
        pool = mkpool(12, shards)
        give_records(pool, ["e1", "e3", "e7"])
        slot = pool.store.slot_of["e3"]
        pool.remove_entitlement("e3")          # its records are evicted
        check(pool)
        add(pool, 40)                          # recycles a freed row
        give_records(pool, ["e40"])
        check(pool)
        # a record left on a freed row: the mask reads the row set the
        # gather reads (the scalar oracle goes by name, so not it)
        pool.remove_entitlement("e40")
        assert slot not in pool.store.slot_of.values()
        live = np.flatnonzero(pool.table.col["has_record"])
        pool.table.col["owner"][live[0]] = slot
        check(pool, oracle=False)

    def test_store_growth_across_a_doubling(self, shards):
        pool = mkpool(8, shards)
        give_records(pool, ["e2", "e6"])
        before = pool.store.capacity
        check(pool)
        for i in range(8, 20):
            add(pool, i)
        assert pool.store.capacity == 4 * before
        give_records(pool, ["e17", "e11"], tag="g")
        traced = TRACE_COUNTS["owner_min"]
        check(pool)
        assert TRACE_COUNTS["owner_min"] - traced <= 1   # one new width
        with assert_no_retrace("owner_min"):
            check(pool)

    def test_sharded_store_places_the_mask_with_the_rows(self):
        pool = mkpool(12, shards=4)
        assert isinstance(pool.store, ShardedResidentStore)
        give_records(pool, ["e0", "e9"])
        check(pool)
        mask = pool.store.put_rows(np.zeros(pool.store.capacity, bool))
        assert mask.sharding == pool.store.device_state().bound.sharding
        if len(jax.devices()) > 1:
            assert len(mask.sharding.device_set) > 1


class TestCompilesOnce:
    def test_owner_counts_1_to_64_do_not_retrace(self):
        pool = mkpool(64)
        assert pool.store.capacity == 64
        quantum_snapshot(pool, 0.0)            # warm: compiles once
        with assert_no_retrace("owner_min"):
            for i in range(64):
                give_records(pool, [f"e{i}"])
                assert pool.inflight_owner_slots().size == i + 1
                check(pool, oracle=False, eager=False)

    def test_no_compile_span_under_the_snapshot_after_the_first(self):
        jax.clear_caches()        # nothing compiled by earlier tests
        mgr = PoolManager([mkpool(64)])
        gw = Gateway(mgr, telemetry=True)
        for i in range(64):
            gw.register_key(f"k{i}", f"e{i}", pool="p")
        for n in range(1, 66):             # owner counts 0..64, 1 new each
            key = f"k{(n - 1) % 64}"           # two requests: one is scalar
            gw.handle_quantum([QuantumRequest(key, f"q{n}.{j}", 32, 32)
                               for j in range(2)], now=0.01 * n)
        assert mgr.pool("p").inflight_owner_slots().size == 64
        r = gw.telemetry.spans.rows()
        ids = [int(i) for i in r["id"]]
        snaps = [i for i, name in zip(ids, r["name"])
                 if name == "gateway.snapshot"]
        assert len(snaps) == 65
        late = [p for name, p in zip(r["name"], r["parent"])
                if name == "compile" and int(p) in snaps[1:]]
        assert late == []


def test_owner_min_is_bitwise_the_gather_min():
    rng = np.random.default_rng(7)
    w = rng.lognormal(0.0, 3.0, 256).astype(np.float32)
    for k in (0, 1, 2, 17, 255, 256):
        rows = np.sort(rng.choice(256, k, replace=False))
        mask = np.zeros(256, bool)
        mask[rows] = True
        got = np.asarray(owner_min(jnp.asarray(w), jnp.asarray(mask)))
        want = w[rows].min() if k else np.float32(np.inf)
        assert got.dtype == np.float32
        assert got.tobytes() == np.float32(want).tobytes()
