#!/usr/bin/env python3
"""Bring-up smoke run of the token-pool control plane on a TPU.

    python chip_smoke.py [--seed N]     # one chip: the served path
    python chip_smoke.py --chips 4      # four chips: the sharded plane

One chip.  A three-pool fleet is built through the public ``repro.core``
/ ``repro.gateway`` surfaces: ``main`` holds 2^17 entitlements
(guaranteed, elastic and spot, one key each, single-leg routes: one
round a quantum); ``east`` and ``west`` hold a few thousand keys
whose routes spill ``east`` → ``west`` (leg rounds).
Quanta of 8192 requests, Zipf-skewed over the keys with long-tailed
input/output lengths drawn from ``--seed``, go through
``Gateway.handle_quantum``; admitted requests are dispatched and settled
through ``on_complete_batch``, and ``PoolManager.tick`` +
``Gateway.plan_quantum`` run between quanta.  ``main`` carries its own
priority coefficients, so it ticks alone on ``control_tick`` while the
spill pools tick together on ``control_tick_pools``.  The results are
held to the system's own oracles, on the chip:

* one quantum of each route shape replayed through the scalar
  ``Gateway.handle`` on an identically built and driven twin gives the
  same status / reason / pool per request;
* one ``main`` tick matches ``reference_tick`` within the tolerances of
  ``tests/test_control_plane.py``;
* every library chaos scenario passes every invariant checker and
  replays decision-identically (``repro.chaos.run_replay``).

Four chips (``--chips 4``).  Only the sharded plane and what it is
compared with: a ``PoolSpec(shards=4)`` pool of 2^20 entitlements and a
flat twin take the same ticks (``shard_tick`` vs ``control_tick``) and
quanta (``shard_admit_quantum`` vs ``admit_quantum``), and must agree
bit for bit in decisions, burst, debt and allocations; each row block
of the sharded store must sit on the chip that owns it; and
``shard_plan_fleet`` must equal ``plan_fleet`` over a 512-pool fleet.

Each phase prints one JSON line (wall seconds end at
``block_until_ready``; compile seconds per kernel and whether each came
from the persistent cache).  These are bring-up diagnostics, not
benchmark metrics.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase exits non-zero before it.  There is no CPU fallback:
without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: one full-size scheduling quantum
QUANTUM = 8192
#: per-token KV bytes of the served model: 32 layers × 8 KV heads × 128
#: head dim × bf16 (an 8B-class GQA model)
KV_PER_TOKEN = 2.0 * 32 * 8 * 128 * 2
#: kernels the one-chip served path must have compiled and run
ONE_CHIP_KERNELS = ("admit_quantum", "control_tick", "control_tick_pools",
                    "plan_fleet")
FOUR_CHIP_KERNELS = ("shard_tick", "shard_admit_quantum", "shard_plan_fleet",
                     "control_tick", "admit_quantum", "plan_fleet")
#: tolerances of tests/test_control_plane.py (kernel f32 vs f64 oracle)
ALLOC_REL, ALLOC_ABS = 2e-3, 1e-2
WEIGHT_REL = 1e-4
STATE_REL, STATE_ABS = 1e-4, 1e-5


class SmokeFailure(Exception):
    """A phase's result disagrees with its oracle."""


def emit(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- compile accounting -------------------------------------------------------

class CompileLog:
    """Backend compiles seen by this process, by jitted function name,
    with whether each was loaded from the persistent cache."""

    def __init__(self, jax) -> None:
        self.events: list[tuple[str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kw.get("fun_name"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.events.append((name, duration, self._hit))
            self._hit = False

    def summary(self, kernels) -> dict:
        out = {}
        for name in kernels:
            ev = [e for e in self.events if e[0] == name]
            out[name] = {"compiles": len(ev),
                         "compile_s": sum(e[1] for e in ev),
                         "cache_hits": sum(e[2] for e in ev)}
        other = [e for e in self.events if e[0] not in kernels]
        out["other"] = {"compiles": len(other),
                        "compile_s": sum(e[1] for e in other),
                        "cache_hits": sum(e[2] for e in other)}
        return out


class Phases:
    """Named wall-clock phases, each ended with ``block_until_ready``
    on what the phase left on the device."""

    def __init__(self, jax) -> None:
        self.jax = jax

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        result, on_device, info = fn(*args, **kw)
        self.jax.block_until_ready(on_device)
        emit(phase=name, wall_s=time.perf_counter() - t0, **info)
        return result


def device_states(gateways) -> list:
    return [p.store.device_state() for gw in gateways
            for p in gw.manager.pools.values()]


# -- the one-chip fleet -------------------------------------------------------

def entitlement_specs(rng, pool: str, names, mix):
    """Seeded mixed-class entitlements for ``names``.  ``mix`` maps a
    service class to (share, baseline tok/s, decode slots)."""
    from repro.core import EntitlementSpec, QoS, Resources, ServiceClass

    classes = list(mix)
    share = [mix[c][0] for c in classes]
    picks = rng.choice(len(classes), size=len(names), p=share)
    scale = rng.lognormal(0.0, 0.6, size=len(names))
    specs = []
    for name, k, s in zip(names, picks, scale):
        klass = classes[k]
        _, tps, slots = mix[klass]
        if klass is ServiceClass.SPOT:
            slo, base = 30_000.0, Resources(0.0, 0.0, 0.0)
        else:
            slo = (float(rng.choice([200.0, 500.0, 1000.0]))
                   if klass is ServiceClass.GUARANTEED
                   else float(rng.uniform(1000.0, 30_000.0)))
            base = Resources(float(tps * s), slots * 8192 * KV_PER_TOKEN,
                             float(slots))
        specs.append(EntitlementSpec(
            name=name, tenant_id=f"t-{name}", pool=pool,
            qos=QoS(klass, slo), baseline=base))
    return specs


def build_fleet(seed: int, n_main: int, n_spill: int):
    """The three-pool fleet behind one ``Gateway(manager,
    telemetry=True)``; the same seed always builds the same fleet."""
    import numpy as np

    from repro.core import (
        PoolManager, PoolSpec, PriorityCoefficients, Resources,
        RouteEntry, ScalingBounds, ServiceClass)
    from repro.gateway import Gateway

    rng = np.random.default_rng(seed)
    g, e, s = (ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
               ServiceClass.SPOT)
    main_specs = entitlement_specs(
        rng, "main", [f"m{i}" for i in range(n_main)],
        {g: (0.02, 600.0, 1), e: (0.68, 150.0, 0), s: (0.30, 0.0, 0)})
    reserved = sum(sp.baseline.tokens_per_second for sp in main_specs
                   if sp.qos.service_class is not s)
    replicas = 16
    manager = PoolManager()
    # the last ~1% of reservations do not fit the virtual node: those
    # leases stay pending and their entitlements Degraded (NOT_BOUND)
    manager.add_pool(PoolSpec(
        name="main", model="llama-3-8b",
        scaling=ScalingBounds(replicas, replicas),
        per_replica=Resources(0.99 * reserved / replicas,
                              float(1 << 44), 192.0),
        coefficients=PriorityCoefficients(alpha_debt=2.0),
        bucket_window_s=60.0))
    spill_mix = {g: (0.10, 400.0, 2), e: (0.70, 150.0, 0),
                 s: (0.20, 0.0, 0)}
    east = entitlement_specs(rng, "east",
                             [f"e{i}" for i in range(n_spill)], spill_mix)
    spill_reserved = sum(sp.baseline.tokens_per_second for sp in east
                         if sp.qos.service_class is not s)
    for name, share in (("east", 0.3), ("west", 0.6)):
        manager.add_pool(PoolSpec(
            name=name, model="llama-3-8b", scaling=ScalingBounds(1, 4),
            per_replica=Resources(share * spill_reserved, float(1 << 42),
                                  256.0),
            bucket_window_s=60.0))
    gw = Gateway(manager, telemetry=True)
    for sp in main_specs:
        manager.add_entitlement(sp)
        gw.register_key(f"k-{sp.name}", sp.name, pool="main")
    for sp in east:
        west = dataclasses.replace(sp, name="w" + sp.name[1:], pool="west")
        manager.add_entitlement(sp)
        manager.add_entitlement(west)
        gw.register_route(f"k-{sp.name}", [RouteEntry("east", sp.name),
                                           RouteEntry("west", west.name)])
    return gw


def zipf_keys(rng, keys: list, n: int, s: float = 0.9) -> list:
    import numpy as np
    p = 1.0 / np.arange(1, len(keys) + 1) ** s
    ranks = rng.choice(len(keys), size=n, p=p / p.sum())
    order = rng.permutation(len(keys))        # hot set is seeded, not key 0
    return [keys[order[r]] for r in ranks]


def long_tail_lengths(rng, n: int):
    """(input tokens, max output tokens): log-normal, clipped."""
    import numpy as np
    inp = np.clip(rng.lognormal(np.log(512.0), 1.0, n), 16, 16384)
    out = np.clip(rng.lognormal(np.log(128.0), 0.8, n), 8, 4096)
    return inp.astype(int), out.astype(int)


class Driver:
    """Applies the same quanta and lifecycle events to every gateway it
    holds (the fleet and its twin), so their states stay identical."""

    def __init__(self, gateways, seed: int) -> None:
        import numpy as np
        self.gws = gateways
        self.rng = np.random.default_rng(seed + 1)
        self.outstanding: list[tuple[str, str, int]] = []
        self.counts: dict[str, int] = {}

    def quantum(self, tag: str, keys: list):
        from repro.gateway import QuantumRequest
        ks = zipf_keys(self.rng, keys, QUANTUM)
        inp, out = long_tail_lengths(self.rng, QUANTUM)
        return [QuantumRequest(k, f"{tag}-{i}", int(a), int(b),
                               KV_PER_TOKEN)
                for i, (k, a, b) in enumerate(zip(ks, inp, out))]

    def admit(self, reqs, now: float, scalar_twin: bool = False):
        """Quantum on every gateway; with ``scalar_twin`` the second
        gateway replays it through the scalar ``handle`` instead."""
        resps = []
        for j, gw in enumerate(self.gws):
            if scalar_twin and j == 1:
                resps.append([gw.handle(q.api_key, q.request_id,
                                        q.input_tokens, q.max_tokens, now,
                                        kv_bytes_per_token=q.kv_bytes_per_token)
                              for q in reqs])
            else:
                resps.append(gw.handle_quantum(reqs, now))
        first = resps[0]
        counts: dict[str, int] = {}
        for r in first:
            key = "admit" if r.status == 200 else r.reason or str(r.status)
            counts[key] = counts.get(key, 0) + 1
        for key, n in counts.items():
            self.counts[key] = self.counts.get(key, 0) + n
        self.last_counts = dict(sorted(counts.items()))
        admitted = [(r.request_id, r.pool, q.max_tokens)
                    for r, q in zip(first, reqs) if r.status == 200]
        # dispatch half of the new admits onto decode slots (residency
        # is what the concurrency check counts)
        for rid, pool, _ in admitted[::2]:
            for gw in self.gws:
                gw.manager.pool(pool).on_start(rid)
        self.outstanding.extend(admitted)
        return resps

    def settle(self, now: float) -> int:
        """Complete a seeded half of the outstanding requests."""
        done = self.rng.random(len(self.outstanding)) < 0.4
        frac = self.rng.random(len(self.outstanding))
        batch = [(rid, max(1, int(f * mt)), 0.05 + f)
                 for (rid, _, mt), d, f in zip(self.outstanding, done, frac)
                 if d]
        self.outstanding = [o for o, d in zip(self.outstanding, done)
                            if not d]
        for gw in self.gws:
            gw.on_complete_batch(batch, now)
        return len(batch)

    def tick_and_plan(self, now: float) -> list:
        records = []
        for gw in self.gws:
            rec = gw.manager.tick(now)
            gw.plan_quantum(now, records=rec)
            records.append(rec)
        return records


def decision_diff(a, b) -> list:
    return [(x.request_id, (x.status, x.reason, x.pool),
             (y.status, y.reason, y.pool))
            for x, y in zip(a, b)
            if (x.status, x.reason, x.pool) != (y.status, y.reason, y.pool)]


def approx(got, want, rel: float, abs_: float = 1e-12):
    """pytest.approx semantics, vectorized: |got−want| ≤ max(rel·|want|, abs)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    bad = np.abs(got - want) > np.maximum(rel * np.abs(want), abs_)
    return int(bad.sum())


def tick_oracle_inputs(pool):
    """The pre-tick rows of ``pool`` (live rows, slot order), read
    before ``PoolManager.tick`` runs."""
    c = pool.store.col
    idx = pool.store.live_slots().copy()
    return idx, {k: c[k][idx].copy() for k in ("burst", "debt")}


def reference_tick_check(pool, record, idx, pre) -> dict:
    """``reference_tick`` over the inputs ``record``'s tick saw: the
    pre-tick burst/debt, the static columns, and the measurement
    columns the tick's own measurement step wrote."""
    import numpy as np

    from repro.core import OracleRow, ServiceClass, reference_tick
    from repro.core.control_plane import CLASS_CODES

    by_code = {v: k for k, v in CLASS_CODES.items()}
    c = pool.store.col
    f32 = {k: c[k][idx].astype(np.float32) for k in (
        "baseline_tps", "baseline_kv", "baseline_conc", "slo_ms",
        "measured_tps", "kv_in_use", "resident", "demand_tps")}
    rows = [OracleRow(
        service_class=by_code[int(code)], bound=bool(bound),
        baseline_tps=float(f32["baseline_tps"][i]),
        baseline_kv=float(f32["baseline_kv"][i]),
        baseline_conc=float(f32["baseline_conc"][i]),
        slo_ms=float(f32["slo_ms"][i]),
        burst=float(pre["burst"][i]), debt=float(pre["debt"][i]),
        measured_tps=float(f32["measured_tps"][i]),
        used_kv=float(f32["kv_in_use"][i]),
        used_conc=float(f32["resident"][i]),
        demand_tps=float(f32["demand_tps"][i]))
        for i, (code, bound) in enumerate(zip(c["class_code"][idx],
                                              c["bound"][idx]))]
    o_rows, o_alloc, o_w = reference_tick(
        rows, record.capacity_tps, pool.pool_avg_slo(),
        pool.spec.coefficients)
    names = pool.store.live_names()
    alloc = np.array([record.allocations[n] for n in names])
    weights = np.array([record.priorities[n] for n in names])
    bad = {
        "weights": approx(weights, o_w, WEIGHT_REL),
        "alloc": approx(alloc, o_alloc, ALLOC_REL, ALLOC_ABS),
        "burst": approx(c["burst"][idx], [r.burst for r in o_rows],
                        STATE_REL, STATE_ABS),
        "debt": approx(c["debt"][idx], [r.debt for r in o_rows],
                       STATE_REL, STATE_ABS),
    }
    classes = {k.value: int(np.sum(c["class_code"][idx] == CLASS_CODES[k]))
               for k in ServiceClass}
    return {"rows": len(rows), "mismatched": bad, "classes": classes,
            "alloc_total": float(alloc.sum()),
            "capacity_tps": record.capacity_tps}


def run_one_chip(jax, seed: int, n_main: int = 1 << 17,
                 n_spill: int = 3072, n_quanta: int = 5,
                 chaos: bool = True) -> None:
    """The served path on one chip, held to its oracles."""
    import numpy as np

    phases = Phases(jax)

    def build():
        gws = [build_fleet(seed, n_main, n_spill) for _ in range(2)]
        pools = gws[0].manager.pools
        return gws, device_states(gws), {
            "entitlements": {n: len(p.entitlements)
                             for n, p in pools.items()},
            "degraded_main": int(np.sum(
                ~pools["main"].store.col["bound"]
                & pools["main"].store.col["alive"]))}

    gws = phases.run("build", build)
    drv = Driver(gws, seed)
    main_keys = [f"k-m{i}" for i in range(n_main)]
    spill_keys = [f"k-e{i}" for i in range(n_spill)]

    def tick(now):
        recs = drv.tick_and_plan(now)
        return recs, device_states(gws), {
            "replicas": {n: p.replicas
                         for n, p in gws[0].manager.pools.items()}}

    parity_q = n_quanta - 1
    for q in range(n_quanta):
        now = q + 0.5
        for tag, keys in (("main", main_keys), ("spill", spill_keys)):
            reqs = drv.quantum(f"{tag}{q}", keys)

            def admit():
                resps = drv.admit(reqs, now, scalar_twin=(q == parity_q))
                return resps, device_states(gws), {
                    "requests": len(reqs), "decisions": drv.last_counts}

            resps = phases.run(f"quantum_{tag}_{q}", admit)
            diff = decision_diff(*resps)
            if q == parity_q:
                emit(check=f"scalar_parity_{tag}", requests=len(reqs),
                     mismatches=len(diff), first=diff[:5])
                check(not diff, f"scalar Gateway.handle disagrees with "
                      f"handle_quantum on {len(diff)} {tag} requests")
            else:
                check(not diff, f"twin gateways diverged on {tag} quantum {q}")

        def settle():
            n = drv.settle(now + 0.25)
            return n, device_states(gws), {"completed": n}

        phases.run(f"settle_{q}", settle)
        main = gws[0].manager.pool("main")
        idx, pre = tick_oracle_inputs(main)
        recs = phases.run(f"tick_plan_{q}", tick, q + 1.0)
        if q == parity_q:
            res = reference_tick_check(main, recs[0]["main"], idx, pre)
            emit(check="reference_tick", **res)
            check(not any(res["mismatched"].values()),
                  f"TokenPool.tick vs reference_tick: {res['mismatched']}")
    for name, p in gws[0].manager.pools.items():
        twin = gws[1].manager.pool(name)
        for col in ("burst", "debt", "eff_tps", "in_flight", "resident"):
            check(np.array_equal(p.store.col[col], twin.store.col[col]),
                  f"fleet and twin differ in {name}.{col}")
    emit(check="decisions", counts=dict(sorted(drv.counts.items())))
    if chaos:
        phases.run("chaos", run_chaos)


def run_chaos():
    from repro.chaos import SCENARIOS, run_replay, run_scenario
    out = {}
    for sc in SCENARIOS:
        rep = run_scenario(sc)
        res = run_replay(sc)
        out[sc.name] = {"checkers_pass": rep["passed"],
                        "violations": len(rep["violations"]),
                        "replay_identical": res.identical,
                        "requests": len(res.traces["scalar"].outcomes)}
        emit(check=f"chaos_{sc.name}", **out[sc.name],
             first_mismatches=res.mismatches[:5],
             first_violations=rep["violations"][:3])
        check(rep["passed"], f"chaos {sc.name}: invariant violations")
        check(res.identical, f"chaos {sc.name}: replay not identical")
    return out, None, {}


# -- the four-chip sharded plane ----------------------------------------------

def build_shard_pair(seed: int, n_rows: int, shards: int):
    """(flat gateway, sharded gateway) over the same entitlements in the
    same row layout.  The sharded store spreads new rows across its
    shards, so the flat twin adds the entitlements in the sharded
    store's slot order: the tick's positional tree sums then add the
    same f32 values in the same order on both, which is what makes a
    bit-for-bit comparison of the kernels meaningful.  ``n_rows`` fills
    the stores exactly (no free slots), and every reservation fits the
    virtual node, so add order cannot change which leases bind."""
    import numpy as np

    from repro.core import (
        PoolSpec, Resources, ScalingBounds, ServiceClass, TokenPool)
    from repro.gateway import Gateway

    rng = np.random.default_rng(seed)
    g, e, s = (ServiceClass.GUARANTEED, ServiceClass.ELASTIC,
               ServiceClass.SPOT)
    specs = entitlement_specs(
        rng, "p", [f"m{i}" for i in range(n_rows)],
        {g: (0.05, 400.0, 1), e: (0.65, 100.0, 0), s: (0.30, 0.0, 0)})
    reserved = sum(sp.baseline.tokens_per_second for sp in specs
                   if sp.qos.service_class is not s)
    gws = []
    for n_shards in (shards, None):
        pool = TokenPool(PoolSpec(
            name="p", model="llama-3-8b", scaling=ScalingBounds(16, 16),
            per_replica=Resources(1.01 * reserved / 16, float(1 << 46),
                                  float(n_rows // 16)),
            bucket_window_s=60.0, shards=n_shards))
        gw = Gateway(pool)
        for sp in specs:
            pool.add_entitlement(sp)
            gw.register_key(f"k-{sp.name}", sp.name, pool="p")
        gws.append(gw)
        slot_of = pool.store.slot_of
        specs = sorted(specs, key=lambda sp: slot_of[sp.name])
    shard_gw, flat_gw = gws
    check(shard_gw.pool.store.slot_of == flat_gw.pool.store.slot_of,
          "flat twin does not share the sharded store's row layout")
    return flat_gw, shard_gw


def placement(store) -> dict:
    """Where each mirrored column's row blocks live."""
    import numpy as np

    from repro.core import ControlState
    mesh = store.mesh
    devices = list(mesh.devices.flat)
    rows = store.capacity // mesh.size
    state = store.device_state()
    wrong = 0
    layout = {}
    for f in dataclasses.fields(ControlState):
        for piece in getattr(state, f.name).addressable_shards:
            lo = piece.index[0].start or 0
            owner = devices[lo // rows]
            wrong += int(piece.device != owner
                         or piece.data.shape[0] != rows)
            layout[str(piece.device)] = [lo, lo + piece.data.shape[0]]
    probe = store.put_rows(np.zeros(store.capacity, np.float32))
    inputs_ok = all(p.device == devices[(p.index[0].start or 0) // rows]
                    for p in probe.addressable_shards)
    return {"mesh": [str(d) for d in devices], "rows_per_device": rows,
            "blocks": layout, "misplaced": wrong,
            "row_inputs_placed": inputs_ok}


def namewise(flat, shard, cols) -> dict:
    import numpy as np
    names = list(flat.store.slot_of)
    sf = np.fromiter((flat.store.slot_of[n] for n in names), np.int64)
    ss = np.fromiter((shard.store.slot_of[n] for n in names), np.int64)
    return {c: int(np.sum(flat.store.col[c][sf] != shard.store.col[c][ss]))
            for c in cols}


def run_four_chip(jax, seed: int, n_rows: int = 1 << 20,
                  n_quanta: int = 3, n_plan_pools: int = 512) -> None:
    """The sharded plane on a 4-chip ``rows`` mesh against the flat
    single-device kernels, bit for bit."""
    import numpy as np

    from repro.core import control_plane
    from repro.core.fleet import FleetPlannerConfig, plan_fleet
    from repro.core.shard_plane import pool_mesh, row_mesh, shard_plan_fleet

    phases = Phases(jax)
    n_dev = 4

    def build():
        gws = build_shard_pair(seed, n_rows, n_dev)
        return gws, device_states(gws), {"rows": n_rows}

    flat_gw, shard_gw = phases.run("build", build)
    flat, shard = flat_gw.pool, shard_gw.pool
    mesh = pool_mesh(shard)
    check(mesh is not None and mesh.size == n_dev,
          f"sharded pool dispatches on {mesh}, not a {n_dev}-chip mesh")
    drv = Driver([flat_gw, shard_gw], seed)
    keys = [f"k-m{i}" for i in range(n_rows)]
    cols = ("burst", "debt", "eff_tps")

    def tick(now):
        recs = [gw.pool.tick(now) for gw in (flat_gw, shard_gw)]
        names = flat.store.live_names()
        w = [np.array([r.priorities[n] for n in names]) for r in recs]
        diff = namewise(flat, shard, cols)
        diff["weights"] = int(np.sum(w[0] != w[1]))
        return diff, device_states([flat_gw, shard_gw]), {"mismatched": diff}

    for q in range(n_quanta):
        diff = phases.run(f"tick_{q}", tick, float(q))
        check(not any(diff.values()), f"sharded tick differs: {diff}")
        reqs = drv.quantum(f"q{q}", keys)

        def admit():
            resps = drv.admit(reqs, q + 0.5)
            bad = sum((a.status, a.reason, a.priority)
                      != (b.status, b.reason, b.priority)
                      for a, b in zip(*resps))
            return bad, device_states([flat_gw, shard_gw]), {
                "requests": len(reqs), "mismatched": bad}

        bad = phases.run(f"quantum_{q}", admit)
        check(bad == 0, f"sharded admission differs on {bad} requests")
        drv.settle(q + 0.75)
        if q == 0:
            layout = placement(shard.store)
            emit(check="placement_full_upload", **layout)
            check(layout["misplaced"] == 0 and layout["row_inputs_placed"],
                  "sharded store rows are not on their owning chips")
            # one host-side row write dirties one block: it is uploaded
            # to its owning chip alone and the sharded array reassembled
            before = (shard.store.block_uploads, shard.store.full_uploads)
            for gw in (flat_gw, shard_gw):
                gw.pool.status["m7"].debt = 0.5
            layout = placement(shard.store)
            uploads = (shard.store.block_uploads - before[0],
                       shard.store.full_uploads - before[1])
            emit(check="placement_block_upload", block_uploads=uploads[0],
                 full_uploads=uploads[1], misplaced=layout["misplaced"])
            check(layout["misplaced"] == 0 and uploads == (1, 0),
                  f"block re-upload misplaced or not block-local: {uploads}")
    diff = phases.run("tick_final", tick, float(n_quanta))
    check(not any(diff.values()), f"sharded tick differs: {diff}")
    emit(check="decisions", counts=dict(sorted(drv.counts.items())))

    def plan():
        rng = np.random.default_rng(seed + 2)
        p = n_plan_pools
        f32 = lambda lo, hi: jax.numpy.asarray(                # noqa: E731
            rng.uniform(lo, hi, p).astype(np.float32))
        args = (jax.numpy.asarray(rng.integers(1, 8, p), np.int32),
                jax.numpy.ones(p, np.int32),
                jax.numpy.full((p,), 16, np.int32),
                f32(10, 100), f32(20, 200), f32(1, 8), f32(0, 800),
                f32(0, 1000), f32(0, 40), f32(0, 1500), f32(0, 1000),
                jax.numpy.asarray(rng.random(p) < 0.7),
                jax.numpy.asarray(rng.integers(0, 4, p), np.int32))
        cfg = FleetPlannerConfig()
        ref = plan_fleet(*args, config=cfg)
        got = shard_plan_fleet(*args, config=cfg, mesh=row_mesh(n_dev))
        bad = sum(int(np.sum(np.asarray(r) != np.asarray(g)))
                  for r, g in zip(ref, got))
        return bad, [ref, got], {"pools": p, "mismatched": bad}

    bad = phases.run("plan_fleet", plan)
    check(bad == 0, f"shard_plan_fleet differs from plan_fleet: {bad}")
    counts = {k: control_plane.TRACE_COUNTS[k]
              for k in ("shard_tick", "shard_admit_quantum",
                        "shard_plan_fleet")}
    check(all(counts.values()), f"a sharded kernel never ran: {counts}")


# -- entry point --------------------------------------------------------------

def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path; 4: the sharded plane only")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not next to this script ({SRC})")
    sys.path.insert(0, str(SRC))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        fail(f"no TPU found: JAX could not start a backend ({exc})")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devices)} {dev.platform} "
             f"device(s) ({dev.device_kind})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU chips, "
             f"JAX sees {len(devices)}")
    compiles = CompileLog(jax)
    emit(device_kind=dev.device_kind, devices=len(devices),
         jax=jax.__version__, compile_cache=str(cache), seed=args.seed,
         chips=args.chips)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            run_one_chip(jax, args.seed)
            kernels = ONE_CHIP_KERNELS
        else:
            run_four_chip(jax, args.seed)
            kernels = FOUR_CHIP_KERNELS
        summary = compiles.summary(kernels)
        emit(compile=summary, total_wall_s=time.perf_counter() - t0)
        missing = [k for k in kernels if not summary[k]["compiles"]]
        check(not missing, f"kernels never compiled on the chip: {missing}")
    except SmokeFailure as exc:
        fail(f"FAILED: {exc}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
