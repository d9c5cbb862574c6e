"""Tenant fleets from a configuration file and a seed.

:func:`fleet_spec` turns a configuration (``bench/configs/<name>.json``)
into plain pool and entitlement specs: the same seed always gives the
same fleet, and both the system under test (:func:`build_gateway`) and
the plain reference are built from these specs, so neither takes
anything the other made.

A fleet serves ``models``, each in every one of ``regions``: one pool per
(model, region).  Each model has ``keys_per_model`` API keys with a Zipf
popularity rank.  A key's home region follows its rank (rank r homes in
region r mod R), and its route has one leg per entry of ``legs``: leg k
lies in the pool of the key's model in region (home + k) mod R, on an
entitlement of its own whose class and reservation the leg's template
sets from the rank.  Ranks are dealt to key names by a seeded
permutation, so every seed gets the same set of tenants under other
names.
"""
from __future__ import annotations

import math

import numpy as np


def zipf_pmf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return p / p.sum()


def class_of_rank(n: int, shares: dict[str, float]) -> list[str]:
    """Classes dealt over ranks in proportion to ``shares``, spread
    evenly (largest remainder per rank), so the class mix is the same
    at every level of popularity."""
    names = list(shares)
    share = np.array([shares[c] for c in names], np.float64)
    share /= share.sum()
    got = np.zeros(len(names))
    out = []
    for r in range(n):
        k = int(np.argmax(share * (r + 1) - got))
        got[k] += 1
        out.append(names[k])
    return out


def _reservation(cc: dict, p: float, config: dict, kv_per_token: float
                 ) -> tuple[float, float, float]:
    """(tokens/s, KV bytes, decode slots) reserved by one entitlement of
    class template ``cc`` whose key draws a share ``p`` of its model's
    traffic."""
    if not cc.get("reserves", True):
        return 0.0, 0.0, 0.0
    tps = round(max(float(cc["min_tps"]),
                    float(cc.get("headroom", config.get(
                        "reservation_headroom", 1.0)))
                    * p * float(config["design_tokens_per_s"])), 3)
    if "slots_of_concurrency" in cc:
        slots = float(max(int(cc.get("min_slots", 1)), math.ceil(
            cc["slots_of_concurrency"] * p
            * float(config["design_concurrency"]))))
    else:
        slots = float(cc.get("slots", 0))
    kv = slots * float(cc.get("kv_tokens_per_slot", 0)) * kv_per_token
    return tps, kv, slots


def fleet_spec(config: dict, seed: int) -> dict:
    """Plain specs of the fleet: ``pools`` (one dict per pool, in add
    order), ``entitlements`` (per pool, in add order; each names its
    key), ``routes`` (key -> [(pool, entitlement), ...]),
    ``keys_by_rank`` (per model, the key of each popularity rank) and
    ``kv_of_key``."""
    rng = np.random.default_rng(seed % (1 << 63))
    pool_cfg = config["pool"]
    models = config["models"]
    regions = config["regions"]
    n = int(config["keys_per_model"])
    p = zipf_pmf(n, float(config["design_zipf_s"]))
    legs = config["legs"]
    classes = [class_of_rank(n, {c: v["share"]
                                 for c, v in leg["classes"].items()})
               for leg in legs]
    pool_names = [[f"{m['pool']}-{r}" for r in regions] for m in models]
    ents: dict[str, list] = {name: [] for row in pool_names
                             for name in row}
    routes: dict[str, list] = {}
    keys_by_rank, kv_of_key = [], {}
    for mi, model in enumerate(models):
        kv_per_token = float(model["kv_bytes_per_token"])
        order = rng.permutation(n)          # key id of each rank
        rank_of = np.empty(n, np.int64)
        rank_of[order] = np.arange(n)
        by_rank = [None] * n
        for e in range(n):                  # add order: key id
            rank = int(rank_of[e])
            key = f"k{mi}-{e}"
            by_rank[rank] = key
            kv_of_key[key] = kv_per_token
            home = rank % len(regions)
            route = []
            for li, leg in enumerate(legs):
                cls = classes[li][rank]
                cc = leg["classes"][cls]
                tps, kv, slots = _reservation(cc, p[rank], config,
                                              kv_per_token)
                pool = pool_names[mi][(home + li) % len(regions)]
                name = f"e{mi}-{e}" + (f"-{li}" if len(legs) > 1 else "")
                ents[pool].append({
                    "name": name, "key": key, "class": cls, "tps": tps,
                    "kv": kv, "conc": slots,
                    "slo_ms": float(cc["slo_ms"][rank % len(cc["slo_ms"])]),
                    "rank": rank, "leg": li})
                route.append((pool, name))
            routes[key] = route
        keys_by_rank.append(by_rank)
    pools = []
    replicas = int(pool_cfg["replicas"])
    over = float(pool_cfg["capacity_over_reserved"])
    for mi, model in enumerate(models):
        for name in pool_names[mi]:
            es = ents[name]
            reserving = [e for e in es if e["tps"] > 0 or e["conc"] > 0]
            # exact sums: the same for every order the seed deals
            reserved = math.fsum(e["tps"] for e in reserving)
            reserved_kv = math.fsum(e["kv"] for e in reserving)
            reserved_conc = math.fsum(e["conc"] for e in reserving)
            per = [over * reserved / replicas,
                   max(float(pool_cfg["per_replica_kv_bytes"]),
                       over * reserved_kv / replicas),
                   max(float(pool_cfg["per_replica_concurrency"]),
                       float(np.ceil(over * reserved_conc / replicas)))]
            pools.append({
                "name": name, "model": model["name"],
                "replicas": replicas, "min_replicas": replicas,
                "max_replicas": replicas, "per_replica": per,
                "bucket_window_s": float(pool_cfg["bucket_window_s"]),
                "accounting_interval_s": float(
                    pool_cfg["accounting_interval_s"]),
                "coefficients": dict(pool_cfg.get("coefficients", {})),
                "kv_bytes_per_token": float(model["kv_bytes_per_token"])})
    return {"pools": pools, "entitlements": ents, "routes": routes,
            "keys_by_rank": keys_by_rank, "kv_of_key": kv_of_key}


def build_gateway(spec: dict):
    """The system under test, built through the public ``repro.core`` /
    ``repro.gateway`` API from the plain specs."""
    from repro.core import (EntitlementSpec, PoolManager, PoolSpec,
                            PriorityCoefficients, QoS, Resources,
                            ScalingBounds, ServiceClass)
    from repro.gateway import Gateway

    manager = PoolManager()
    for pool in spec["pools"]:
        manager.add_pool(PoolSpec(
            name=pool["name"], model=pool["model"],
            scaling=ScalingBounds(pool["min_replicas"],
                                  pool["max_replicas"]),
            per_replica=Resources(*pool["per_replica"]),
            coefficients=PriorityCoefficients(**pool["coefficients"]),
            bucket_window_s=pool["bucket_window_s"],
            accounting_interval_s=pool["accounting_interval_s"]))
    gw = Gateway(manager, telemetry=True)
    for pool in spec["pools"]:
        for e in spec["entitlements"][pool["name"]]:
            manager.add_entitlement(EntitlementSpec(
                name=e["name"], tenant_id=f"t-{e['key']}",
                pool=pool["name"],
                qos=QoS(ServiceClass(e["class"]), e["slo_ms"]),
                baseline=Resources(e["tps"], e["kv"], e["conc"])))
    for key, route in spec["routes"].items():
        if len(route) == 1:
            gw.register_key(key, route[0][1], pool=route[0][0])
        else:
            gw.register_route(key, route)
    return gw
