"""Serving launcher: a token-pool-governed engine on a small model.

    PYTHONPATH=src python -m repro.launch.serve --requests 24

Brings up: TokenPool (+virtual node) → Gateway (key auth, admission) →
InferenceEngine (continuous batching over a JAX model), and drives a
two-tenant workload (guaranteed + spot) through it.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.core import (
    EntitlementSpec,
    PoolSpec,
    QoS,
    Resources,
    ScalingBounds,
    ServiceClass,
    TokenPool,
)
from repro.gateway import Gateway
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import InferenceEngine, Request
from repro.serving.request import latency_summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced(vocab_size=1024, num_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    spec = PoolSpec(name=cfg.name, model=cfg.name,
                    scaling=ScalingBounds(1, 1),
                    per_replica=Resources(2e4, float(1 << 30),
                                          float(args.slots)),
                    default_max_tokens=args.max_tokens)
    pool = TokenPool(spec)
    pool.add_entitlement(EntitlementSpec(
        name="prod", tenant_id="prod", pool=cfg.name,
        qos=QoS(ServiceClass.GUARANTEED, 200.0),
        baseline=Resources(1e4, 0.0, float(args.slots))))
    pool.add_entitlement(EntitlementSpec(
        name="batch", tenant_id="batch", pool=cfg.name,
        qos=QoS(ServiceClass.SPOT, 30000.0),
        baseline=Resources(0.0, 0.0, 0.0)))
    pool.ledger.set_rate("batch", 2e4, 0.0)
    pool.ledger.bucket("batch").level = 2e4
    gw = Gateway(pool)
    gw.register_key("k-prod", "prod")
    gw.register_key("k-batch", "batch")

    eng = InferenceEngine(model, params, slots=args.slots,
                          max_seq=cfg.max_seq_len, gateway=gw)
    reqs = []
    for i in range(args.requests):
        tenant = "prod" if i % 2 == 0 else "batch"
        r = Request(request_id=f"r{i}", entitlement=tenant,
                    prompt_tokens=[2 + i % 7, 3, 5],
                    max_tokens=args.max_tokens, arrival_s=float(i) * 0.01,
                    api_key=f"k-{tenant}")
        reqs.append(r)
        eng.submit(r, now=r.arrival_s)
    eng.run_until_drained()

    for tenant in ("prod", "batch"):
        sel = [r for r in reqs if r.entitlement == tenant]
        print(tenant, latency_summary(sel))
    print("pool tokens served:", {
        n: pool.status[n].tokens_total for n in pool.status})


if __name__ == "__main__":
    main()
