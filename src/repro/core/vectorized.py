"""Vectorized admission path + back-compat shims over the unified
control plane.

The tick math that used to live here is now THE control plane
(``core.control_plane``) — ``TokenPool.tick`` and ``PoolManager.tick``
execute it directly.  This module keeps:

- :func:`admit_quantum` — exact sequential admission replay for one
  scheduling quantum as a jit-compiled ``lax.fori_loop``: this IS the
  gateway's default request path (``Gateway.handle_quantum`` batches
  each (pool, leg) group through one dispatch);
- :func:`arrays_from_pool` / :func:`quantum_snapshot` — O(1) views
  over a ``TokenPool``'s RESIDENT arrays (``core.resident``): the
  kernel state is the store's cached device mirror and bucket levels
  are one vectorized projection, with nothing mutated and nothing
  gathered per row; the running-min seed is :func:`owner_min`, one
  fixed-width masked min over the in-flight owner rows;
- aliases (``PoolArrays``, ``tick_batch``, ``waterfill_batch``, …) so
  existing imports keep working.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.control_plane import (
    BURSTOK_MASK as _BURSTOK,
    CLASS_CODES,
    CLASS_W as _W,
    ControlState,
    DEBTOK_MASK as _DEBTOK,
    ELASTIC_MASK as _ELASTIC,
    PROTECTED_MASK as _PROTECTED,
    allocate_rows as allocate_tps_batch,
    bucket_width,
    burst_delta_rows as burst_delta_batch,
    control_tick,
    ewma,
    priority_rows as priority_batch,
    waterfill_rows as waterfill_batch,
)
from repro.core.markers import kernel
from repro.core.types import PriorityCoefficients, ServiceClass
from repro.telemetry.spans import child, readback

#: Back-compat name: the array-of-rows state is the ControlState.
PoolArrays = ControlState


@kernel(oracle="repro.core.pool.TokenPool.tick")
@partial(jax.jit, static_argnames=("coeff",))
def tick_batch(arr: ControlState, capacity_tps: jax.Array,
               measured_tps: jax.Array, used_kv: jax.Array,
               used_conc: jax.Array, demand_tps: jax.Array,
               coeff: PriorityCoefficients = PriorityCoefficients(),
               ) -> tuple[ControlState, jax.Array, jax.Array]:
    """Legacy entry point: one tick with ℓ̄* computed as the live mean
    over bound rows (``control_tick`` takes it explicitly instead, so
    the pool can pin it via ``PoolSpec.fixed_avg_slo_ms``)."""
    n_bound = jnp.maximum(jnp.sum(arr.bound), 1)
    avg_slo = jnp.sum(jnp.where(arr.bound, arr.slo_ms, 0.0)) / n_bound
    return control_tick(arr, capacity_tps, measured_tps, used_kv,
                        used_conc, demand_tps,
                        jnp.maximum(avg_slo, 1e-9), coeff=coeff)


@kernel(oracle="repro.core.admission.AdmissionController.decide")
@partial(jax.jit, static_argnames=("coeff", "slack"))
def admit_quantum(arr: ControlState,
                  bucket_level: jax.Array,       # f32 [N] tokens available
                  in_flight: jax.Array,          # i32 [N] RESIDENT seqs
                  kv_in_use: jax.Array,          # f32 [N]
                  pool_in_flight: jax.Array,     # i32 []
                  pool_conc_cap: jax.Array,      # f32 []
                  running_min_priority: jax.Array,  # f32 [] (inf if none)
                  pool_avg_slo: jax.Array,       # f32 []
                  req_ent: jax.Array,            # i32 [M] entitlement row
                  req_tokens: jax.Array,         # f32 [M] input+max_tokens
                  req_kv: jax.Array,             # f32 [M] kv bytes needed
                  pool_resident: jax.Array = None,  # i32 [] RESIDENT seqs
                  req_live: Optional[jax.Array] = None,  # bool [M] padding
                  weights: Optional[jax.Array] = None,   # f32 [N] Eq. 1
                  coeff: PriorityCoefficients = PriorityCoefficients(),
                  slack: float = 0.0,
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exact sequential admission replay for one scheduling quantum.

    Requests are processed in array order (arrival order).  Returns
    (admitted bool [M], deny_reason int [M], priority f32 [M]) with
    reason codes: 0=admitted, 1=not_bound, 2=concurrency,
    3=token_budget, 4=low_priority.  State updates (bucket charge,
    in-flight increments, running-min threshold) are applied between
    requests exactly as the scalar controller does — but inside one
    fused XLA loop.

    ``running_min_priority`` must be seeded with the LIVE priorities of
    the entitlements that currently own in-flight requests (what
    ``TokenPool.admission_threshold`` computes — use
    :func:`running_min_live`), not the stale per-record snapshots;
    ``pool_resident`` is the pool-wide count of RESIDENT sequences
    (frozen within a quantum — admission does not place KV, dispatch
    does) feeding the burst-class free-slot escape of check 3.
    ``req_live=False`` marks padding rows: they are denied without
    touching any state, so quanta can be padded to a power-of-two
    length without retracing or perturbing the replay.  Pass the
    snapshot's ``weights`` (``QuantumSnapshot.weights``) to reuse the
    Eq. 1 row weights the ``running_min_priority`` seed was computed
    from — the SAME array makes self-threshold ties bit-exact by
    construction; when omitted they are recomputed here.
    """
    from repro.core.control_plane import TRACE_COUNTS
    TRACE_COUNTS["admit_quantum"] += 1         # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants
    M = req_ent.shape[0]
    if pool_resident is None:
        # legacy callers: no resident count ⇒ no free-slot escape
        pool_resident = jnp.asarray(pool_conc_cap, jnp.float32)
    if weights is None:
        weights = priority_batch(arr, pool_avg_slo, coeff)

    def body(i, state):
        (bucket, infl, kv, pool_infl, run_min, admitted, reason) = state
        e = req_ent[i]
        tok = req_tokens[i]
        kvn = req_kv[i]
        w = weights[e]

        ok_bound = arr.bound[e]
        r_lim = arr.baseline_conc[e]
        # spot with no explicit limit is bounded by pool concurrency
        is_spot = arr.class_code[e] == CLASS_CODES[ServiceClass.SPOT]
        r_eff = jnp.where((r_lim <= 0) & is_spot, pool_conc_cap, r_lim)
        # Burst-capable classes (Table 1) may exceed r_e while the pool
        # has idle decode slots and nobody is waiting — the concurrency
        # dimension of work-conserving backfill (scalar check 3's
        # BURST_CLASSES escape; the overage then raises b_e and lowers
        # their priority).  Resident counts are frozen within a quantum,
        # but contention evolves with the admitted count below.
        burst_escape = (_BURSTOK[arr.class_code[e]]
                        & (pool_resident < pool_conc_cap)
                        & ~(pool_infl > pool_conc_cap))
        ok_conc = (r_eff <= 0) | (infl[e] < r_eff) | burst_escape
        ok_budget = bucket[e] >= tok
        chi = arr.baseline_kv[e]
        ok_kv = (chi <= 0) | (kv[e] + kvn <= chi)
        contended = pool_infl > pool_conc_cap
        shielded = _PROTECTED[arr.class_code[e]]
        ok_prio = shielded | ~contended | (w > run_min * (1.0 - slack))

        live = (jnp.bool_(True) if req_live is None else req_live[i])
        admit = live & ok_bound & ok_conc & ok_budget & ok_kv & ok_prio
        reason_i = jnp.where(
            ~ok_bound, 1,
            jnp.where(~ok_conc, 2,
                      jnp.where(~(ok_budget & ok_kv), 3,
                                jnp.where(~ok_prio, 4, 0))))

        bucket = bucket.at[e].add(jnp.where(admit, -tok, 0.0))
        # NOTE: `infl` counts RESIDENT sequences (check 3).  Admission
        # alone does not make a request resident — dispatch does — so
        # within one quantum the resident counts are frozen; only the
        # pool-level admitted count moves (contention, check 5).
        kv = kv.at[e].add(jnp.where(admit, kvn, 0.0))
        pool_infl = pool_infl + jnp.where(admit, 1, 0)
        run_min = jnp.where(admit, jnp.minimum(run_min, w), run_min)
        admitted = admitted.at[i].set(admit)
        reason = reason.at[i].set(reason_i)
        return (bucket, infl, kv, pool_infl, run_min, admitted, reason)

    state0 = (bucket_level, in_flight, kv_in_use, pool_in_flight,
              running_min_priority,
              jnp.zeros((M,), dtype=bool), jnp.zeros((M,), dtype=jnp.int32))
    out = jax.lax.fori_loop(0, M, body, state0)
    return out[5], out[6], weights[req_ent]


def arrays_from_pool(pool, now: float = 0.0
                     ) -> tuple[ControlState, jax.Array, jax.Array,
                                jax.Array]:
    """Bridge: view a ``TokenPool``'s RESIDENT arrays in kernel form.
    Returns (ControlState, bucket_levels, in_flight, kv_in_use) with
    rows in resident-slot order (``pool.store.slot_of`` maps names to
    rows); free slots ride along as inert unbound rows, so the width
    is the store's pow2 capacity and never retraces the kernels.

    Pure read: bucket levels are projected to ``now`` with one
    vectorized ``Ledger.peek_levels`` expression — snapshotting
    neither creates buckets nor advances refill clocks, so observing a
    pool cannot change any later admission decision.  The
    ``ControlState`` is the store's cached device mirror: after a tick
    this is O(1) Python (no per-row gather)."""
    c = pool.store.col
    # scalar fallback rate for bucketless rows: effective-or-baseline,
    # the same `eff or baseline` rule the scalar §4.3 pipeline applies
    fallback = np.where(c["eff_tps"] != 0.0, c["eff_tps"],
                        c["baseline_tps"].astype(np.float64))
    levels = pool.ledger.peek_levels(fallback, now)
    put = pool.store.put_rows
    return (pool.store.device_state(),
            put(levels.astype(np.float32)),
            put(c["resident"].astype(np.int32)),
            put(c["kv_in_use"].astype(np.float32)))


def running_min_live(pool) -> float:
    """Seed for ``running_min_priority``: the minimum LIVE priority
    among entitlements that currently own in-flight requests — exactly
    what ``TokenPool.admission_threshold`` evaluates when the pool is
    contended (debt/burst evolve after admission, so per-record
    priority snapshots would overstate the threshold).  +inf when the
    pool is empty.

    Scalar-oracle form (float64); :func:`quantum_snapshot` seeds the
    kernel with the float32 equivalent instead so a request whose OWN
    entitlement sets the threshold ties bit-exactly inside the kernel
    (the strict ``>`` of check 5 must not flip on a 1-ulp precision
    gap between the seed and the kernel's weight)."""
    owners = {r.entitlement for r in pool.in_flight.values()}
    ws = [pool.priority(e) for e in owners if e in pool.entitlements]
    return min(ws) if ws else float("inf")


@kernel(oracle="repro.core.vectorized.running_min_live")
@jax.jit
def owner_min(weights: jax.Array, owner: jax.Array) -> jax.Array:
    """Minimum of the Eq. 1 row ``weights`` over the rows that
    ``owner`` (bool, store width) marks; +inf when it marks none.

    Both shapes are the store's capacity, so the program compiles once
    per capacity whatever the owner count.  The min of a set of f32
    values is exact and order-free, so this equals ``jnp.min`` over
    the gathered owner rows bit for bit; the +inf fill never wins
    against a live weight (Eq. 1 weights are finite)."""
    from repro.core.control_plane import TRACE_COUNTS
    TRACE_COUNTS["owner_min"] += 1             # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants
    return jnp.min(jnp.where(owner, weights, jnp.inf))


def _running_min_f32(pool, weights: jax.Array) -> float:
    """float32 twin of :func:`running_min_live`, evaluated on the SAME
    Eq. 1 weight array handed to ``admit_quantum`` — one computation
    serves both the seed and the kernel, so a request whose own
    entitlement sets the threshold ties bit-exactly.

    The owner rows (the set ``inflight_owner_slots`` lists: owner
    slots ARE store row indices) are one scatter into a store-width
    mask, uploaded where the store's rows live and reduced by
    :func:`owner_min` — one fixed-shape program, however many owners
    come and go."""
    store, c = pool.store, pool.table.col
    owner = np.zeros(bucket_width(store.capacity), bool)
    owner[c["owner"][c["has_record"]]] = True
    return float(readback(owner_min(weights, store.put_rows(owner))))


@dataclasses.dataclass
class QuantumSnapshot:
    """Everything ``admit_quantum`` needs about one pool, snapshotted
    once per (pool, leg) batch by the gateway.  ``row_of`` maps
    entitlement name → row index in the arrays; ``weights`` holds the
    Eq. 1 row weights (pass them back to ``admit_quantum`` so the
    kernel and the ``running_min_priority`` seed share one array)."""

    names: list[str]
    row_of: dict[str, int]
    state: ControlState
    bucket_level: jax.Array
    in_flight: jax.Array
    kv_in_use: jax.Array
    weights: jax.Array
    pool_in_flight: int
    pool_resident: int
    pool_conc_cap: float
    running_min_priority: float
    pool_avg_slo: float


def quantum_snapshot(pool, now: float) -> QuantumSnapshot:
    """Snapshot a ``TokenPool`` for one batched admission quantum.
    Pure read (see :func:`arrays_from_pool`): the state arrays are
    views of the pool's resident arrays — no per-row Python gather
    (the name→slot map and name list are C-speed container copies, so
    a held snapshot stays internally consistent even if membership
    churns after it was taken).  Inside a quantum it is the
    ``gateway.snapshot`` span, which ends on the readback of the
    owner-min seed (:func:`owner_min`)."""
    with child("gateway.snapshot", pool.spec.name):
        state, levels, infl, kvu = arrays_from_pool(pool, now)
        row_of = dict(pool.store.slot_of)
        avg_slo = float(pool.pool_avg_slo())
        weights = priority_batch(state, jnp.float32(avg_slo),
                                 pool.spec.coefficients)
        return QuantumSnapshot(
            names=list(pool.store.live_names()),
            row_of=row_of,
            state=state,
            bucket_level=levels,
            in_flight=infl,
            kv_in_use=kvu,
            weights=weights,
            pool_in_flight=pool.pool_in_flight(),
            pool_resident=pool.total_resident(),
            pool_conc_cap=float(pool.capacity().concurrency),
            running_min_priority=_running_min_f32(pool, weights),
            pool_avg_slo=avg_slo,
        )
