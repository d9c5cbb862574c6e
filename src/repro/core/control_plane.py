"""The unified control plane — the ONE implementation of the paper's
capacity model (Eq. 1–3 + priority-weighted water-filling).

Every accounting tick in the system executes here: ``TokenPool.tick``
gathers its entitlement state into a :class:`ControlState` (array of
rows), runs :func:`control_tick` (a single fused, jit-compiled jnp op),
and scatters the results back into the ledger and per-entitlement
status.  ``PoolManager`` batches P pools into one
:func:`control_tick_pools` call (a ``vmap`` over an added pool axis),
so the whole fleet's accounting is one XLA dispatch.

The module also keeps :func:`reference_tick` — a deliberately naive
pure-Python replay of the same math built on the scalar oracle
functions in ``core.priority`` and ``core.pool.waterfill``.  It is the
TEST ORACLE (and the "paper-style per-entitlement loop" baseline in
``benchmarks/admission_throughput.py``); production code must never
call it.

Everything jnp here is pure-functional: state arrays in, state arrays
out.  Entitlements are rows; service classes are small int codes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.markers import kernel
from repro.core.types import PriorityCoefficients, ServiceClass

# class codes (row order matters: used for lookups)
CLASS_CODES: dict[ServiceClass, int] = {
    ServiceClass.DEDICATED: 0,
    ServiceClass.GUARANTEED: 1,
    ServiceClass.ELASTIC: 2,
    ServiceClass.SPOT: 3,
    ServiceClass.PREEMPTIBLE: 4,
}
CLASS_W = jnp.array([1000.0, 1000.0, 100.0, 1.0, 0.1])     # CLASS_WEIGHT
PROTECTED_MASK = jnp.array([True, True, False, False, False])
BURSTOK_MASK = jnp.array([True, False, True, True, True])   # Table 1 "Burst"
DEBTOK_MASK = jnp.array([False, False, True, False, False])  # debt classes
ELASTIC_MASK = jnp.array([False, False, True, False, False])

#: Python-side trace counters: a jitted kernel's body only executes as
#: Python while TRACING, so bumping a counter inside it counts compiled
#: variants.  Tests pin that entitlement churn within a pow2 resident
#: bucket never retraces (``tests/test_resident.py``).
TRACE_COUNTS: dict[str, int] = {"control_tick": 0, "admit_quantum": 0,
                                "shard_tick": 0, "shard_admit_quantum": 0,
                                "shard_plan_fleet": 0, "owner_min": 0}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ControlState:
    """Per-entitlement state-of-the-world, array-of-rows layout.

    The first five fields mirror the EntitlementSpec (static between
    membership changes); ``burst``/``debt`` are the Eq. 2–3 EWMAs that
    the tick evolves.  A leading pool axis turns this into the batched
    multi-pool state consumed by :func:`control_tick_pools`.
    """

    class_code: jax.Array        # int32 [N]
    bound: jax.Array             # bool  [N]
    baseline_tps: jax.Array      # f32 [N] λ_e
    baseline_kv: jax.Array       # f32 [N] χ_e
    baseline_conc: jax.Array     # f32 [N] r_e
    slo_ms: jax.Array            # f32 [N] ℓ*_e
    burst: jax.Array             # f32 [N] b_e
    debt: jax.Array              # f32 [N] d_e

    @property
    def n_rows(self) -> int:
        return self.class_code.shape[-1]


def priority_rows(state: ControlState, pool_avg_slo: jax.Array,
                  coeff: PriorityCoefficients) -> jax.Array:
    """Eq. (1), row-parallel."""
    w_class = CLASS_W[state.class_code]
    slo_f = 1.0 / (1.0 + coeff.alpha_slo * (state.slo_ms / pool_avg_slo))
    burst_f = 1.0 / (1.0 + coeff.alpha_burst
                     * jnp.maximum(state.burst, 0.0))
    debt_f = jnp.maximum(1e-3, 1.0 + coeff.alpha_debt * state.debt)
    return w_class * slo_f * burst_f * debt_f


def burst_delta_rows(used_tps: jax.Array, used_kv: jax.Array,
                     used_conc: jax.Array, state: ControlState) -> jax.Array:
    """Eq. (3), row-parallel, matching the scalar zero-baseline rule:
    a dimension with no baseline contributes 1 whenever it is used."""

    def term(used, base):
        return jnp.where(
            base > 0.0,
            jnp.maximum(0.0, used / jnp.maximum(base, 1e-30) - 1.0),
            jnp.where(used > 0.0, 1.0, 0.0))

    return (term(used_tps, state.baseline_tps)
            + term(used_kv, state.baseline_kv)
            + term(used_conc, state.baseline_conc))


def ewma(prev: jax.Array, x: jax.Array, gamma: float) -> jax.Array:
    """Eq. (2) form: γ·prev + (1−γ)·x."""
    return gamma * prev + (1.0 - gamma) * x


# -- shard-stable reductions --------------------------------------------------
#
# Every pool-level aggregate in the tick (protected floor, water-filling
# shares, demand totals) reduces the row axis with a FIXED binary tree
# over the pow2-padded rows instead of ``jnp.sum``'s backend-chosen
# order.  The pairing depends only on element POSITION, so any
# contiguous pow2 blocking of the rows computes bit-identical partials:
# per-shard subtrees plus the top tree over the gathered shard roots IS
# the full single-device tree.  That is what lets ``shard_plane`` run
# the same math under ``shard_map`` with ``axis_name`` set and return
# decisions bit-identical to the single-device kernel, without f64
# accumulation (x64 stays disabled) or Kahan compensation.

def _pairwise(x: jax.Array, op) -> jax.Array:
    """Reduce the trailing (pow2) axis with positional pairing."""
    while x.shape[-1] > 1:
        x = op(x[..., 0::2], x[..., 1::2])
    return x[..., 0]


def tree_sum(x: jax.Array, axis_name: str | None = None) -> jax.Array:
    """Binary-tree sum over the row axis; with ``axis_name`` the rows
    are a shard_map block and the shard roots combine through the top
    of the same tree (``all_gather`` orders roots by device index, i.e.
    block order).  Non-pow2 widths pad with zeros (exact for adds)."""
    w = bucket_width(x.shape[-1])
    if w != x.shape[-1]:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (w - x.shape[-1],), x.dtype)],
            axis=-1)
    local = _pairwise(x, jnp.add)
    if axis_name is None:
        return local
    return _pairwise(jax.lax.all_gather(local, axis_name), jnp.add)


def tree_any(x: jax.Array, axis_name: str | None = None) -> jax.Array:
    """Binary-tree logical-or over the row axis (pad with False)."""
    w = bucket_width(x.shape[-1])
    if w != x.shape[-1]:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (w - x.shape[-1],), bool)],
            axis=-1)
    local = _pairwise(x, jnp.logical_or)
    if axis_name is None:
        return local
    return _pairwise(jax.lax.all_gather(local, axis_name),
                     jnp.logical_or)


def tree_count(x: jax.Array, axis_name: str | None = None) -> jax.Array:
    """Row count of a bool mask as int32 (integer adds are exact, so
    any order agrees — the tree keeps the structure uniform)."""
    return tree_sum(x.astype(jnp.int32), axis_name)


def waterfill_rows(capacity: jax.Array, want: jax.Array,
                   weight: jax.Array, max_rounds: int = 32,
                   axis_name: str | None = None) -> jax.Array:
    """Priority-weighted progressive water-filling (jnp mirror of
    ``core.pool.waterfill``).  Runs the same cap-and-redistribute rounds
    inside a ``lax.while_loop``; converges in ≤ #distinct-caps rounds,
    bounded by ``max_rounds`` for compile-time safety.

    With ``axis_name`` the rows are one shard_map block: the per-round
    couplings (total weight, active count, filled total, the done /
    progress flags) combine across shards through the shard-stable tree
    reductions, and the loop state that the ``cond`` reads (remaining /
    round counter / any-active) is replicated — every device runs the
    same trip count."""
    want = jnp.maximum(want, 0.0)
    active0 = want > 1e-12

    def cond(state):
        alloc, remaining, active, i, has_active = state
        return (remaining > 1e-9) & has_active & (i < max_rounds)

    def body(state):
        alloc, remaining, active, i, _ = state
        w = jnp.where(active, weight, 0.0)
        total_w = tree_sum(w, axis_name)
        n_active = tree_count(active, axis_name)
        total_w_safe = jnp.where(total_w > 0.0, total_w, 1.0)
        share = jnp.where(
            total_w > 0.0,
            remaining * (w / total_w_safe),
            jnp.where(active, remaining / jnp.maximum(n_active, 1), 0.0))
        room = want - alloc
        take = jnp.minimum(room, share)
        take = jnp.where(active, take, 0.0)
        alloc = alloc + take
        remaining = remaining - tree_sum(take, axis_name)
        # done when the share covered the remaining room — compare take
        # to room with a magnitude-scaled epsilon (f32-safe; an absolute
        # 1e-12 misfires once want ≳ 1e2 in float32)
        newly_done = active & (take >= room
                               - 1e-6 * jnp.maximum(1.0, want))
        # scalar loop breaks when a round fills nobody
        progress = tree_any(newly_done, axis_name)
        active = active & ~newly_done
        i = jnp.where(progress, i + 1, max_rounds)
        return alloc, remaining, active, i, tree_any(active, axis_name)

    alloc0 = jnp.zeros_like(want)
    alloc, _, _, _, _ = jax.lax.while_loop(
        cond, body, (alloc0, jnp.maximum(capacity, 0.0), active0,
                     jnp.asarray(0), tree_any(active0, axis_name)))
    return alloc


def allocate_rows(capacity: jax.Array, state: ControlState,
                  weights: jax.Array, demand_tps: jax.Array,
                  axis_name: str | None = None) -> jax.Array:
    """Funding allocation with work conservation (the Table-1 ordering):
    protected funded at baseline (emergency-scaled if their *active* use
    exceeds capacity) → elastic demand-capped baselines water-filled →
    work-conserving backfill of the surplus to burst-eligible classes."""
    live = state.bound
    protected = live & PROTECTED_MASK[state.class_code]
    base_p = jnp.where(protected, state.baseline_tps, 0.0)
    active_p = jnp.minimum(base_p, jnp.where(protected, demand_tps, 0.0))
    total_active_p = tree_sum(active_p, axis_name)
    emergency = total_active_p > capacity
    scale = jnp.where(emergency,
                      capacity / jnp.maximum(total_active_p, 1e-30), 1.0)
    alloc_p = base_p * scale
    remaining = jnp.where(
        emergency, 0.0, jnp.maximum(0.0, capacity - total_active_p))

    elastic = live & ELASTIC_MASK[state.class_code]
    want_e = jnp.where(elastic,
                       jnp.minimum(state.baseline_tps, demand_tps), 0.0)
    fill_e = waterfill_rows(remaining, want_e,
                            jnp.where(elastic, weights, 0.0),
                            axis_name=axis_name)
    alloc = alloc_p + fill_e
    remaining = jnp.maximum(0.0, remaining - tree_sum(fill_e, axis_name))

    burst_ok = live & BURSTOK_MASK[state.class_code]
    used = jnp.where(protected, active_p,
                     jnp.minimum(alloc, demand_tps))
    want_b = jnp.where(burst_ok,
                       jnp.maximum(0.0, demand_tps - used), 0.0)
    fill_b = waterfill_rows(remaining, want_b,
                            jnp.where(burst_ok, weights, 0.0),
                            axis_name=axis_name)
    return alloc + fill_b


def _tick_impl(state: ControlState, capacity_tps: jax.Array,
               measured_tps: jax.Array, used_kv: jax.Array,
               used_conc: jax.Array, demand_tps: jax.Array,
               avg_slo_ms: jax.Array, coeff: PriorityCoefficients,
               axis_name: str | None = None,
               ) -> tuple[ControlState, jax.Array, jax.Array]:
    """Tick body shared by the single-pool and vmapped entry points.
    Mirrors the scalar controller's steps 2–5: burst EWMA → priority →
    allocation → debt EWMA."""
    TRACE_COUNTS["control_tick"] += 1          # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants
    delta = burst_delta_rows(measured_tps, used_kv, used_conc, state)
    burst = ewma(state.burst, delta, coeff.gamma_burst)
    s1 = dataclasses.replace(state, burst=burst)

    weights = priority_rows(s1, jnp.maximum(avg_slo_ms, 1e-9), coeff)
    alloc = allocate_rows(capacity_tps, s1, weights, demand_tps,
                          axis_name=axis_name)

    # Eq. 2 debt: underservice only counts against live demand, service
    # is the measured completion rate floored by demand-capped funding.
    served = jnp.maximum(measured_tps, jnp.minimum(alloc, demand_tps))
    entitled_now = jnp.minimum(s1.baseline_tps,
                               jnp.maximum(demand_tps, served))
    gap = jnp.where(
        (demand_tps > 1e-9) & (s1.baseline_tps > 0.0),
        (entitled_now - served) / jnp.maximum(s1.baseline_tps, 1e-30),
        0.0)
    gap = jnp.clip(gap, -coeff.gap_clip, coeff.gap_clip)
    debtok = DEBTOK_MASK[s1.class_code]
    debt = jnp.where(
        debtok,
        jnp.clip(ewma(s1.debt, gap, coeff.gamma_debt),
                 coeff.debt_min, coeff.debt_max),
        s1.debt)
    return dataclasses.replace(s1, debt=debt), alloc, weights


@kernel(oracle="repro.core.control_plane.reference_tick")
@partial(jax.jit, static_argnames=("coeff",))
def control_tick(state: ControlState, capacity_tps: jax.Array,
                 measured_tps: jax.Array, used_kv: jax.Array,
                 used_conc: jax.Array, demand_tps: jax.Array,
                 avg_slo_ms: jax.Array,
                 coeff: PriorityCoefficients = PriorityCoefficients(),
                 ) -> tuple[ControlState, jax.Array, jax.Array]:
    """One accounting tick for one pool, fused: returns (new state,
    allocations λ̂, priority weights).  ``avg_slo_ms`` is ℓ̄* — the
    caller owns the Fixed-vs-live-mean policy (PoolSpec.fixed_avg_slo_ms)."""
    return _tick_impl(state, capacity_tps, measured_tps, used_kv,
                      used_conc, demand_tps, avg_slo_ms, coeff)


@kernel(oracle="repro.core.control_plane.reference_tick")
@partial(jax.jit, static_argnames=("coeff",))
def control_tick_pools(states: ControlState, capacity_tps: jax.Array,
                       measured_tps: jax.Array, used_kv: jax.Array,
                       used_conc: jax.Array, demand_tps: jax.Array,
                       avg_slo_ms: jax.Array,
                       coeff: PriorityCoefficients = PriorityCoefficients(),
                       ) -> tuple[ControlState, jax.Array, jax.Array]:
    """Batched tick across P pools: every array carries a leading pool
    axis ([P, N] rows, [P] scalars) and the whole fleet ticks in one
    fused dispatch.  Pools with fewer rows are padded with unbound rows
    (see :func:`pad_state`) — padding provably cannot affect live rows
    because every mask is ANDed with ``bound``."""

    def one(s, cap, m, kv, conc, d, slo):
        return _tick_impl(s, cap, m, kv, conc, d, slo, coeff)

    return jax.vmap(one)(states, capacity_tps, measured_tps, used_kv,
                         used_conc, demand_tps, avg_slo_ms)


# -- padding / stacking helpers (PoolManager batching) -----------------------

def bucket_width(n_rows: int) -> int:
    """Next power of two ≥ ``n_rows`` (min 1).  Shapes are static under
    jit, so ticking on exact widths would retrace the kernel on every
    entitlement add/remove; padding to pow2 buckets bounds the number
    of compiled variants to log2(N) while padding stays inert."""
    return max(1, 1 << (max(n_rows, 1) - 1).bit_length())


def quantum_width(n_requests: int) -> int:
    """Pad width for the REQUEST axis of an admission quantum: pow2
    buckets up to 4096, quarter-steps (5/8, 6/8, 7/8 of the next
    pow2) between octaves above that.  Large quanta pay for every
    padded row inside the kernel scan, so capping the waste at 25%
    (instead of pow2's 100%) is a real throughput lever — at the cost
    of at most three extra compiled variants per octave, still
    O(log n) traces.  Small quanta keep pure pow2 widths: the
    no-retrace pins (and row-axis padding, which always uses
    :func:`bucket_width`) rely on them."""
    w = bucket_width(n_requests)
    if n_requests > 4096:
        step = w >> 3
        for num in (5, 6, 7):
            c = step * num
            if n_requests <= c:
                return c
    return w


def pad_rows(x: jax.Array, n_rows: int, fill=0) -> jax.Array:
    """Right-pad a row vector to ``n_rows`` (the single source of the
    padding idiom — ``pad_state``, ``PoolManager.tick`` and the
    gateway's quantum batches all bucket through this)."""
    n = x.shape[0]
    if n == n_rows:
        return x
    return jnp.concatenate(
        [x, jnp.full((n_rows - n,), fill, dtype=x.dtype)])


def pad_state(state: ControlState, n_rows: int) -> ControlState:
    """Right-pad a state to ``n_rows`` with inert rows: unbound, zero
    baselines, class 0.  Unbound rows are excluded from every allocation
    mask and their EWMAs see zero inputs, so they stay identically zero."""
    if state.n_rows == n_rows:
        return state
    return ControlState(
        class_code=pad_rows(state.class_code, n_rows),
        bound=pad_rows(state.bound, n_rows, False),
        baseline_tps=pad_rows(state.baseline_tps, n_rows),
        baseline_kv=pad_rows(state.baseline_kv, n_rows),
        baseline_conc=pad_rows(state.baseline_conc, n_rows),
        slo_ms=pad_rows(state.slo_ms, n_rows, 1.0),
        burst=pad_rows(state.burst, n_rows),
        debt=pad_rows(state.debt, n_rows),
    )


def stack_states(states: Sequence[ControlState],
                 width: int = 0) -> ControlState:
    """Stack per-pool states (padded to a common width — at least the
    widest state; pass ``width`` to bucket it) along a new leading
    pool axis."""
    width = max(width, max(s.n_rows for s in states))
    padded = [pad_state(s, width) for s in states]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


# -- the scalar test oracle ---------------------------------------------------

@dataclasses.dataclass
class OracleRow:
    """One entitlement row for :func:`reference_tick` — plain floats."""

    service_class: ServiceClass
    bound: bool
    baseline_tps: float
    baseline_kv: float
    baseline_conc: float
    slo_ms: float
    burst: float
    debt: float
    measured_tps: float = 0.0
    used_kv: float = 0.0
    used_conc: float = 0.0
    demand_tps: float = 0.0


def reference_tick(rows: list[OracleRow], capacity_tps: float,
                   avg_slo_ms: float,
                   coeff: PriorityCoefficients = PriorityCoefficients(),
                   ) -> tuple[list[OracleRow], list[float], list[float]]:
    """Pure-Python per-entitlement replay of the tick — the TEST ORACLE.

    Exactly the pre-unification ``TokenPool.tick`` steps 2–5: a dict
    loop over ``core.priority`` Eq. 1–3 plus ``core.pool.waterfill``.
    Returns (updated rows, allocations, priority weights) in row order.
    O(N) Python — this is the paper-style baseline the unified tick is
    benchmarked against; never call it from the serving path.
    """
    from repro.core import priority as prio
    from repro.core.pool import waterfill
    from repro.core.types import (
        BURST_CLASSES,
        DEBT_CLASSES,
        PROTECTED_CLASSES,
        Resources,
    )

    rows = [dataclasses.replace(r) for r in rows]
    idx = list(range(len(rows)))

    # burst EWMA (Eq. 3) then priority (Eq. 1)
    weights: list[float] = []
    for r in rows:
        delta = prio.burst_overconsumption(
            Resources(r.measured_tps, r.used_kv, r.used_conc),
            Resources(r.baseline_tps, r.baseline_kv, r.baseline_conc))
        r.burst = prio.burst_update(r.burst, delta, coeff.gamma_burst)
        weights.append(prio.priority_weight(
            r.service_class, r.slo_ms, max(avg_slo_ms, 1e-9),
            r.burst, r.debt, coeff))

    # allocation: protected reserved → elastic baselines → backfill
    alloc = [0.0] * len(rows)
    live = [i for i in idx if rows[i].bound]
    protected = [i for i in live
                 if rows[i].service_class in PROTECTED_CLASSES]
    base_p = {i: rows[i].baseline_tps for i in protected}
    active_p = {i: min(base_p[i], rows[i].demand_tps) for i in protected}
    total_active_p = sum(active_p.values())
    if total_active_p > capacity_tps and total_active_p > 0:
        scale = capacity_tps / total_active_p
        for i in protected:
            alloc[i] = base_p[i] * scale
        remaining = 0.0
    else:
        for i in protected:
            alloc[i] = base_p[i]
        remaining = max(0.0, capacity_tps - total_active_p)

        elastic = [i for i in live
                   if rows[i].service_class is ServiceClass.ELASTIC]
        want_e = {i: min(rows[i].baseline_tps, rows[i].demand_tps)
                  for i in elastic}
        fill = waterfill(remaining, want_e, {i: weights[i] for i in elastic})
        for i in elastic:
            alloc[i] = fill[i]
        remaining = max(0.0, remaining - sum(fill.values()))

        burst_ok = [i for i in live
                    if rows[i].service_class in BURST_CLASSES]
        want_b = {}
        for i in burst_ok:
            used = (active_p[i] if i in active_p
                    else min(alloc[i], rows[i].demand_tps))
            want_b[i] = max(0.0, rows[i].demand_tps - used)
        fill = waterfill(remaining, want_b, {i: weights[i] for i in burst_ok})
        for i in burst_ok:
            alloc[i] += fill[i]

    # debt EWMA (Eq. 2) for debt-bearing classes
    for i, r in enumerate(rows):
        if r.service_class not in DEBT_CLASSES:
            continue
        demand, base = r.demand_tps, r.baseline_tps
        if demand <= 1e-9 or base <= 0.0:
            gap = 0.0
        else:
            served = max(r.measured_tps, min(alloc[i], demand))
            entitled_now = min(base, max(demand, served))
            gap = (entitled_now - served) / base
        gap = min(coeff.gap_clip, max(-coeff.gap_clip, gap))
        r.debt = min(coeff.debt_max, max(
            coeff.debt_min, prio.debt_update(r.debt, gap, coeff.gamma_debt)))
    return rows, alloc, weights


def state_from_rows(rows: Sequence[OracleRow]) -> ControlState:
    """Build a ControlState from oracle rows (tests/benchmarks)."""
    return ControlState(
        class_code=jnp.array([CLASS_CODES[r.service_class] for r in rows],
                             jnp.int32),
        bound=jnp.array([r.bound for r in rows], bool),
        baseline_tps=jnp.array([r.baseline_tps for r in rows], jnp.float32),
        baseline_kv=jnp.array([r.baseline_kv for r in rows], jnp.float32),
        baseline_conc=jnp.array([r.baseline_conc for r in rows],
                                jnp.float32),
        slo_ms=jnp.array([r.slo_ms for r in rows], jnp.float32),
        burst=jnp.array([r.burst for r in rows], jnp.float32),
        debt=jnp.array([r.debt for r in rows], jnp.float32),
    )
