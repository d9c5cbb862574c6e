"""Fleet capacity planner — entitlement-driven autoscaling + cross-pool
rebalancing on the vectorized control plane.

The paper's central claim is that token pools authorize *both*
admission and autoscaling from one capacity model.  This module is the
autoscaling half at FLEET scale: :func:`plan_fleet` consumes the
per-pool signals the batched accounting tick already produces (demand
EWMA, reserved baselines, replica bounds) and emits, in ONE fused
jit/vmapped dispatch for the whole fleet, a :class:`ScaleDecision` per
pool — the reserved-floor + headroom-on-demand policy with scale-down
hysteresis of the scalar ``core.autoscaler`` (which survives as the
single-pool PARITY ORACLE; ``tests/test_fleet.py`` pins the two
decision-identical).

On top of the scale decisions, :class:`FleetPlanner` proposes
cross-pool REBALANCES: an ELASTIC/SPOT entitlement that stays
underserved on a scarce pool (debt above threshold, or allocation
persistently below its demand) for ``starve_persistence_ticks``
consecutive plans is migrated to the slack pool with the most headroom
(capacity-aware pool selection in the spirit of token-budget-aware
pool routing; debt-based fairness per VTC).

Migration invariants (``TokenPool.detach_entitlement`` /
``attach_entitlement``, applied by ``PoolManager.migrate_entitlement``):

  * the ledger bucket moves with its ACCRUED LEVEL and outstanding
    charges — no budget is minted or burned by a move (the burst
    window re-bases to the target ledger, clamping if smaller);
  * ``EntitlementStatus`` moves verbatim — debt, burst and usage
    counters carry, so an underserved tenant arrives at the target
    with the compensatory priority it is owed (cross-pool debt);
  * in-flight records move — completions settle on the NEW owner,
    which also holds their charges;
  * the demand EWMA moves — the target's next tick sees the real
    demand instead of a cold start;
  * the source lease is released before the target lease is
    submitted; the target's authorized ceiling is raised first
    (``PoolManager.migrate_entitlement``) so a planner-shrunk target
    does not spuriously degrade the arrival.

The closed control loop this enables (wired through
``PoolManager.plan_quantum``):

  admission → batched tick → plan_fleet → authorize/provision →
  admission

— the same signals that deny spot traffic also raise capacity, which
is the paper's consistency story (``benchmarks/experiment3_autoscale``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import control_plane
from repro.core.autoscaler import ScaleDecision, replicas_for
from repro.core.markers import hot_path, kernel
from repro.core.pool import TickRecord, TokenPool
from repro.core.types import Resources, ServiceClass
from repro.telemetry.spans import child, moved_to_device, readback

#: Reason codes emitted by :func:`plan_fleet` (index = code), matching
#: the scalar ``Autoscaler.plan`` reason strings.
REASONS = ("steady", "scale_up:reserved", "scale_up:demand",
           "hold:cooldown", "scale_down")
_STEADY, _UP_RESERVED, _UP_DEMAND, _HOLD, _DOWN = range(5)

_ELASTIC = control_plane.CLASS_CODES[ServiceClass.ELASTIC]
_SPOT = control_plane.CLASS_CODES[ServiceClass.SPOT]


@dataclasses.dataclass(frozen=True)
class FleetPlannerConfig:
    """Scale policy (identical semantics to ``AutoscalerConfig``) plus
    the rebalance policy knobs.  Frozen → usable as a static jit arg."""

    headroom: float = 1.2          # demand multiplier before scaling
    demand_ewma: float = 0.5       # smoothing of the demand signal
    cooldown_ticks: int = 5        # consecutive low ticks before shrink
    #: elastic entitlements migrate once their debt EWMA crosses this
    debt_migrate_threshold: float = 0.25
    #: spot entitlements count as starved when alloc < frac · demand
    starve_frac: float = 0.5
    #: consecutive starved plans before a migration is proposed
    starve_persistence_ticks: int = 3
    #: plans an entitlement is pinned to its pool after migrating
    migrate_cooldown_ticks: int = 10
    #: migrations proposed per scarce pool per plan (anti-thrash)
    max_migrations_per_pool: int = 1


@dataclasses.dataclass(frozen=True)
class RebalanceProposal:
    """Move ``entitlement`` from the scarce ``src`` to the slack
    ``dst``, carrying ``debt`` (the Eq. 2 EWMA at proposal time)."""

    entitlement: str
    src: str
    dst: str
    debt: float
    baseline_tps: float
    reason: str                     # "debt" | "starved_demand"


@dataclasses.dataclass
class FleetPlan:
    """One planning round: per-pool decisions + rebalance proposals.
    ``applied``/``preempted`` are filled by ``PoolManager.plan_quantum``
    when the plan is executed."""

    decisions: dict[str, ScaleDecision]
    migrations: list[RebalanceProposal]
    #: replicas the fleet cannot place (need beyond maxReplicas), tok/s
    #: equivalent — scarcity observability, keyed by pool
    unmet_replicas: dict[str, float]
    applied: list[RebalanceProposal] = dataclasses.field(
        default_factory=list)
    #: proposals NOT applied because the destination pool lost its
    #: replicas between planning and execution (same-quantum failure) —
    #: the entitlement stays put rather than migrating into a dead pool
    skipped: list[RebalanceProposal] = dataclasses.field(
        default_factory=list)
    preempted: dict[str, list[str]] = dataclasses.field(
        default_factory=dict)
    #: pools whose AUTHORIZED replica count moved this round, as
    #: (old, new) — one entry per actual scaling event, unlike the
    #: per-round decisions which repeat desired > current every tick
    #: while provisioning lag is converging
    scale_events: dict[str, tuple[int, int]] = dataclasses.field(
        default_factory=dict)
    #: elastic/spot rows starved this round, by (pool, reason) with
    #: reason "debt" or "starved_demand" — the rows the rebalance pass
    #: handles one by one
    starved_rows: dict[tuple[str, str], int] = dataclasses.field(
        default_factory=dict)


def _plan_one(current, lo, hi, per_tps, per_kv, per_conc,
              res_tps, res_kv, res_conc, demand, ewma_prev, seeded,
              low_ticks, config: FleetPlannerConfig):
    """Scale policy for ONE pool — the jnp mirror of the scalar
    ``Autoscaler.observe_demand`` + ``Autoscaler.plan`` pair.
    ``plan_fleet`` vmaps this over the pool axis."""
    g = config.demand_ewma
    ewma = jnp.where(seeded, g * ewma_prev + (1.0 - g) * demand, demand)

    def dim(need, per):
        return jnp.where(per > 0.0, need / jnp.maximum(per, 1e-30),
                         jnp.where(need > 0.0, jnp.inf, 0.0))

    need_reserved = jnp.maximum(
        dim(res_tps, per_tps),
        jnp.maximum(dim(res_kv, per_kv), dim(res_conc, per_conc)))
    need_demand = dim(ewma * config.headroom, per_tps)
    need = jnp.maximum(need_reserved, need_demand)
    # an unsatisfiable dimension (need inf) must clamp UP to hi, not
    # wrap through the int cast — bound the ceil operand first
    desired = jnp.maximum(
        1, jnp.ceil(jnp.minimum(need, 1e9)).astype(jnp.int32))
    desired = jnp.clip(desired, lo, hi)

    scale_up = desired > current
    scale_dn = desired < current
    hold = scale_dn & (low_ticks + 1 < config.cooldown_ticks)
    new_low = jnp.where(hold, low_ticks + 1, 0)
    desired = jnp.where(hold, current, desired)
    reason = jnp.where(
        scale_up,
        jnp.where(need_demand > need_reserved, _UP_DEMAND, _UP_RESERVED),
        jnp.where(hold, _HOLD, jnp.where(scale_dn, _DOWN, _STEADY)))
    return desired, reason.astype(jnp.int32), ewma, new_low, need


@kernel(oracle="repro.core.autoscaler.Autoscaler.plan")
@partial(jax.jit, static_argnames=("config",))
def plan_fleet(current: jax.Array, lo: jax.Array, hi: jax.Array,
               per_tps: jax.Array, per_kv: jax.Array, per_conc: jax.Array,
               res_tps: jax.Array, res_kv: jax.Array, res_conc: jax.Array,
               demand_tps: jax.Array, ewma_prev: jax.Array,
               seeded: jax.Array, low_ticks: jax.Array,
               config: FleetPlannerConfig = FleetPlannerConfig(),
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                          jax.Array]:
    """One fused scale plan for the WHOLE fleet.

    Every argument carries a leading pool axis ([P]); the returns are
    ``(desired, reason_code, demand_ewma, low_ticks, need_replicas)``,
    all [P].  ``need_replicas`` is the unclamped fractional requirement
    — ``need > hi`` means the pool is SCARCE (feeds the rebalancer),
    ``need < hi`` leaves slack.  Padding rows (see
    ``FleetPlanner._arrays``) use per_replica 1 / bounds [1, 1] so they
    stay inert and finite."""

    def one(c, l, h, pt, pk, pc, rt, rk, rc, d, e, s, lt):
        return _plan_one(c, l, h, pt, pk, pc, rt, rk, rc, d, e, s, lt,
                         config)

    return jax.vmap(one)(current, lo, hi, per_tps, per_kv, per_conc,
                         res_tps, res_kv, res_conc, demand_tps,
                         ewma_prev, seeded, low_ticks)


def _reserve_replicas(espec, pool: TokenPool) -> float:
    """Replica cost of hosting an entitlement's reserve on ``pool`` —
    the same rule the virtual-node lease uses: spot/preemptible
    reserve nothing."""
    if espec.qos.service_class in (ServiceClass.SPOT,
                                   ServiceClass.PREEMPTIBLE):
        return 0.0
    return max(0.0, replicas_for(espec.baseline, pool.spec.per_replica))


@dataclasses.dataclass
class _PoolPlanState:
    """Planner-side hysteresis state for one pool."""

    ewma: float = 0.0
    seeded: bool = False
    low_ticks: int = 0


class FleetPlanner:
    """Stateful shell around :func:`plan_fleet` + the rebalancer.

    Holds the per-pool demand EWMA / cooldown state and the
    per-entitlement starvation counters between plans; each
    :meth:`plan` call gathers the fleet's signals, runs ONE fused
    kernel dispatch (padded to a power-of-two pool bucket so fleet
    membership churn does not retrace it), and derives rebalance
    proposals from the scarcity outputs."""

    def __init__(self, config: Optional[FleetPlannerConfig] = None
                 ) -> None:
        self.config = (config if config is not None
                       else FleetPlannerConfig())
        self._state: dict[str, _PoolPlanState] = {}
        self._starved: dict[str, int] = {}          # entitlement → plans
        self._cooldown: dict[str, int] = {}         # entitlement → plans
        self._plans = 0

    # -- signal gathering ------------------------------------------------------
    @staticmethod
    def pool_demand(pool: TokenPool,
                    record: Optional[TickRecord]) -> float:
        """Total demand (tok/s) — the sum of the demand EWMAs the tick
        emits (admitted + denied demand, so denial pressure raises
        capacity).  Without a tick record this is one masked column sum
        over the pool's resident arrays (``demand_total_tps``), not a
        per-name dict walk."""
        if record is None:
            return pool.demand_total_tps()
        demand = record.column("demand_tps")
        values = (record.demand_tps.values() if demand is None
                  else demand.tolist())
        # Python's float sum (compensated) in row order: the bits of
        # summing the record's dict, which ``np.sum`` would not give
        return float(sum(values))

    @staticmethod
    def _record_rows(pool: TokenPool, record: Optional[TickRecord],
                     slots: np.ndarray
                     ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """The record's (demand, allocation) in float64 over the pool's
        live ``slots``, 0 for a row the record lacks; None without a
        record.  A record from a tick over today's membership carries
        the store's cached live-name list, so its arrays line up as
        they are; otherwise (rows added or removed since the tick, a
        record built from dicts) they are aligned by name."""
        if record is None:
            return None
        store = pool.store
        demand = record.column("demand_tps")
        alloc = record.column("allocations")
        if demand is not None and record.names is store.live_names():
            return (np.asarray(demand, np.float64),
                    np.asarray(alloc, np.float64))
        if demand is None:
            names = store.live_names()
            want, got = record.demand_tps, record.allocations
            return (np.fromiter((want.get(n, 0.0) for n in names),
                                np.float64, len(names)),
                    np.fromiter((got.get(n, 0.0) for n in names),
                                np.float64, len(names)))
        rows = np.fromiter((store.slot_of.get(n, -1) for n in record.names),
                           np.int64, len(record.names))
        kept = rows >= 0
        out = []
        for col in (demand, alloc):
            full = np.zeros(store.capacity, np.float64)
            full[rows[kept]] = np.asarray(col, np.float64)[kept]
            out.append(full[slots])
        return out[0], out[1]

    def _starved_rows(self, pool: TokenPool,
                      record: Optional[TickRecord]
                      ) -> tuple[np.ndarray, np.ndarray]:
        """The pool's elastic/spot rows that are starved now, as
        (store slots, reason is "debt") — the reason "starved_demand"
        otherwise.  Debt is compared in float64, as ``float(debt)``
        against the threshold."""
        cfg = self.config
        c = pool.store.col
        slots = pool.store.live_slots()
        code = c["class_code"][slots]
        elastic = code == _ELASTIC
        debt = elastic & (c["debt"][slots].astype(np.float64)
                          >= cfg.debt_migrate_threshold)
        starved = debt
        rows = self._record_rows(pool, record, slots)
        if rows is not None:
            demand, alloc = rows
            starved = debt | ((elastic | (code == _SPOT))
                              & (demand > 1e-9)
                              & (alloc < cfg.starve_frac * demand))
        hit = np.flatnonzero(starved)
        return slots[hit], debt[hit]

    def _arrays(self, pools: dict[str, TokenPool],
                records: dict[str, TickRecord]) -> tuple[list, dict]:
        names = sorted(pools)
        width = control_plane.bucket_width(len(names))
        f32 = lambda fill: np.full(width, fill, np.float32)   # noqa: E731
        i32 = lambda fill: np.full(width, fill, np.int32)     # noqa: E731
        arr = {
            "current": i32(1), "lo": i32(1), "hi": i32(1),
            "per_tps": f32(1.0), "per_kv": f32(1.0), "per_conc": f32(1.0),
            "res_tps": f32(0.0), "res_kv": f32(0.0), "res_conc": f32(0.0),
            "demand_tps": f32(0.0), "ewma_prev": f32(0.0),
            "seeded": np.zeros(width, bool), "low_ticks": i32(0),
        }
        for i, name in enumerate(names):
            pool = pools[name]
            st = self._state.setdefault(name, _PoolPlanState())
            reserved = pool.reserved_baseline()
            per = pool.spec.per_replica
            arr["current"][i] = pool.replicas
            arr["lo"][i] = pool.spec.scaling.min_replicas
            arr["hi"][i] = pool.spec.scaling.max_replicas
            arr["per_tps"][i] = per.tokens_per_second
            arr["per_kv"][i] = per.kv_bytes
            arr["per_conc"][i] = per.concurrency
            arr["res_tps"][i] = reserved.tokens_per_second
            arr["res_kv"][i] = reserved.kv_bytes
            arr["res_conc"][i] = reserved.concurrency
            arr["demand_tps"][i] = self.pool_demand(
                pool, records.get(name))
            arr["ewma_prev"][i] = st.ewma
            arr["seeded"][i] = st.seeded
            arr["low_ticks"][i] = st.low_ticks
        return names, arr

    # -- the plan --------------------------------------------------------------
    def plan(self, pools: dict[str, TokenPool],
             records: Optional[dict[str, TickRecord]] = None,
             now: float = 0.0) -> FleetPlan:
        """One planning round over the fleet: ONE ``plan_fleet``
        dispatch (the ``fleet.kernel`` span, upload through readback)
        + the rebalance pass over columns and tick arrays
        (``fleet.rebalance``)."""
        records = records or {}
        self._plans += 1
        # drop state of pools that left the fleet
        for gone in set(self._state) - set(pools):
            del self._state[gone]
        if not pools:
            return FleetPlan(decisions={}, migrations=[],
                             unmet_replicas={})
        names, arr = self._arrays(pools, records)
        with child("fleet.kernel"):
            dev = {k: jnp.asarray(v) for k, v in arr.items()}
            moved_to_device(sum(x.nbytes for x in dev.values()))
            out = plan_fleet(**dev, config=self.config)
            desired, reason, ewma, low, need = (readback(x) for x in out)

        decisions: dict[str, ScaleDecision] = {}
        unmet: dict[str, float] = {}
        for i, name in enumerate(names):
            st = self._state[name]
            st.ewma = float(ewma[i])
            st.seeded = True
            st.low_ticks = int(low[i])
            decisions[name] = ScaleDecision(
                current=int(arr["current"][i]), desired=int(desired[i]),
                reserved_tps=float(arr["res_tps"][i]),
                demand_tps=float(ewma[i]),
                reason=REASONS[int(reason[i])], pool=name)
            over = float(need[i]) - float(arr["hi"][i])
            if over > 1e-6:
                unmet[name] = over
        with child("fleet.rebalance"):
            migrations, starved = self._rebalance(pools, records, names,
                                                  need, arr)
        return FleetPlan(decisions=decisions, migrations=migrations,
                         unmet_replicas=unmet, starved_rows=starved)

    # -- rebalancing -----------------------------------------------------------
    @hot_path
    def _rebalance(self, pools: dict[str, TokenPool],
                   records: dict[str, TickRecord], names: list[str],
                   need: np.ndarray, arr: dict
                   ) -> tuple[list[RebalanceProposal],
                              dict[tuple[str, str], int]]:
        """Rebalance proposals, and the count of starved rows by (pool,
        reason).  The starvation flags are masks over each pool's
        columns and its tick record; Python touches only the rows that
        are starved now.  Entitlement names are unique in the fleet."""
        cfg = self.config
        hi = {n: float(arr["hi"][i]) for i, n in enumerate(names)}
        need_by = {n: float(need[i]) for i, n in enumerate(names)}
        slack = {n: hi[n] - need_by[n] for n in names}

        # 1. persistence counters: one more plan for each starved row,
        #    and every other entitlement's counter dropped
        prev, self._starved = self._starved, {}
        starved: dict[str, tuple[list[str], np.ndarray, np.ndarray]] = {}
        counts: dict[tuple[str, str], int] = {}
        for pname in names:
            pool = pools[pname]
            slots, is_debt = self._starved_rows(pool, records.get(pname))
            name_of = pool.store.name_of
            ents = [name_of[s] for s in slots.tolist()]
            for ent in ents:
                self._starved[ent] = prev.get(ent, 0) + 1
            starved[pname] = (ents, slots, is_debt)
            n_debt = int(np.count_nonzero(is_debt))
            counts[(pname, "debt")] = n_debt
            counts[(pname, "starved_demand")] = len(ents) - n_debt

        # 2. proposals: scarce pools shed their most-indebted starved
        #    entitlements onto the slackest pool that can hold them
        proposals: list[RebalanceProposal] = []
        for src in names:
            if need_by[src] <= hi[src] + 1e-6:
                continue                             # not scarce
            pool = pools[src]
            ents, slots, is_debt = starved[src]
            candidates = []
            for ent, debt, by_debt in zip(
                    ents, pool.store.col["debt"][slots].tolist(),
                    is_debt.tolist()):
                if self._starved.get(ent, 0) < cfg.starve_persistence_ticks:
                    continue
                if self._plans - self._cooldown.get(ent, -10**9) \
                        < cfg.migrate_cooldown_ticks:
                    continue
                candidates.append(
                    (debt, ent, "debt" if by_debt else "starved_demand"))
            candidates.sort(key=lambda c: (-c[0], c[1]))
            moved = 0
            for debt, ent, why in candidates:
                if moved >= cfg.max_migrations_per_pool:
                    break
                espec = pool.entitlements[ent]
                dst = self._pick_target(pools, names, src, espec, slack)
                if dst is None:
                    continue
                slack[dst] -= _reserve_replicas(espec,
                                                pools[dst])
                self._cooldown[ent] = self._plans
                self._starved.pop(ent, None)
                proposals.append(RebalanceProposal(
                    entitlement=ent, src=src, dst=dst, debt=float(debt),
                    baseline_tps=espec.baseline.tokens_per_second,
                    reason=why))
                moved += 1
        return proposals, counts

    def _pick_target(self, pools: dict[str, TokenPool], names: list[str],
                     src: str, espec, slack: dict[str, float]
                     ) -> Optional[str]:
        """Slackest pool (≠ src) whose remaining headroom under
        maxReplicas can absorb the entitlement's baseline reserve."""
        best, best_slack = None, 0.0
        for dst in names:
            if dst == src:
                continue
            remaining = slack[dst] - _reserve_replicas(espec, pools[dst])
            if remaining < -1e-6:
                continue
            if best is None or slack[dst] > best_slack:
                best, best_slack = dst, slack[dst]
        return best


class ReferenceFleetPlanner(FleetPlanner):
    """The rebalance pass as a per-entitlement loop over names, status
    views and the record's dicts: the parity oracle of
    :class:`FleetPlanner`'s array pass, called only by tests (as
    ``control_plane.reference_tick`` is).  Same kernel, same state."""

    @staticmethod
    def pool_demand(pool: TokenPool,
                    record: Optional[TickRecord]) -> float:
        if record is None:
            return pool.demand_total_tps()
        return float(sum(record.demand_tps.values()))

    def _starvation(self, pool: TokenPool, name: str,
                    record: Optional[TickRecord]) -> Optional[str]:
        """Starvation signal for one elastic/spot entitlement, or None."""
        st = pool.status[name]
        klass = pool.entitlements[name].qos.service_class
        if klass is ServiceClass.ELASTIC \
                and st.debt >= self.config.debt_migrate_threshold:
            return "debt"
        if record is None:
            return None
        demand = record.demand_tps.get(name, 0.0)
        alloc = record.allocations.get(name, 0.0)
        if demand > 1e-9 and alloc < self.config.starve_frac * demand:
            return "starved_demand"
        return None

    def _rebalance(self, pools: dict[str, TokenPool],
                   records: dict[str, TickRecord], names: list[str],
                   need: np.ndarray, arr: dict
                   ) -> tuple[list[RebalanceProposal],
                              dict[tuple[str, str], int]]:
        cfg = self.config
        hi = {n: float(arr["hi"][i]) for i, n in enumerate(names)}
        need_by = {n: float(need[i]) for i, n in enumerate(names)}
        slack = {n: hi[n] - need_by[n] for n in names}

        # 1. persistence counters for every migratable entitlement
        live: set[str] = set()
        counts: dict[tuple[str, str], int] = {}
        for pname in names:
            pool = pools[pname]
            rec = records.get(pname)
            counts[(pname, "debt")] = counts[(pname, "starved_demand")] = 0
            for ent, espec in pool.entitlements.items():
                if espec.qos.service_class not in (ServiceClass.ELASTIC,
                                                   ServiceClass.SPOT):
                    continue
                live.add(ent)
                why = self._starvation(pool, ent, rec)
                if why is not None:
                    counts[(pname, why)] += 1
                    self._starved[ent] = self._starved.get(ent, 0) + 1
                else:
                    self._starved.pop(ent, None)
        for gone in set(self._starved) - live:
            del self._starved[gone]

        # 2. proposals: scarce pools shed their most-indebted starved
        #    entitlements onto the slackest pool that can hold them
        proposals: list[RebalanceProposal] = []
        for src in names:
            if need_by[src] <= hi[src] + 1e-6:
                continue                             # not scarce
            pool = pools[src]
            rec = records.get(src)
            candidates = []
            for ent, espec in pool.entitlements.items():
                if espec.qos.service_class not in (ServiceClass.ELASTIC,
                                                   ServiceClass.SPOT):
                    continue
                if self._starved.get(ent, 0) < cfg.starve_persistence_ticks:
                    continue
                if self._plans - self._cooldown.get(ent, -10**9) \
                        < cfg.migrate_cooldown_ticks:
                    continue
                why = self._starvation(pool, ent, rec)
                if why is None:
                    continue
                candidates.append((pool.status[ent].debt,
                                   ent, espec, why))
            candidates.sort(key=lambda c: (-c[0], c[1]))
            moved = 0
            for debt, ent, espec, why in candidates:
                if moved >= cfg.max_migrations_per_pool:
                    break
                dst = self._pick_target(pools, names, src, espec, slack)
                if dst is None:
                    continue
                slack[dst] -= _reserve_replicas(espec,
                                                pools[dst])
                self._cooldown[ent] = self._plans
                self._starved.pop(ent, None)
                proposals.append(RebalanceProposal(
                    entitlement=ent, src=src, dst=dst, debt=float(debt),
                    baseline_tps=espec.baseline.tokens_per_second,
                    reason=why))
                moved += 1
        return proposals, counts
