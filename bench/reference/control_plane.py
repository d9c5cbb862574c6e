"""Plain reference of the token-pool control plane's semantics.

A straightforward float64 re-statement of what the system under test
promises, written from its documented rules (the paper's §4.3 admission
pipeline, Eqs. 1-3, priority-weighted water-filling, token buckets and
the reserved-floor scale policy).  It imports nothing of the program and
takes nothing the program made: it is built from the same plain
entitlement specs the benchmark builds the fleet from, and it is driven
by the event log of a run (quanta with their ``now``, the decisions the
program returned, dispatches, completions, ticks and plans).

The admission side is *teacher-forced*: the reference applies the
program's own decision to its state after judging it, as a served
model's reference is fed the served tokens.  For every decision it
returns how far the decision lies from being right under the reference
state (0 when it is right), so rounding in the program shows as a small
number and a wrong decision as a large one.

``rnd`` rounds every stored quantity: the identity gives the reference,
:func:`bfloat16` gives the control (the same reference kept at the next
precision below the program's float32).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

#: service classes in code order
CLASSES = ("dedicated", "guaranteed", "elastic", "spot", "preemptible")
CLASS_W = np.array([1000.0, 1000.0, 100.0, 1.0, 0.1])
PROTECTED = np.array([True, True, False, False, False])
BURST_OK = np.array([True, False, True, True, True])
DEBT_OK = np.array([False, False, True, False, False])
RESERVING = np.array([True, True, True, False, False])
SPOT = CLASSES.index("spot")

#: decision codes (the program's deny reasons, in check order), and a
#: denial whose reason the program's answer does not carry (a route's
#: later legs)
ADMIT, NOT_BOUND, CONCURRENCY, TOKEN_BUDGET, LOW_PRIORITY, DENY_ANY = \
    range(6)
REASONS = {None: ADMIT, "entitlement_not_bound": NOT_BOUND,
           "concurrency_limit": CONCURRENCY, "token_budget": TOKEN_BUDGET,
           "low_priority": LOW_PRIORITY}


@dataclasses.dataclass(frozen=True)
class Coeff:
    """Eq. 1-3 coefficients (the paper's defaults)."""

    alpha_slo: float = 2.0
    alpha_burst: float = 1.0
    alpha_debt: float = 4.0
    gamma_debt: float = 0.7
    gamma_burst: float = 0.7
    gap_clip: float = 1.0
    debt_min: float = -0.15
    debt_max: float = 2.0


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """The scale policy: reserved floor plus headroom on demand."""

    headroom: float = 1.2
    demand_ewma: float = 0.5
    cooldown_ticks: int = 5


def exact(x):
    return x


def bfloat16(x):
    """Round to bfloat16 (8 significant bits, round to nearest even)."""
    a = np.asarray(x, np.float64).astype(np.float32)
    bits = a.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = bits.astype(np.uint32).view(np.float32).astype(np.float64)
    return out if np.ndim(x) else float(out)


class RefPool:
    """One pool: its entitlements as float64 rows, its token buckets,
    in-flight records and tick state."""

    def __init__(self, spec: dict, ents: list[dict], rnd=exact) -> None:
        self.name = spec["name"]
        self.rnd = rnd
        self.coeff = Coeff(**spec.get("coefficients", {}))
        self.replicas = int(spec["replicas"])
        self.max_replicas = int(spec["max_replicas"])
        self.min_replicas = int(spec["min_replicas"])
        self.per = np.array(spec["per_replica"], np.float64)  # tps, kv, conc
        self.window = float(spec["bucket_window_s"])
        self.interval = float(spec["accounting_interval_s"])
        self.slack = float(spec.get("admission_slack", 0.0))
        self.names = [e["name"] for e in ents]
        self.row = {n: i for i, n in enumerate(self.names)}
        n = len(ents)
        self.cls = np.array([CLASSES.index(e["class"]) for e in ents])
        self.base = np.array([[e["tps"], e["kv"], e["conc"]] for e in ents],
                             np.float64).reshape(n, 3)
        self.slo = np.array([e["slo_ms"] for e in ents], np.float64)
        self.bound = self._bind()
        # token buckets: funded at baseline for one window at t=0
        self.rate = rnd(self.base[:, 0].copy())
        self.level = rnd(self.rate * self.window)
        self.refilled = np.zeros(n)
        self.burst = np.zeros(n)
        self.debt = np.zeros(n)
        self.demand = np.zeros(n)
        self.demand_window = np.zeros(n)
        self.window_tokens = np.zeros(n)
        self.in_flight = np.zeros(n, np.int64)
        self.resident = np.zeros(n, np.int64)
        self.kv = np.zeros(n)
        self.last_tick = 0.0
        #: request id -> [row, charged, input tokens, kv bytes, resident,
        #: admitted at, (pool, row) of the first leg when served off a
        #: spill leg]
        self.records: dict[str, list] = {}

    # -- leases --------------------------------------------------------------
    def _bind(self) -> np.ndarray:
        """Leases bind first come, first served while the reserve fits
        the pool's ceiling (max replicas) in every dimension."""
        cap = self.per * self.max_replicas
        used = np.zeros(3)
        bound = np.zeros(len(self.names), bool)
        for i in range(len(self.names)):
            req = self.base[i] if RESERVING[self.cls[i]] else np.zeros(3)
            free = np.maximum(cap - used, 0.0)
            if np.all(req <= free + 1e-9):
                used += req
                bound[i] = True
        return bound

    def capacity(self) -> np.ndarray:
        return self.per * self.replicas

    # -- Eq. 1 ---------------------------------------------------------------
    def avg_slo(self) -> float:
        return float(np.mean(self.slo[self.bound])) if self.bound.any() \
            else 1000.0

    def weights(self, burst=None) -> np.ndarray:
        c, r = self.coeff, self.rnd
        burst = self.burst if burst is None else burst
        slo_f = r(1.0 / (1.0 + c.alpha_slo * r(self.slo / self.avg_slo())))
        burst_f = r(1.0 / (1.0 + c.alpha_burst * np.maximum(burst, 0.0)))
        debt_f = r(np.maximum(1e-3, 1.0 + c.alpha_debt * self.debt))
        return r(r(CLASS_W[self.cls] * slo_f) * burst_f * debt_f)

    # -- buckets ---------------------------------------------------------------
    def _projected(self, i: int, now: float) -> float:
        cap = self.rate[i] * self.window
        dt = max(0.0, now - self.refilled[i])
        return self.rnd(min(cap, self.level[i] + dt * self.rate[i]))

    def _refill(self, i: int, now: float) -> None:
        self.level[i] = self._projected(i, now)
        self.refilled[i] = now

    # -- admission -------------------------------------------------------------
    def judge_quantum(self, now: float, reqs: list, decided: list
                      ) -> list[tuple]:
        """Margins of one pool batch.  ``reqs`` holds (request id, row,
        charged tokens, input tokens, kv bytes, now, first leg or None)
        in arrival order,
        ``decided`` the program's decision code per request.  Returns,
        per request, (bound, concurrency ok, budget margin, KV margin,
        priority margin) as this pool sees them (:func:`_gap` and
        :func:`_decide` read them), then applies the program's
        decisions to the state."""
        w = self.weights()
        cap_conc = self.capacity()[2]
        owners = self.in_flight > 0
        run_min = float(w[owners].min()) if owners.any() else math.inf
        pool_infl = int(self.in_flight.sum())
        pool_res = int(self.resident.sum())
        level: dict[int, float] = {}
        kv: dict[int, float] = {}
        margins = []
        for (rid, i, tok, inp, kvn, *_), d in zip(reqs, decided):
            if i not in level:
                level[i] = self._projected(i, now)
                kv[i] = self.kv[i]
            k = self.cls[i]
            ok_bound = bool(self.bound[i])
            r_lim = self.base[i, 2]
            r_eff = cap_conc if (r_lim <= 0 and k == SPOT) else r_lim
            escape = (BURST_OK[k] and pool_res < cap_conc
                      and not pool_infl > cap_conc)
            ok_conc = r_eff <= 0 or self.resident[i] < r_eff or escape
            cap_b = self.rate[i] * self.window
            s_budget = (level[i] - tok) / max(cap_b, tok, 1e-30)
            chi = self.base[i, 1]
            s_kv = ((chi - (kv[i] + kvn)) / chi) if chi > 0 else 1.0
            if (not PROTECTED[k]) and pool_infl > cap_conc:
                thr = run_min * (1.0 - self.slack)
                s_prio = (w[i] - thr) / thr
            else:
                s_prio = 1.0
            margins.append((ok_bound, ok_conc, s_budget, s_kv, s_prio))
            if d == ADMIT:
                level[i] = self.rnd(level[i] - tok)
                kv[i] = self.rnd(kv[i] + kvn)
                pool_infl += 1
                run_min = min(run_min, float(w[i]))
        self._apply(now, reqs, decided, level, kv)
        return margins

    def _apply(self, now, reqs, decided, level, kv) -> None:
        for i in {r[1] for r, d in zip(reqs, decided) if d == ADMIT}:
            self.level[i] = level[i]
            self.refilled[i] = now
            self.kv[i] = kv[i]
        for (rid, i, tok, inp, kvn, at, first), d in zip(reqs, decided):
            if d != NOT_BOUND:
                self.demand_window[i] += tok
            if d == ADMIT:
                self.in_flight[i] += 1
                self.records[rid] = [i, tok, inp, kvn, False, at, first]

    def start(self, rids) -> None:
        for rid in rids:
            rec = self.records.get(rid)
            if rec is not None and not rec[4]:
                rec[4] = True
                self.resident[rec[0]] += 1

    def settle(self, now: float, done: list) -> list:
        """Completions: (request id, output tokens).  Returns (record,
        settled tokens) of each one served off a spill leg."""
        spilled = []
        for rid, out in done:
            rec = self.records.pop(rid)
            i, charged, inp, kvn, res = rec[:5]
            self.in_flight[i] = max(0, self.in_flight[i] - 1)
            if res:
                self.resident[i] = max(0, self.resident[i] - 1)
            self.kv[i] = self.rnd(max(0.0, self.kv[i] - kvn))
            actual = float(inp + out)
            self._refill(i, now)
            cap = self.rate[i] * self.window
            self.level[i] = self.rnd(
                min(cap, self.level[i] + max(0.0, charged - actual)))
            self.window_tokens[i] += actual
            if rec[6] is not None:
                spilled.append((rec, actual))
        return spilled

    # -- the accounting tick -----------------------------------------------------
    def tick(self, now: float) -> dict:
        """Measurement, then Eq. 3 burst, Eq. 1 weights, allocation and
        Eq. 2 debt; the buckets re-rate to the allocations."""
        c, r = self.coeff, self.rnd
        dt = max(1e-9, now - self.last_tick)
        self.last_tick = now
        measured = r(self.window_tokens / dt)
        self.window_tokens[:] = 0.0
        inst = self.demand_window / dt
        retain = 2.0 ** (-dt / self.interval)
        self.demand = r(np.maximum(retain * self.demand
                                   + (1.0 - retain) * inst, measured))
        self.demand_window[:] = 0.0
        demand = self.demand

        def term(used, base):
            return np.where(base > 0.0,
                            np.maximum(0.0, used / np.maximum(base, 1e-30)
                                       - 1.0),
                            np.where(used > 0.0, 1.0, 0.0))

        delta = (term(measured, self.base[:, 0]) + term(self.kv, self.base[:, 1])
                 + term(self.resident.astype(float), self.base[:, 2]))
        self.burst = r(c.gamma_burst * self.burst + (1.0 - c.gamma_burst)
                       * delta)
        w = self.weights()
        alloc = self._allocate(float(self.capacity()[0]), w, demand)
        base = self.base[:, 0]
        served = np.maximum(measured, np.minimum(alloc, demand))
        entitled = np.minimum(base, np.maximum(demand, served))
        gap = np.where((demand > 1e-9) & (base > 0.0),
                       (entitled - served) / np.maximum(base, 1e-30), 0.0)
        gap = np.clip(gap, -c.gap_clip, c.gap_clip)
        self.debt = r(np.where(
            DEBT_OK[self.cls],
            np.clip(c.gamma_debt * self.debt + (1.0 - c.gamma_debt) * gap,
                    c.debt_min, c.debt_max),
            self.debt))
        # buckets: refill at the old rate, adopt the new, clamp
        dtb = np.maximum(0.0, now - self.refilled)
        self.level = r(np.minimum(self.rate * self.window,
                                  self.level + dtb * self.rate))
        self.rate = r(np.maximum(0.0, alloc))
        self.level = r(np.minimum(self.level, self.rate * self.window))
        self.refilled[:] = now
        return {"allocations": alloc, "priorities": w, "bursts": self.burst,
                "debts": self.debt}

    def _allocate(self, capacity: float, w: np.ndarray,
                  demand: np.ndarray) -> np.ndarray:
        """Protected classes at baseline (scaled in an emergency), then
        elastic demand-capped baselines water-filled by weight, then the
        surplus water-filled to burst-capable classes up to demand."""
        r = self.rnd
        live = self.bound
        prot = live & PROTECTED[self.cls]
        base = self.base[:, 0]
        base_p = np.where(prot, base, 0.0)
        active_p = np.minimum(base_p, np.where(prot, demand, 0.0))
        total_p = r(float(np.sum(active_p)))
        if total_p > capacity:
            return r(base_p * (capacity / max(total_p, 1e-30)))
        remaining = max(0.0, capacity - total_p)
        elastic = live & (self.cls == CLASSES.index("elastic"))
        want_e = np.where(elastic, np.minimum(base, demand), 0.0)
        fill_e = self._waterfill(remaining, want_e, np.where(elastic, w, 0.0))
        alloc = r(base_p + fill_e)
        remaining = max(0.0, remaining - r(float(np.sum(fill_e))))
        ok = live & BURST_OK[self.cls]
        used = np.where(prot, active_p, np.minimum(alloc, demand))
        want_b = np.where(ok, np.maximum(0.0, demand - used), 0.0)
        return r(alloc + self._waterfill(remaining, want_b,
                                         np.where(ok, w, 0.0)))

    def _waterfill(self, capacity, want, weight, max_rounds: int = 32):
        """Shares of ``capacity`` in proportion to ``weight``, each capped
        at its ``want``; what a capped row leaves over is shared again
        among the rest, until nothing remains or a round caps nobody."""
        r = self.rnd
        want = np.maximum(want, 0.0)
        alloc = np.zeros_like(want)
        active = want > 1e-12
        remaining = max(capacity, 0.0)
        for _ in range(max_rounds):
            if remaining <= 1e-9 or not active.any():
                break
            wa = np.where(active, weight, 0.0)
            total = float(np.sum(wa))
            if total > 0.0:
                share = r(remaining * (wa / total))
            else:
                share = np.where(active, remaining / active.sum(), 0.0)
            room = want - alloc
            take = np.where(active, np.minimum(room, share), 0.0)
            alloc = r(alloc + take)
            remaining = r(remaining - float(np.sum(take)))
            done = active & (take >= room - 1e-6 * np.maximum(1.0, want))
            active &= ~done
            if not done.any():
                break
        return alloc

    # -- planner inputs -----------------------------------------------------------
    def reserved(self) -> np.ndarray:
        mask = RESERVING[self.cls]
        return self.base[mask].sum(axis=0)


def _gap(d, ok_bound, ok_conc, s_budget, s_kv, s_prio) -> float:
    """How far decision ``d`` lies from being right: 0 when it is the
    reference's decision, the smallest relative change of a margin that
    would make it so otherwise, and 1 for a disagreement in a check that
    has no margin (binding, concurrency counts)."""
    if d == DENY_ANY:
        if not (ok_bound and ok_conc):
            return 0.0
        return min(max(0.0, s_budget), max(0.0, s_kv), max(0.0, s_prio))
    if d == NOT_BOUND:
        return 0.0 if not ok_bound else 1.0
    if not ok_bound:
        return 1.0
    if d == CONCURRENCY:
        return 0.0 if not ok_conc else 1.0
    if not ok_conc:
        return 1.0
    pass_budget = max(0.0, -s_budget), max(0.0, -s_kv)
    if d == TOKEN_BUDGET:
        return min(max(0.0, s_budget), max(0.0, s_kv))
    if d == LOW_PRIORITY:
        return max(*pass_budget, max(0.0, s_prio))
    return max(*pass_budget, max(0.0, -s_prio))


def _decide(ok_bound, ok_conc, s_budget, s_kv, s_prio) -> int:
    if not ok_bound:
        return NOT_BOUND
    if not ok_conc:
        return CONCURRENCY
    if s_budget < 0 or s_kv < 0:
        return TOKEN_BUDGET
    if s_prio <= 0:
        return LOW_PRIORITY
    return ADMIT


class RefPlanner:
    """The reserved-floor + headroom-on-demand scale policy with
    scale-down hysteresis, per pool."""

    def __init__(self, config: PlanConfig = PlanConfig(), rnd=exact) -> None:
        self.config = config
        self.rnd = rnd
        self.state: dict[str, list] = {}     # ewma, seeded, low ticks

    def plan(self, pools: dict[str, RefPool]) -> dict[str, dict]:
        cfg, r = self.config, self.rnd
        out = {}
        for name in sorted(pools):
            p = pools[name]
            ewma_prev, seeded, low = self.state.get(name, (0.0, False, 0))
            demand = float(np.sum(p.demand))
            g = cfg.demand_ewma
            ewma = r(g * ewma_prev + (1.0 - g) * demand) if seeded \
                else r(demand)

            def dim(need, per):
                if per > 0:
                    return need / per
                return math.inf if need > 0 else 0.0

            res = p.reserved()
            need_res = max(dim(res[k], p.per[k]) for k in range(3))
            need_dem = dim(ewma * cfg.headroom, p.per[0])
            need = max(need_res, need_dem)
            desired = max(1, math.ceil(min(need, 1e9)))
            desired = min(max(desired, p.min_replicas), p.max_replicas)
            if desired < p.replicas and low + 1 < cfg.cooldown_ticks:
                low, desired = low + 1, p.replicas
            else:
                low = 0
            self.state[name] = (ewma, True, low)
            out[name] = {"desired": desired, "demand_ewma": ewma,
                         "need": need}
        return out
