"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, the
plain reference (``bench/reference``) is built from the same fleet specs
and driven through the run's event log: every quantum with its ``now``
and the decisions the program returned, every dispatch and completion,
every tick and plan.  Three numbers come out, each the widest gap over
the run:

* ``decision_gap`` — per request and leg, how far the program's
  admission decision lies from the reference's, as the relative change
  of a bucket, KV or priority margin that would make it right (1 for a
  wrong binding or concurrency verdict).  A request meets its route's
  legs in order: the leg that admitted it (``pool``) admitted, each leg
  before it denied, the first for the ``reason`` a 429 reports, the
  others for a reason the answer does not carry;
* ``tick_gap`` — per tick and row, the gap between the program's
  allocation, Eq. 1 weight, burst and debt and the reference's, over the
  larger of the reference value and the mean magnitude of that
  quantity over the pool;
* ``plan_gap`` — per plan and pool, a wrong replica decision (1) or the
  relative gap of the demand EWMA, and 1 for a migration (no cell here
  has a second pool to migrate to).

With ``control`` the reference also runs a second time, kept at
bfloat16 and fed the same events, and its own decisions and ticks are
judged by the same numbers.
"""
from __future__ import annotations

import numpy as np

from bench.reference.control_plane import (
    ADMIT, DEBT_OK, DENY_ANY, REASONS, RefPlanner, RefPool, _decide, _gap, bfloat16,
    exact)

QUANTITIES = ("allocations", "priorities", "bursts", "debts")


def _codes(resp) -> list[int]:
    return [ADMIT if s == 200 else REASONS.get(r, -1) for s, r, _ in resp]


def _row_gap(got: np.ndarray, want: np.ndarray, live: np.ndarray) -> float:
    if not live.any():
        return 0.0
    floor = max(float(np.mean(np.abs(want[live]))), 1e-12)
    return float(np.max(np.abs(got[live] - want[live])
                        / np.maximum(np.abs(want[live]), floor)))


class Replay:
    """Reference (and optionally control) pools fed one event log."""

    def __init__(self, fleet: dict, arrivals, control: bool = False) -> None:
        self.arrivals = arrivals
        self.sides = {"program": None}
        self.ref = {p["name"]: RefPool(p, fleet["entitlements"][p["name"]])
                    for p in fleet["pools"]}
        self.planner = RefPlanner()
        self.ctrl = None
        if control:
            self.ctrl = {p["name"]: RefPool(p, fleet["entitlements"][p["name"]],
                                            rnd=bfloat16)
                         for p in fleet["pools"]}
            self.ctrl_planner = RefPlanner(rnd=bfloat16)
        #: key -> [(pool, row), ...], its route's legs in order
        self.legs = {key: [(pool, self.ref[pool].row[ent])
                           for pool, ent in route]
                     for key, route in fleet["routes"].items()}
        self.kv_per_token = {p["name"]: p["kv_bytes_per_token"]
                             for p in fleet["pools"]}
        zero = {"decision_gap": 0.0, "tick_gap": 0.0, "plan_gap": 0.0}
        self.program = dict(zero)
        self.control = dict(zero) if control else None
        self.counts = {"decisions": 0, "ticks": 0, "plans": 0}

    def _bump(self, side: dict, name: str, value: float) -> None:
        side[name] = max(side[name], float(value))

    def run(self, events: list) -> None:
        for ev in events:
            getattr(self, "_" + ev[0])(*ev[1:])

    # -- events -----------------------------------------------------------------
    def _quantum(self, now: float, lo: int, hi: int, resp) -> None:
        a = self.arrivals
        if resp is None:                      # the call raised
            self._bump(self.program, "decision_gap", 1.0)
            return
        decided = _codes(resp)
        rounds: list[dict[str, list]] = []
        for k, i in enumerate(range(lo, hi)):
            legs = self.legs[a.key[i]]
            status, _, served = resp[k]
            last = len(legs) - 1
            if status == 200:
                last = next((li for li, (p, _) in enumerate(legs)
                             if p == served), None)
            if decided[k] < 0 or last is None:
                self._bump(self.program, "decision_gap", 1.0)
                continue
            tok = float(a.input_tokens[i] + a.max_tokens[i])
            for li in range(last + 1):
                pool, row = legs[li]
                if status == 200:
                    d = ADMIT if li == last else DENY_ANY
                else:
                    d = decided[k] if li == 0 else DENY_ANY
                while len(rounds) <= li:
                    rounds.append({})
                rounds[li].setdefault(pool, []).append(
                    (d, (f"r{i}", row, tok, int(a.input_tokens[i]),
                         tok * self.kv_per_token[pool], now,
                         legs[0] if li else None)))
        for by_pool in rounds:
            for pool, items in by_pool.items():
                reqs = [r for _, r in items]
                codes = [d for d, _ in items]
                margins = self.ref[pool].judge_quantum(now, reqs, codes)
                for d, m in zip(codes, margins):
                    self._bump(self.program, "decision_gap", _gap(d, *m))
                if self.ctrl is not None:
                    own = self.ctrl[pool].judge_quantum(now, reqs, codes)
                    for m_ctrl, m in zip(own, margins):
                        self._bump(self.control, "decision_gap",
                                   _gap(_decide(*m_ctrl), *m))
                self.counts["decisions"] += len(items)

    def _start(self, rids) -> None:
        for side in (self.ref, self.ctrl or {}):
            for pool in side.values():
                pool.start(rids)

    def _settle(self, now: float, done) -> None:
        for side in (self.ref, self.ctrl or {}):
            # each pool settles its share, then hands on the debt credit
            # of what it served off a spill leg (pools in the order the
            # batch first names them)
            order: dict[str, list] = {}
            for d in done:
                for name, pool in side.items():
                    if d[0] in pool.records:
                        order.setdefault(name, []).append(d)
                        break
            for name, mine in order.items():
                for rec, actual in side[name].settle(now, mine):
                    transfer_spill_debt(side, side[name], rec, actual, now)

    def _tick(self, now: float, records: dict) -> None:
        for name, pool in self.ref.items():
            want = pool.tick(now)
            rec = records[name]
            rows = np.fromiter((pool.row[n] for n in rec._names), np.int64,
                               count=len(rec._names))
            live = pool.bound[rows]
            for q in QUANTITIES:
                got = np.asarray(rec._arrays[q], np.float64)
                self._bump(self.program, "tick_gap",
                           _row_gap(got, want[q][rows], live))
            if self.ctrl is not None:
                ctrl = self.ctrl[name].tick(now)
                for q in QUANTITIES:
                    self._bump(self.control, "tick_gap",
                               _row_gap(ctrl[q], want[q], pool.bound))
        self.counts["ticks"] += 1

    def _plan(self, now: float, plan) -> None:
        want = self.planner.plan(self.ref)
        self._plan_gap(self.program, want, {
            n: (d.desired, d.demand_tps) for n, d in plan.decisions.items()})
        if plan.migrations or plan.applied:
            self._bump(self.program, "plan_gap", 1.0)
        if self.ctrl is not None:
            got = self.ctrl_planner.plan(self.ctrl)
            self._plan_gap(self.control, want, {
                n: (g["desired"], g["demand_ewma"]) for n, g in got.items()})
        self.counts["plans"] += 1

    def _plan_gap(self, side: dict, want: dict, got: dict) -> None:
        for name, w in want.items():
            desired, ewma = got.get(name, (None, np.nan))
            if desired != w["desired"]:
                self._bump(side, "plan_gap", 1.0)
            self._bump(side, "plan_gap",
                       abs(ewma - w["demand_ewma"])
                       / max(abs(w["demand_ewma"]), 1.0))


def transfer_spill_debt(side: dict, serving: RefPool, rec: list,
                        actual: float, now: float) -> None:
    """A request served off a spill leg moves the Eq. 2 gap-equivalent of
    its settled tokens from the debt of its first leg's entitlement to
    the serving one's (when that one bears debt), within each side's
    clamps."""
    src_pool, src_row = rec[6]
    src = side[src_pool]
    base = src.base[src_row, 0]
    if not DEBT_OK[src.cls[src_row]] or base <= 0.0 or actual <= 0.0:
        return
    c = src.coeff
    window = max(now - rec[5], src.interval)
    credit = (1.0 - c.gamma_debt) * min(c.gap_clip, actual / (base * window))
    delta = min(credit, src.debt[src_row] - c.debt_min)
    if delta <= 0.0:
        return
    row = rec[0]
    if DEBT_OK[serving.cls[row]]:
        delta = min(delta, serving.coeff.debt_max - serving.debt[row])
        if delta <= 0.0:
            return
        serving.debt[row] = serving.rnd(serving.debt[row] + delta)
    src.debt[src_row] = src.rnd(src.debt[src_row] - delta)


def compare(fleet: dict, arrivals, events: list, control: bool = False
            ) -> Replay:
    rep = Replay(fleet, arrivals, control=control)
    rep.run(events)
    return rep


__all__ = ["Replay", "compare", "exact"]
