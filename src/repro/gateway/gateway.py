"""AI Gateway: the admission boundary (paper Fig. 1, LiteLLM role).

Responsibilities (paper §4.3):
  - resolve the inference key to its route (auth): an ordered list of
    (pool, entitlement) legs — one leg is the classic single-pool
    deployment, several legs give dual-pool-style spill-over routing;
  - run the admission pipeline BEFORE the request reaches a backend,
    walking the route until a pool admits (spill-over) or every leg
    has denied;
  - on rejection return 429 + Retry-After (the most optimistic hint
    across the legs that were actually tried);
  - on completion, post actual token consumption back to the auth
    service (the callback that closes admission ↔ execution
    accounting), attributed to whichever pool admitted the request.

Two request paths share these semantics:

- :meth:`Gateway.handle` — one request through the scalar §4.3
  pipeline (``AdmissionController.decide``); the per-request fallback
  and the parity oracle for the batched path;
- :meth:`Gateway.handle_quantum` — the DEFAULT hot path at scale, one
  path for every route shape: the requests of one scheduling quantum
  are grouped per (key, token shape) and routed once; leg round ``k``
  sends every undecided request to its ``k``-th live leg, each pool is
  snapshotted once, and ONE fused ``admit_quantum`` dispatch replays
  the §4.3 pipeline for the pool's batch; denials spill into the next
  round, so routes keep their ``route_order`` semantics.  Requests are
  padded to a power-of-two per dispatch so quantum-size churn does not
  retrace the kernel.

State lives in the StateStore (Redis contract): key → route mapping and
per-entitlement counters, so a real deployment can point this class at
an actual Redis.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from itertools import chain
from operator import attrgetter
from typing import NamedTuple, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro.core import (
    AdmissionController,
    AdmissionRequest,
    DenyReason,
    RouteEntry,
    StateStore,
    TokenPool,
)
from repro.core.control_plane import (
    bucket_width,
    pad_rows,
    pad_state,
    quantum_width,
)
from repro.core.markers import hot_path
from repro.core import shard_plane
from repro.core.pool_manager import PoolOrManager, as_manager
from repro.core.vectorized import admit_quantum, quantum_snapshot
from repro.telemetry import flight as flightrec
from repro.telemetry.spans import child, moved_to_device, readback, span

#: C-speed attribute extractors for the quantum path.
_Q_RID = attrgetter("request_id")
_Q_KV = attrgetter("kv_bytes_per_token")

#: ``admit_quantum`` deny-reason codes → gateway deny reasons.
_REASON_CODES = {
    1: DenyReason.NOT_BOUND,
    2: DenyReason.CONCURRENCY,
    3: DenyReason.TOKEN_BUDGET,
    4: DenyReason.LOW_PRIORITY,
}


class GatewayResponse(NamedTuple):
    """Immutable per-request verdict.  A NamedTuple, not a dataclass:
    the quantum hot path constructs one per request, and tuple
    construction is ~3x cheaper than a frozen dataclass's
    ``object.__setattr__`` per field."""

    status: int                      # 200 admitted / 401 / 429
    request_id: str
    retry_after_s: Optional[float] = None
    reason: Optional[str] = None
    priority: float = 0.0
    #: pool + entitlement that admitted the request (multi-pool routing)
    pool: Optional[str] = None
    entitlement: Optional[str] = None
    #: position of the admitting leg in the client's declared route
    #: (0 = preferred pool; >0 = request spilled past denied or
    #: unavailable higher-preference legs)
    spill_hops: int = 0


@dataclasses.dataclass(frozen=True)
class QuantumRequest:
    """One request of a scheduling quantum (``Gateway.handle_quantum``)."""

    api_key: str
    request_id: str
    input_tokens: int
    max_tokens: Optional[int] = None     # None → each leg's pool default
    kv_bytes_per_token: float = 0.0


class Gateway:
    def __init__(self, pools: PoolOrManager,
                 store: Optional[StateStore] = None,
                 spill_policy: str = "static",
                 telemetry=None) -> None:
        from repro.core.pool_manager import SPILL_POLICIES
        if spill_policy not in SPILL_POLICIES:
            raise ValueError(f"unknown spill policy {spill_policy!r}; "
                             f"expected one of {SPILL_POLICIES}")
        self.manager = as_manager(pools)
        self.store = store or StateStore()
        self.spill_policy = spill_policy
        self.controllers: dict[str, AdmissionController] = {
            name: AdmissionController(pool)
            for name, pool in self.manager.pools.items()}
        # ``telemetry=True`` builds a fresh ``repro.telemetry.Telemetry``;
        # passing an instance shares one plane across gateways.  Off by
        # default; on, it also records the quantum's, tick's and plan's
        # spans (``repro.telemetry.spans``): 3–7 µs a span and ~35 µs a
        # root call on a CPU host, ~1000 spans a minute at 4000
        # requests/s.  On a TPU v5e host the admission median with them
        # stayed within the run-to-run spread of the same gateway with
        # its span sites stubbed out.
        if telemetry is True:
            from repro.telemetry import Telemetry
            telemetry = Telemetry()
        self.telemetry = telemetry or None
        if self.telemetry is not None:
            for pool in self.manager.pools.values():
                self.telemetry.attach_pool(pool)

    # -- back-compat accessors -------------------------------------------------
    @property
    def pool(self) -> TokenPool:
        """The default (first) pool — single-pool callers' view."""
        return self.manager.default_pool()

    @property
    def controller(self) -> AdmissionController:
        return self.controllers[self.pool.spec.name]

    def _controller(self, pool_name: str) -> AdmissionController:
        ctrl = self.controllers.get(pool_name)
        if ctrl is None:
            ctrl = AdmissionController(self.manager.pool(pool_name))
            self.controllers[pool_name] = ctrl
        return ctrl

    # -- key management ---------------------------------------------------------
    def register_key(self, api_key: str, entitlement: str,
                     pool: Optional[str] = None) -> None:
        """Single-leg route (legacy API): key → entitlement on one pool.

        When ``pool`` is omitted the entitlement's OWNING pool is
        looked up, and a miss is an error — silently defaulting to the
        first pool would leave the key permanently 429-ing NOT_BOUND
        on a multi-pool gateway."""
        if pool is None:
            owners = [name for name, p in self.manager.pools.items()
                      if entitlement in p.entitlements]
            if not owners:
                raise ValueError(
                    f"entitlement {entitlement!r} exists in no pool; "
                    "add it before registering a key")
            if len(owners) > 1:
                raise ValueError(
                    f"entitlement {entitlement!r} exists in pools "
                    f"{owners}; pass pool= (or use register_route for "
                    "a multi-pool route)")
            pool = owners[0]
        self.register_route(api_key, [RouteEntry(pool, entitlement)])

    def register_route(self, api_key: str,
                       entries: Sequence[Union[RouteEntry,
                                               tuple[str, str]]]) -> None:
        """Ordered multi-pool route: first leg is the preferred pool,
        later legs are spill-over targets.

        Stored in the StateStore as a JSON string — the store keeps the
        Redis contract (string values), so a real Redis can be swapped
        in behind it."""
        route = tuple(e if isinstance(e, RouteEntry) else RouteEntry(*e)
                      for e in entries)
        if not route:
            raise ValueError("route must have at least one leg")
        self.store.set(f"route:{api_key}", json.dumps(
            [[e.pool, e.entitlement] for e in route]))

    def resolve(self, api_key: str, now: float = 0.0) -> Optional[str]:
        """Entitlement of the preferred leg (legacy single-pool view)."""
        route = self.route(api_key, now)
        return route[0].entitlement if route else None

    def route(self, api_key: str, now: float = 0.0
              ) -> Optional[tuple[RouteEntry, ...]]:
        raw = self.store.get(f"route:{api_key}", now)
        if raw is None:
            return None
        return tuple(RouteEntry(p, e) for p, e in json.loads(raw))

    # -- request path --------------------------------------------------------------
    def handle(self, api_key: str, request_id: str, input_tokens: int,
               max_tokens: Optional[int], now: float,
               kv_bytes_per_token: float = 0.0) -> GatewayResponse:
        tel = self.telemetry
        route = self.route(api_key, now)
        if not route:
            if tel is not None:
                tel.record_terminal_one(
                    now, request_id, flightrec.VERDICT_UNKNOWN_KEY,
                    flightrec.REASON_NONE)
            return GatewayResponse(status=401, request_id=request_id,
                                   reason="unknown_key")
        legs = self.manager.route_order_indexed(
            list(route), input_tokens, max_tokens, now,
            policy=self.spill_policy)
        first_denial = None
        best_retry: Optional[float] = None
        for i_leg, (hop, leg) in enumerate(legs):
            decision = self._controller(leg.pool).decide(AdmissionRequest(
                entitlement=leg.entitlement, input_tokens=input_tokens,
                max_tokens=max_tokens, arrival_s=now,
                request_id=request_id,
                kv_bytes_per_token=kv_bytes_per_token))
            if tel is not None:
                pool = self.manager.pool(leg.pool)
                tel.attach_pool(pool)
                mt = (max_tokens if max_tokens is not None
                      else pool.spec.default_max_tokens)
                tel.record_decision(
                    leg.pool, now, request_id, hop, leg.entitlement,
                    decision.admitted,
                    flightrec.REASON_NONE if decision.reason is None
                    else flightrec.REASON_CODES[decision.reason.value],
                    decision.priority, float(input_tokens + mt))
            if decision.admitted:
                self.store.incr(f"admits:{leg.entitlement}", 1.0, now)
                if hop > 0:
                    self.store.incr(f"spills:{api_key}", 1.0, now)
                    if tel is not None:
                        tel.record_spill_admits(leg.pool,
                                                {legs[0][1].pool: 1})
                if i_leg > 0:
                    # served by a spill leg: remember the PREFERRED leg
                    # so completion can transfer the debt credit
                    # (PoolManager.transfer_spill_debt)
                    rec = self.manager.pool(leg.pool).in_flight.get(
                        request_id)
                    if rec is not None:
                        first = legs[0][1]
                        rec.spill_from = (first.pool, first.entitlement)
                return GatewayResponse(
                    status=200, request_id=request_id,
                    priority=decision.priority, pool=leg.pool,
                    entitlement=leg.entitlement, spill_hops=hop)
            if first_denial is None:
                first_denial = decision
            if decision.retry_after_s is not None:
                best_retry = (decision.retry_after_s if best_retry is None
                              else min(best_retry, decision.retry_after_s))

        # Every leg denied, or none was available.  The denial is
        # attributed to the first leg actually TRIED — when the whole
        # route is down nothing denied it, so the unroutable counter
        # takes it instead of charging a pool that never saw the
        # request.
        if legs:
            self.store.incr(f"denials:{legs[0][1].entitlement}", 1.0, now)
        else:
            self.store.incr(f"unroutable:{api_key}", 1.0, now)
        if first_denial is None:           # no live pool on the route
            if tel is not None:
                tel.record_terminal_one(
                    now, request_id, flightrec.VERDICT_DENY,
                    flightrec.REASON_POOL_UNAVAILABLE)
            return GatewayResponse(
                status=429, request_id=request_id, retry_after_s=5.0,
                reason=DenyReason.POOL_UNAVAILABLE.value)
        return GatewayResponse(
            status=429, request_id=request_id,
            retry_after_s=best_retry,
            reason=(first_denial.reason.value
                    if first_denial.reason else None),
            priority=first_denial.priority)

    # -- batched request path (the scheduling-quantum hot path) -----------------
    @hot_path
    def handle_quantum(self, requests: Sequence[QuantumRequest],
                       now: float) -> list[GatewayResponse]:
        """Admit one scheduling quantum of requests through the fused
        kernel — ONE ``admit_quantum`` dispatch per (pool, leg round)
        instead of five Python checks per request.

        Requests group per distinct (key, token shape), and each
        group's route is resolved once.  Round ``k`` sends every
        still-undecided request to the pool of its ``k``-th live leg
        (a group's requests share their legs, so a group moves from
        round to round together); each pool is snapshotted once, its
        batch replayed through the kernel in arrival order, and the
        resulting charges/denials scattered back through the real
        ledger + pool bookkeeping.  A request denied at round ``k``
        re-enters round ``k+1`` with its next leg; one denied at its
        last leg is answered in that batch.  Responses come back in
        input order.

        Parity contract (pinned by ``tests/test_gateway_quantum.py``):
        each pool decides its batch exactly as the scalar
        :meth:`handle` pipeline would decide that arrival sequence, so
        end-to-end decisions are identical to the sequential handle
        loop whenever routes are single-leg or share one pool order
        (prefixes of a common route — the typical deployment, where a
        pool is only ever reached at one leg depth).  Route sets that
        interleave pools in DIFFERENT orders are still served
        deterministically, but leg-round batching admits a pool's
        round-``k`` arrivals before another request's round-``k+1``
        spill reaches it — where the sequential loop may interleave
        the other way.  Likewise ``headroom`` spill rankings are
        evaluated once at quantum start (per key + token shape), not
        re-ranked between requests mid-quantum.
        """
        tel = self.telemetry
        with span(tel, "gateway.quantum", now=now):
            if len(requests) == 1:
                # A one-request quantum replays the sequential walk
                # exactly (per-pool batches of size one) — skip the
                # snapshot + kernel dispatch and use the scalar pipeline
                # directly.
                q = requests[0]
                responses = [self.handle(
                    q.api_key, q.request_id, q.input_tokens, q.max_tokens,
                    now, kv_bytes_per_token=q.kv_bytes_per_token)]
            else:
                responses = self._leg_rounds(requests, now)
        if tel is not None:
            tel.on_quantum(len(requests))
        return responses

    @hot_path
    def _leg_rounds(self, requests: Sequence[QuantumRequest],
                    now: float) -> list[GatewayResponse]:
        """The leg rounds of :meth:`handle_quantum`.  Where some route
        has several live legs each round is a ``gateway.round`` span;
        otherwise the one round's pool batches sit directly under the
        quantum."""
        tel = self.telemetry
        responses: list[Optional[GatewayResponse]] = [None] * len(requests)
        with child("gateway.route"):
            # within a quantum `now` is fixed, so a key's route (and
            # its headroom ordering) is a constant per token shape
            by_ck: dict[tuple, list[int]] = {}
            for i, q in enumerate(requests):
                ck = (q.api_key, q.input_tokens, q.max_tokens)
                try:
                    by_ck[ck].append(i)
                except KeyError:
                    by_ck[ck] = [i]
            #: (undecided members in arrival order, key, input, max, legs)
            groups: list[tuple] = []
            unknown_ids: list[str] = []
            unroutable_ids: list[str] = []
            unroutable_incr: dict[str, float] = {}
            for (key, inp, mx), idxs in by_ck.items():
                route = self.route(key, now)
                legs = None if route is None else \
                    self.manager.route_order_indexed(
                        list(route), inp, mx, now,
                        policy=self.spill_policy)
                if legs is None:
                    for i in idxs:
                        responses[i] = GatewayResponse(
                            status=401, request_id=requests[i].request_id,
                            reason="unknown_key")
                        unknown_ids.append(requests[i].request_id)
                elif not legs:               # route exists, no live pool
                    for i in idxs:
                        responses[i] = GatewayResponse(
                            status=429, request_id=requests[i].request_id,
                            retry_after_s=5.0,
                            reason=DenyReason.POOL_UNAVAILABLE.value)
                        unroutable_ids.append(requests[i].request_id)
                    unroutable_incr[f"unroutable:{key}"] = \
                        unroutable_incr.get(f"unroutable:{key}", 0.0) \
                        + float(len(idxs))
                else:
                    groups.append((idxs, key, inp, mx, legs))
        if unroutable_incr or unknown_ids:
            with child("gateway.record"):
                if unroutable_incr:
                    self.store.incr_many(unroutable_incr, now)
                if tel is not None:
                    if unknown_ids:
                        tel.record_terminal(now, unknown_ids,
                                            flightrec.VERDICT_UNKNOWN_KEY,
                                            flightrec.REASON_NONE)
                    if unroutable_ids:
                        tel.record_terminal(
                            now, unroutable_ids, flightrec.VERDICT_DENY,
                            flightrec.REASON_POOL_UNAVAILABLE)
        multi_leg = any(len(g[4]) > 1 for g in groups)
        #: request index → (first reason, its priority, least
        #: Retry-After) for requests denied with a leg still to try
        carry: dict[int, tuple] = {}
        k = 0
        while groups:
            batches: dict[str, list[tuple]] = {}
            for g in groups:
                batches.setdefault(g[4][k][1].pool, []).append(g)
            # a round's pools dispatch in the order of their earliest
            # request, as the sequential walk reaches them
            order = sorted(batches,
                           key=lambda p: min(g[0][0] for g in batches[p]))
            groups = []
            with (child("gateway.round", index=k) if multi_leg
                  else contextlib.nullcontext()):
                for pool_name in order:
                    batch = batches[pool_name]
                    left = self._admit_batch(pool_name, batch, k, requests,
                                             responses, carry, now)
                    groups.extend((idxs,) + g[1:]
                                  for g, idxs in zip(batch, left) if idxs)
            k += 1
        return responses

    @hot_path
    def _dispatch_admit(self, pool: TokenPool, snap, rows, tokens, kvs,
                        m: int) -> tuple[np.ndarray, np.ndarray,
                                         np.ndarray]:
        """ONE padded ``admit_quantum`` dispatch for a pool batch of
        ``m`` live requests in replay order (``rows``/``tokens``/
        ``kvs`` arrays).  Returns host-side
        (admitted, reasons, weights) trimmed to the live prefix: the
        ``gateway.admit`` span."""
        with child("gateway.admit", pool.spec.name):
            width = quantum_width(m)
            row_width = bucket_width(snap.state.n_rows)

            def padvec(xs, dtype):
                a = np.zeros(width, dtype)
                a[:m] = xs
                return a

            live = np.zeros(width, bool)
            live[:m] = True
            req_ent = padvec(rows, np.int32)
            req_tokens = padvec(tokens, np.float32)
            req_kv = padvec(kvs, np.float32)
            moved_to_device(live.nbytes + req_ent.nbytes
                            + req_tokens.nbytes + req_kv.nbytes)
            mesh = shard_plane.pool_mesh(pool)
            admit_kw = {} if mesh is None else {"mesh": mesh}
            admit_fn = admit_quantum if mesh is None \
                else shard_plane.shard_admit_quantum
            admitted, reasons, req_w = admit_fn(
                pad_state(snap.state, row_width),
                pad_rows(snap.bucket_level, row_width),
                pad_rows(snap.in_flight, row_width),
                pad_rows(snap.kv_in_use, row_width),
                pool_in_flight=jnp.int32(snap.pool_in_flight),
                pool_conc_cap=jnp.float32(snap.pool_conc_cap),
                running_min_priority=jnp.float32(
                    snap.running_min_priority),
                pool_avg_slo=jnp.float32(snap.pool_avg_slo),
                req_ent=req_ent,
                req_tokens=req_tokens,
                req_kv=req_kv,
                pool_resident=jnp.int32(snap.pool_resident),
                req_live=live,
                weights=pad_rows(snap.weights, row_width),
                coeff=pool.spec.coefficients,
                slack=pool.spec.admission_slack,
                **admit_kw)
            return (readback(admitted)[:m], readback(reasons)[:m],
                    readback(req_w)[:m])

    @hot_path
    def _admit_batch(self, pool_name: str, groups: list[tuple], k: int,
                     requests: Sequence[QuantumRequest], responses: list,
                     carry: dict, now: float) -> list[list[int]]:
        """One pool's batch of leg round ``k``: snapshot → kernel →
        batched scatter.  ``groups`` are the (members, key, input, max,
        legs) groups whose ``k``-th live leg is on this pool; every
        member of a group shares its row, tokens and hop, so group
        constants are gathered into per-request arrays in arrival
        order.

        A denial at a group's last leg is answered here, as
        :meth:`handle` answers an exhausted route: the first denial's
        reason and priority, the least Retry-After over the legs
        tried, counted on the first live leg's entitlement.  Returns,
        per group, the members denied with a leg still to try, in
        arrival order (their state waits in ``carry``)."""
        pool = self.manager.pool(pool_name)
        snap = quantum_snapshot(pool, now)
        row_of = snap.row_of
        default_mt = pool.spec.default_max_tokens
        store = self.store
        tel = self.telemetry
        #: StateStore deltas for the whole batch — flushed as ONE
        #: ``incr_many`` (the Redis pipeline shape) instead of one
        #: ``incr`` per key
        incr_acc: dict[str, float] = {}
        left: list[list[int]] = [[] for _ in groups]

        def deny(i, rid, reason, priority, retry, gid):
            prior = carry.pop(i, None) if carry else None
            if prior is not None:
                reason, priority = prior[0], prior[1]
                if prior[2] is not None:
                    retry = prior[2] if retry is None \
                        else min(prior[2], retry)
            legs = groups[gid][4]
            if k + 1 < len(legs):
                carry[i] = (reason, priority, retry)
                left[gid].append(i)
                return
            k_den = f"denials:{legs[0][1].entitlement}"
            incr_acc[k_den] = incr_acc.get(k_den, 0.0) + 1.0
            responses[i] = GatewayResponse(
                status=429, request_id=rid, retry_after_s=retry,
                reason=reason.value, priority=priority)

        g_ent: list[str] = []
        g_hop: list[int] = []
        g_row: list[int] = []
        g_tok: list[float] = []
        g_inp: list[int] = []
        g_mt: list[int] = []
        counts: list[int] = []
        # NOT_BOUND skips never reach the kernel; their decision rows
        # record with ent_slot -1 and zeroed state dims
        nb_rids: list[str] = []
        nb_hops: list[int] = []
        nb_toks: list[float] = []
        for gid, (idxs, _, inp, mx, legs) in enumerate(groups):
            hop, leg = legs[k]
            row = row_of.get(leg.entitlement)
            mt = mx if mx is not None else default_mt
            g_ent.append(leg.entitlement)
            g_hop.append(hop)
            g_row.append(-1 if row is None else row)
            g_tok.append(float(inp + mt))
            g_inp.append(inp)
            g_mt.append(mt)
            counts.append(0 if row is None else len(idxs))
            if row is None:
                # the scalar pipeline's espec-is-None early out: a
                # NOT_BOUND denial without touching pool state
                for i in idxs:
                    rid = requests[i].request_id
                    deny(i, rid, DenyReason.NOT_BOUND, 0.0, None, gid)
                    if tel is not None:
                        nb_rids.append(rid)
                        nb_hops.append(hop)
                        nb_toks.append(float(inp + mt))
        if tel is not None and nb_rids:
            with child("gateway.record", pool_name):
                tel.record_decisions(
                    pool_name, now, nb_rids,
                    np.full(len(nb_rids), -1, np.int64),
                    np.asarray(nb_hops, np.int64),
                    np.zeros(len(nb_rids), bool),
                    np.full(len(nb_rids), 1, np.int16),   # NOT_BOUND
                    0.0, float(snap.running_min_priority)
                    * (1.0 - pool.spec.admission_slack),
                    np.asarray(nb_toks, np.float64))
        cnt = np.asarray(counts, np.int64)
        m = int(cnt.sum())
        if not m:
            if incr_acc:
                with child("gateway.record", pool_name):
                    store.incr_many(incr_acc, now)
            return left
        # per-group constants expand to per-request arrays by GATHER,
        # not per-group np.full loops; argsort restores arrival order
        idx_cat = np.fromiter(
            chain.from_iterable(g[0] for g, c in zip(groups, counts) if c),
            np.int64, count=m)
        order = np.argsort(idx_cat)
        idx_arr = idx_cat[order]
        gids = np.repeat(np.arange(len(groups), dtype=np.int64),
                         cnt)[order]
        g_row_arr = np.asarray(g_row, np.int64)
        rows64 = g_row_arr[gids]
        toks64 = np.asarray(g_tok, np.float64)[gids]
        inps = np.asarray(g_inp, np.int64)[gids]
        mts = np.asarray(g_mt, np.int64)[gids]
        idx_l = idx_arr.tolist()
        if m == len(requests):
            # whole quantum in one pool batch (the common single-pool
            # deployment): arrival order IS input order, so attribute
            # extraction runs as C-speed maps with no index gather
            rids = list(map(_Q_RID, requests))
            kvpt = np.fromiter(map(_Q_KV, requests), np.float64,
                               count=m)
        else:
            rids = [requests[i].request_id for i in idx_l]
            kvpt = np.fromiter(
                (requests[i].kv_bytes_per_token for i in idx_l),
                np.float64, count=m)
        kvs64 = toks64 * kvpt

        admitted, reasons, req_w = self._dispatch_admit(
            pool, snap, rows64, toks64, kvs64, m)

        #: admits off a later leg than the first, by the first leg's pool
        spilled_from: dict[str, int] = {}
        with child("gateway.charge", pool_name):
            ledger = pool.ledger
            js = np.flatnonzero(admitted)
            charged = np.zeros(m, bool)
            ch_slots = np.empty(0, np.int64)
            charge_ids: list[str] = []
            if js.size:
                # buckets ensured once per group with kernel admits,
                # vectorized: rates come off the eff_tps column, with the
                # scalar path's spec-f64 baseline on the eff==0 fallback;
                # the ledger re-checks every charge (it stays
                # authoritative if f32/f64 disagree on an exact budget
                # boundary — those flip to budget denials below)
                ub = np.unique(gids[js])
                uslots = g_row_arr[ub]
                rates = pool.store.col["eff_tps"][uslots].copy()
                for t in np.flatnonzero(rates == 0.0).tolist():
                    rates[t] = pool.entitlements[
                        g_ent[int(ub[t])]].baseline.tokens_per_second
                ledger.ensure_rows(uslots, rates, now)
                charge_ids = rids if js.size == m else \
                    [rids[t] for t in js.tolist()]
                ok, ch_slots = ledger.charge_rows(
                    charge_ids, rows64[js], toks64[js], inps[js], mts[js],
                    now)
                charged[js] = ok

            acc = np.flatnonzero(charged)
            w_l = req_w.tolist()
            gid_l = gids.tolist()
            if acc.size:
                admit_ids = charge_ids if acc.size == js.size else \
                    [rids[t] for t in acc.tolist()]
                # ch_slots aligns with the accepted charges in charge
                # order — exactly this admit batch
                slots = pool.admit_rows(admit_ids, rows64[acc], kvs64[acc],
                                        toks64[acc], now, slots=ch_slots)
                if k:
                    # served off a later leg than the first: the rows
                    # remember the first live leg, whose debt their
                    # completion transfers (PoolManager.transfer_spill_debt)
                    spill_col = pool.table.spill_from
                    for s, gid in zip(slots.tolist(), gids[acc].tolist()):
                        first = groups[gid][4][0][1]
                        spill_col[s] = (first.pool, first.entitlement)
                # demand lands exactly like the scalar register_admit
                # loop: one unbuffered index-ordered f64 add chain
                np.add.at(pool.store.col["demand_window"], rows64[acc],
                          toks64[acc])
                per_gid = np.bincount(gids[acc], minlength=len(groups))
                for gid, n_adm in enumerate(per_gid.tolist()):
                    if n_adm:
                        k_adm = f"admits:{g_ent[gid]}"
                        incr_acc[k_adm] = incr_acc.get(k_adm, 0.0) \
                            + float(n_adm)
                        if g_hop[gid] > 0:
                            k_sp = f"spills:{groups[gid][1]}"
                            incr_acc[k_sp] = incr_acc.get(k_sp, 0.0) \
                                + float(n_adm)
                            src = groups[gid][4][0][1].pool
                            spilled_from[src] = \
                                spilled_from.get(src, 0) + n_adm
                if acc.size == m:
                    it = zip(idx_l, rids, w_l, gid_l)
                else:
                    it = ((idx_l[j], rids[j], w_l[j], gid_l[j])
                          for j in acc.tolist())
                # tuple.__new__ skips the NamedTuple default-filling
                # wrapper — measurably faster at 10^5 responses/quantum
                mk = tuple.__new__
                for i, rid, w, gid in it:
                    responses[i] = mk(GatewayResponse,
                                      (200, rid, None, None, w, pool_name,
                                       g_ent[gid], g_hop[gid]))

        # Denials run AFTER the batch's admits are registered, so
        # Retry-After hints reflect the pool the retrying client will
        # actually face (the scalar loop's hints see only the admits
        # that preceded each request).  Bookkeeping lands as ONE
        # ``register_deny_batch`` scatter, and hints are memoized per
        # (reason, entitlement, tokens) — see ``_deny_hint``.
        den = np.flatnonzero(~charged)
        if den.size:
            with child("gateway.deny", pool_name):
                hint_cache: dict = {}
                deny_ents: list[str] = []
                deny_demand = np.zeros(den.size, np.float64)
                deny_lp = np.zeros(den.size, bool)
                adm_kernel = admitted.tolist()
                reasons_l = reasons.tolist()
                toks_l = toks64.tolist()
                for d, j in enumerate(den.tolist()):
                    gid = gid_l[j]
                    ent = g_ent[gid]
                    w = w_l[j]
                    code = 3 if adm_kernel[j] else int(reasons_l[j])
                    reason = _REASON_CODES[code]
                    retry = self._deny_hint(pool, pool_name, ent, reason,
                                            toks_l[j], w, now,
                                            cache=hint_cache)
                    deny_ents.append(ent)
                    if reason is not DenyReason.NOT_BOUND:
                        deny_demand[d] = toks_l[j]
                    lp = reason is DenyReason.LOW_PRIORITY
                    deny_lp[d] = lp
                    deny(idx_l[j], rids[j], reason, w if lp else 0.0,
                         retry, gid)
                pool.register_deny_batch(deny_ents, deny_demand, deny_lp)
        with child("gateway.record", pool_name):
            if incr_acc:
                store.incr_many(incr_acc, now)
            if tel is not None:
                tel.record_spill_admits(pool_name, spilled_from)
                # ONE flight scatter for the kernel batch, with reasons
                # finalized the way responses were: a kernel admit the
                # ledger rejected flips to TOKEN_BUDGET (code 3)
                final_reasons = np.where(
                    charged, 0,
                    np.where(admitted, 3, reasons.astype(np.int64)))
                tel.record_decisions(
                    pool_name, now, rids, rows64,
                    np.asarray(g_hop, np.int64)[gids], charged,
                    final_reasons.astype(np.int16),
                    np.asarray(req_w, np.float64),
                    float(snap.running_min_priority)
                    * (1.0 - pool.spec.admission_slack),
                    toks64,
                    levels_at=np.asarray(snap.bucket_level, np.float64))
        return left

    def _deny_hint(self, pool: TokenPool, pool_name: str, ent: str,
                   reason: DenyReason, tokens: float, w: float,
                   now: float, cache: Optional[dict] = None
                   ) -> Optional[float]:
        """Retry-After for a kernel denial — the scalar pipeline's
        §4.3 hint formulas, evaluated on the post-quantum pool state
        (all of this batch's admits applied): the hint describes what
        a client retrying AFTER this quantum will face.

        ``cache`` (one dict per batch) memoizes hints per
        (reason, entitlement, tokens) and the priority threshold per
        batch — valid because post-quantum pool state is fixed for the
        whole denial pass (denials mutate nothing a hint reads)."""
        ctrl = self._controller(pool_name)
        if reason is DenyReason.NOT_BOUND:
            return 5.0
        if reason is DenyReason.LOW_PRIORITY:
            threshold = (cache.get("threshold")
                         if cache is not None else None)
            if threshold is None:
                threshold = (pool.admission_threshold()
                             * (1.0 - pool.spec.admission_slack))
                if cache is not None:
                    cache["threshold"] = threshold
            return ctrl._priority_backoff(w, threshold)
        key = (reason, ent, tokens)
        if cache is not None and key in cache:
            return cache[key]
        if reason is DenyReason.CONCURRENCY:
            hint = ctrl._concurrency_backoff(ent)
        else:                                # TOKEN_BUDGET
            espec = pool.entitlements[ent]
            st = pool.status[ent]
            bucket = pool.ledger.ensure(
                ent, st.effective.tokens_per_second
                or espec.baseline.tokens_per_second, now)
            if not bucket.can_afford(tokens, now):
                hint = min(pool.ledger.retry_after(ent, tokens, now),
                           60.0)
            else:
                hint = 1.0                   # KV headroom denial
        if cache is not None:
            cache[key] = hint
        return hint

    # -- fleet planning -----------------------------------------------------------
    def plan_quantum(self, now: float, records=None):
        """Run one fleet planning round (``PoolManager.plan_quantum``)
        and surface it in the gateway's stats store: per-pool replica
        gauges, scale-up/down counters, and migration counters —
        the same observability surface the admission counters use.
        The round is the ``fleet.plan`` span."""
        with span(self.telemetry, "fleet.plan", now=now):
            plan = self.manager.plan_quantum(now, records=records)
        if self.telemetry is not None:
            self.telemetry.on_plan(now, plan)
        for name, d in plan.decisions.items():
            self.store.set(f"replicas:{name}", float(d.desired), now)
        # count authorization TRANSITIONS, not convergence rounds —
        # under provisioning lag `desired > current` repeats every
        # plan until the replicas come live
        for name, (old, new) in plan.scale_events.items():
            if new > old:
                self.store.incr(f"scale_ups:{name}", 1.0, now)
            elif new < old:
                self.store.incr(f"scale_downs:{name}", 1.0, now)
        for prop in plan.applied:
            self.store.incr(f"migrations:{prop.entitlement}", 1.0, now)
            self.store.set(f"migrated_to:{prop.entitlement}", prop.dst,
                           now)
        return plan

    # -- completion callback ----------------------------------------------------------
    def on_complete(self, request_id: str, actual_output_tokens: int,
                    latency_s: float, now: float) -> None:
        settled = self.manager.on_complete(request_id,
                                           actual_output_tokens, now)
        if settled is not None:
            pool_name, rec = settled
            self.store.incr(f"tokens:{rec.entitlement}",
                            float(actual_output_tokens), now)
            self.store.set(f"last_latency:{rec.entitlement}", latency_s,
                           now)
            if self.telemetry is not None:
                self.telemetry.record_completions(
                    now, [pool_name], [rec.entitlement], [latency_s])

    @hot_path
    def on_complete_batch(self, completions: Sequence[tuple], now: float
                          ) -> None:
        """Batched completion callback — one vectorized settle per
        admitting pool per scheduling quantum.

        ``completions`` is a sequence of
        ``(request_id, actual_output_tokens, latency_s)`` tuples.
        Semantics per element match :meth:`on_complete` (the retained
        scalar oracle); StateStore counters are aggregated so the
        store is hit once per distinct entitlement per batch
        (``last_latency`` keeps last-write-wins order).  The call is the
        ``gateway.settle`` span; the debt transfers of requests served
        on a spill leg are its ``pool.spill_debt`` children."""
        if not completions:
            return
        tel = self.telemetry
        with span(tel, "gateway.settle", now=now):
            settled = self.manager.on_complete_batch(
                [(rid, out) for rid, out, _ in completions], now)
            tokens_incr: dict = {}
            last_lat: dict = {}
            done_pools: list[str] = []
            done_ents: list[str] = []
            done_lats: list[float] = []
            for (_, out, lat), res in zip(completions, settled):
                if res is None:
                    continue
                ent = res[1]
                tokens_incr[f"tokens:{ent}"] = \
                    tokens_incr.get(f"tokens:{ent}", 0.0) + float(out)
                last_lat[ent] = lat
                if tel is not None:
                    done_pools.append(res[0])
                    done_ents.append(ent)
                    done_lats.append(lat)
            self.store.incr_many(tokens_incr, now)
            for ent, lat in last_lat.items():
                self.store.set(f"last_latency:{ent}", lat, now)
            if tel is not None and done_ents:
                # one SLO row-op for the whole drain (per-tier latency
                # histograms + attainment counters)
                tel.record_completions(now, done_pools, done_ents,
                                       done_lats)

    def on_failure(self, request_id: str, now: float) -> None:
        self.manager.on_evict(request_id, now)
