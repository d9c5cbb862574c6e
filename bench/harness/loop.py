"""The timed loop: arrivals into ``Gateway.handle_quantum`` with the
system's own cadence around it (accounting tick and fleet plan every
interval, completions settled as they fall due).

Every call into the program sits in a host span of the benchmark's own,
which ends only once the call's outputs are on the host; with tracing
on, each span is also a ``jax.profiler.TraceAnnotation``, so the
profiler's trace attributes idle device time to what the host was doing.
Each span also records the CPU seconds the loop's thread and the whole
process got in it (a span whose wall time far exceeds both was kept off
the CPU), and a span that runs past ``STALL_S`` has the Python stacks of
every thread written to standard error (``faulthandler``, which needs no
GIL), so a stall shows where it was spent.
"""
from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import heapq
import sys
import time

import numpy as np

from bench.reference.control_plane import REASONS

#: decision codes beyond the reference's: a call that raised, or a
#: request no call decided before the loop ended
RAISED, UNDECIDED = -2, -1
#: a span longer than this has its stacks dumped while it runs
STALL_S = 2.0
#: what each span records of the host beside its wall time
USAGE = ("thread_cpu_s", "process_cpu_s")


def _usage() -> tuple:
    return time.thread_time(), time.process_time()


class Spans:
    """Named host spans on the loop's clock, kept in memory, each with
    the host usage (``USAGE``) it saw."""

    def __init__(self, annotate=None) -> None:
        self.annotate = annotate
        self.rows: list[tuple[str, float, float]] = []
        self.usage: list[tuple] = []
        self.t0 = time.perf_counter()

    def start(self) -> None:
        """Set the clock's zero: the moment the loop starts."""
        self.t0 = time.perf_counter()

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    @contextlib.contextmanager
    def __call__(self, name: str):
        ctx = (self.annotate(name) if self.annotate is not None
               else contextlib.nullcontext())
        faulthandler.dump_traceback_later(STALL_S, repeat=True,
                                          file=sys.stderr)
        u0 = _usage()
        t0 = self.clock()
        try:
            with ctx:
                yield
        finally:
            faulthandler.cancel_dump_traceback_later()
        self.rows.append((name, t0, self.clock()))
        self.usage.append(tuple(b - a for a, b in zip(u0, _usage())))

    def slowest(self, lo: float, hi: float, k: int) -> list[dict]:
        """The ``k`` longest spans inside [lo, hi], with their usage."""
        rows = [(b - a, n, a, u) for (n, a, b), u
                in zip(self.rows, self.usage) if lo <= a and b <= hi]
        rows.sort(key=lambda r: -r[0])
        return [dict(name=n, at_s=a, wall_s=w, **dict(zip(USAGE, u)))
                for w, n, a, u in rows[:k]]

    def between(self, name: str, lo: float, hi: float) -> list[float]:
        return [b - a for n, a, b in self.rows
                if n == name and lo <= a and b <= hi]


@dataclasses.dataclass
class Window:
    """What one run of the loop produced."""

    open_s: float
    close_s: float
    end_s: float
    decided_s: np.ndarray       # when the call that decided it returned
    code: np.ndarray            # reference decision codes, or RAISED/UNDECIDED
    hops: np.ndarray            # spill legs taken by admitted requests
    second_leg: np.ndarray      # the first leg denied, a later one tried
    lateness_s: list            # dispatch - due, after the loop had idled
    events: list                # the event log the reference replays
    kernel_calls: list          # (kernel, shapes, start, end)
    ticks: int
    owner_counts: list          # (when, distinct in-flight owners) per pool
    trace_window: tuple = (0.0, 0.0)


def admit_calls(keys, resp, routes: dict) -> list[tuple[str, int]]:
    """(pool, requests) of each ``admit_quantum`` dispatch one quantum
    made, from its answers: every request meets its first leg's pool in
    round 0 and, once denied there, its next leg's pool in the next
    round.  A quantum of one request takes the scalar path."""
    if len(keys) < 2 or resp is None:
        return []
    rounds: list[dict] = []
    for key, (status, _, pool) in zip(keys, resp):
        legs = routes[key]
        last = next((k for k, (p, _) in enumerate(legs) if p == pool),
                    len(legs) - 1) if status == 200 else len(legs) - 1
        for k in range(last + 1):
            while len(rounds) <= k:
                rounds.append({})
            rounds[k][legs[k][0]] = rounds[k].get(legs[k][0], 0) + 1
    return [item for r in rounds for item in r.items()]


def tick_calls(pools) -> list[tuple[str, dict]]:
    """The tick kernel each group of pools sharing coefficients runs,
    with its shapes: ``control_tick`` over one pool's live rows, or
    ``control_tick_pools`` over P pools of at most N live rows."""
    groups: dict = {}
    for p in pools.values():
        groups.setdefault(p.spec.coefficients, []).append(p)
    out = []
    for group in groups.values():
        rows = max(len(p.entitlements) for p in group)
        if len(group) == 1:
            out.append(("control_tick", {"N": rows}))
        else:
            out.append(("control_tick_pools", {"N": rows, "P": len(group)}))
    return out


def run(gw, arrivals, mix: dict, fleet: dict, seconds: float,
        spans: Spans, trace=None) -> Window:
    """Drive the gateway over the burn-in and the measured window, then
    keep going (at most a minute) until every request due in the window
    has its decision.  A closed loop's clients issue their next requests
    as each call returns (``Arrivals.decided``).  ``trace`` (start, stop, seconds) wraps the last
    ``seconds`` of the window in one profiler trace, so that the stall of
    writing it out falls after the close."""
    import jax

    from repro.gateway import QuantumRequest

    clock = spans.clock
    manager = gw.manager
    pools = manager.pools
    interval = min(p.spec.accounting_interval_s for p in pools.values())
    cap = int(mix["quantum_cap"])
    burn = float(mix["burn_in_s"])
    close = burn + float(seconds)
    n = len(arrivals)
    due = arrivals.due
    kv = fleet["kv_of_key"]
    routes = fleet["routes"]
    reqs = [QuantumRequest(k, f"r{i}", int(a), int(b), kv[k])
            for i, (k, a, b) in enumerate(zip(arrivals.key,
                                              arrivals.input_tokens,
                                              arrivals.max_tokens))]
    spans.start()
    decided = np.full(n, np.nan)
    code = np.full(n, UNDECIDED, np.int64)
    hops = np.zeros(n, np.int64)
    second_leg = np.zeros(n, bool)
    heap: list[tuple[float, int]] = []
    events: list = []
    calls: list = []
    owners: list = []
    lateness: list[float] = []
    next_req, next_tick, ticks = 0, interval, 0
    idled = False
    tracing = traced = False
    trace_start = close if trace is None else max(burn, close - trace[2])
    trace_window = (trace_start, trace_start)
    tick_kernels = tick_calls(pools)
    while True:
        now = clock()
        if trace is not None and not traced and now >= trace_start:
            trace[0]()
            tracing = traced = True
            trace_window = (clock(), close)
        if tracing and now >= close:
            trace[1]()
            tracing, trace_window = False, (trace_window[0], clock())
        # due times never fall with the index and calls return their
        # decisions, so once the next request is due after the close,
        # every request due in the window has its decision
        if (now >= close and (next_req >= n or due[next_req] >= close)) \
                or now >= close + 60.0:
            break
        busy = False
        if now >= next_tick:
            t_tick = clock()
            with spans("tick"):
                records = manager.tick(t_tick)
                jax.block_until_ready([p.store.device_state()
                                       for p in pools.values()])
            t_end = clock()
            calls.extend((k, shapes, t_tick, t_end)
                         for k, shapes in tick_kernels)
            t_plan = clock()
            with spans("plan"):
                plan = gw.plan_quantum(t_plan, records=records)
            events.append(("tick", t_tick, records))
            events.append(("plan", t_plan, plan))
            next_tick += interval
            ticks += 1
            busy = True
        now = clock()
        if heap and heap[0][0] <= now:
            done = []
            while heap and heap[0][0] <= now:
                t_done, i = heapq.heappop(heap)
                done.append((f"r{i}", int(arrivals.output_tokens[i]),
                             t_done - decided[i]))
            with spans("settle"):
                gw.on_complete_batch(done, now)
            events.append(("settle", now, [(r, o) for r, o, _ in done]))
            busy = True
        now = clock()
        if next_req < n and due[next_req] <= now:
            j = min(int(np.searchsorted(due, now, "right")), next_req + cap)
            if idled:
                lateness.append(now - due[next_req])
            batch = reqs[next_req:j]
            t = clock()
            try:
                with spans("quantum"):
                    resp = gw.handle_quantum(batch, now)
            except Exception as exc:          # a failed call fails its batch
                resp = None
                print(f"bench: handle_quantum raised {exc!r}", flush=True)
            t_dec = clock()
            decided[next_req:j] = t_dec
            arrivals.decided(next_req, j, t_dec)
            if resp is None:
                code[next_req:j] = RAISED
                events.append(("quantum", now, next_req, j, None))
            else:
                with spans("dispatch"):
                    rids = []
                    for k, r in enumerate(resp):
                        i = next_req + k
                        code[i] = REASONS.get(r.reason, RAISED) \
                            if r.status in (200, 429) else RAISED
                        if r.status == 200:
                            hops[i] = r.spill_hops
                            pools[r.pool].on_start(r.request_id)
                            rids.append(r.request_id)
                            heapq.heappush(heap, (t_dec + arrivals.hold_s[i],
                                                  i))
                answers = [(r.status, r.reason, r.pool) for r in resp]
                for k, (status, _, pool) in enumerate(answers):
                    legs = routes[batch[k].api_key]
                    second_leg[next_req + k] = len(legs) > 1 and not (
                        status == 200 and pool == legs[0][0])
                calls.extend(("admit_quantum",
                              {"M": m, "N": len(pools[p].entitlements)},
                              t, t_dec)
                             for p, m in admit_calls(
                                 [q.api_key for q in batch], answers,
                                 routes))
                events.append(("quantum", now, next_req, j, answers))
                events.append(("start", rids))
                # the owner count the next snapshot of each pool meets
                owners.extend((t_dec, p.inflight_owner_slots().size)
                              for p in pools.values())
            next_req = j
            busy = True
        idled = not busy
        if not busy:
            wake = min(due[next_req] if next_req < n else np.inf, next_tick,
                       heap[0][0] if heap else np.inf,
                       close if clock() < close else np.inf)
            with spans("wait_arrival"):
                time.sleep(max(0.0, min(wake - clock(), 0.05)))
    if tracing:
        trace[1]()
        trace_window = (trace_window[0], clock())
    return Window(open_s=burn, close_s=close, end_s=clock(),
                  decided_s=decided, code=code, hops=hops,
                  second_leg=second_leg,
                  lateness_s=lateness, events=events, kernel_calls=calls,
                  ticks=ticks, owner_counts=owners,
                  trace_window=trace_window)
