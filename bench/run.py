#!/usr/bin/env python3
"""On-chip benchmark of the token-pool gateway: one cell, one seed.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a tenant-fleet configuration and a
traffic mix.  The run builds the fleet from the seed through the public
``repro.core`` / ``repro.gateway`` API, warms every kernel shape the
window can use, drives the mix's arrivals (``bench/harness/traffic.py``)
through ``Gateway.handle_quantum`` with the accounting tick and the
fleet plan at their interval, and then holds what the timed path decided to the
plain reference (``bench/reference``).  With ``--trace 1`` the last
``TRACE_S`` seconds of the window run under the JAX profiler and the
per-layer metrics are reported.

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each with its limit, are the last
lines of standard error.  Without a TPU (or with fewer chips than the
cell asks for) the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import fleet as fleet_mod  # noqa: E402
from bench.harness import loop, replay, stats, traffic  # noqa: E402
from bench.harness.device import (CompileLog, NoChip, describe,  # noqa: E402
                                  require_tpu)
from bench.harness.spec import Bench  # noqa: E402

DECISION_NAMES = ("admit", "entitlement_not_bound", "concurrency_limit",
                  "token_budget", "low_priority")
#: seconds of the window the profiler traces (up to its close): every
#: step of the admission scan is a device event, so a whole window's
#: trace would run to gigabytes
TRACE_S = 4.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also replay the bfloat16 control and print its "
                         "readings (a check of the limits, not a metric)")
    return ap.parse_args(argv)


def info(**row) -> None:
    print(json.dumps(row, default=float), flush=True)


def warm_up(gw, cap: int) -> dict:
    """Compile every kernel shape the window can use, without touching
    the fleet's state: each admission width up to the quantum cap on a
    snapshot (a pure read), the tick (one pool, or each group of pools
    that share coefficients stacked as ``PoolManager.tick`` stacks them)
    and the plan on the live arrays.  What a tick compiles around the
    kernel compiles in the burn-in, which is set-up too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import control_plane as cp
    from repro.core.fleet import FleetPlanner, plan_fleet
    from repro.core.vectorized import quantum_snapshot

    widths = sorted({cp.quantum_width(m) for m in range(1, cap + 1)})
    pools = list(gw.manager.pools.values())
    for pool in pools:
        snap = quantum_snapshot(pool, 0.0)
        for w in widths:
            gw._dispatch_admit(pool, snap, np.zeros(w, np.int64),
                               np.ones(w), np.zeros(w), w)
    groups: dict = {}
    for pool in pools:
        groups.setdefault(pool.spec.coefficients, []).append(pool)
    ticks = []
    for coeff, group in groups.items():
        if len(group) == 1:
            pool = group[0]
            jax.block_until_ready(cp.control_tick(
                pool.store.device_state(),
                jnp.float32(pool.capacity().tokens_per_second),
                *pool._kernel_inputs(), jnp.float32(pool.pool_avg_slo()),
                coeff=coeff))
            ticks.append(("control_tick", 1))
            continue
        width = cp.bucket_width(max(p.store.capacity for p in group))

        def stacked(col):
            out = np.zeros((len(group), width), np.float32)
            for i, p in enumerate(group):
                out[i, :p.store.capacity] = p.store.col[col]
            return jnp.asarray(out)

        jax.block_until_ready(cp.control_tick_pools(
            cp.stack_states([p.store.device_state() for p in group],
                            width=width),
            jnp.asarray([p.capacity().tokens_per_second for p in group],
                        jnp.float32),
            stacked("measured_tps"), stacked("kv_in_use"),
            stacked("resident"), stacked("demand_tps"),
            jnp.asarray([p.pool_avg_slo() for p in group], jnp.float32),
            coeff=coeff))
        ticks.append(("control_tick_pools", len(group)))
    planner = FleetPlanner()
    _, arr = planner._arrays(gw.manager.pools, {})
    jax.block_until_ready(plan_fleet(
        **{k: jnp.asarray(v) for k, v in arr.items()},
        config=planner.config))
    return {"admit_widths": widths, "ticks": ticks}


def gc_recorder(clock) -> list:
    """Record each garbage-collector pass as [start, end, generation] on
    ``clock``."""
    pauses: list = []

    def on_gc(phase, info_):
        if phase == "start":
            pauses.append([clock(), None, info_["generation"]])
        elif pauses and pauses[-1][1] is None:
            pauses[-1][1] = clock()

    gc.callbacks.append(on_gc)
    return pauses


def window_summary(window, arrivals, spans, sel, in_window,
                   gc_pauses) -> dict:
    """What the earlier line of a run reports about its window: the
    decision mix, second legs, compiles, ticks, generator lateness,
    seconds per host span, the longest spans with what the host did in
    them, GC pauses and latency per quarter."""
    codes = window.code[sel]
    decided = codes >= 0
    lo, hi = window.open_s, window.close_s
    late = [1e3 * x for x in window.lateness_s]
    span_s: dict = {}
    for name, a, b in spans.rows:
        if lo <= a and b <= hi:
            span_s[name] = span_s.get(name, 0.0) + b - a
    by_span: dict = {}
    for name, _, _, t in in_window:
        where = next((n for n, a, b in spans.rows if a <= t <= b), "none")
        by_span[where] = by_span.get(where, 0) + 1
    gcs = [(a, b, g) for a, b, g in gc_pauses
           if b is not None and lo <= a <= hi]
    quarter = (hi - lo) / 4
    by_quarter = []
    for k in range(4):
        part = stats.latencies(arrivals.due, window.decided_s,
                               lo + k * quarter, lo + (k + 1) * quarter,
                               window.end_s)
        by_quarter.append([1e3 * stats.percentile(part, 50),
                           1e3 * stats.percentile(part, 99)])
    return {
        "decided": int(decided.sum()),
        "decisions": {DECISION_NAMES[c]: int(sum(codes == c))
                      for c in range(len(DECISION_NAMES))
                      if (codes == c).any()},
        "second_leg_share": float(window.second_leg[sel][decided].mean())
        if decided.any() else 0.0,
        "spill_admitted_share": float((window.hops[sel][codes == 0] > 0)
                                      .mean()) if (codes == 0).any()
        else 0.0,
        "window_compiles": len(in_window),
        "window_compile_s": sum(e[1] for e in in_window),
        "window_compile_names": sorted({e[0] for e in in_window})[:12],
        "compiles_by_span": by_span,
        "ticks": window.ticks,
        "quanta": len(spans.between("quantum", lo, hi)),
        "generator_late_p50_ms": stats.percentile(late, 50) if late
        else None,
        "generator_late_max_ms": max(late, default=None),
        "span_s": span_s,
        "loop_s": hi - lo - sum(span_s.values()),
        "gc_s": sum(b - a for a, b, _ in gcs),
        "gc_full": sum(1 for *_, g in gcs if g == 2),
        "p50_p99_ms_by_quarter": by_quarter,
        "slowest_spans": spans.slowest(lo, hi, 5),
        "stalls": sum(1 for n, a, b in spans.rows
                      if lo <= a and b <= hi and b - a > loop.STALL_S),
        "arrivals_exhausted": arrivals.exhausted,
        "owner_counts": _owner_counts(window.owner_counts, lo, hi),
    }


def _owner_counts(rows, lo: float, hi: float) -> dict:
    """The distinct in-flight owner counts the pools' snapshots met:
    each new count compiles the program's owner gather anew."""
    seen_before = {k for t, k in rows if t < lo}
    inside = [k for t, k in rows if lo <= t <= hi]
    return {"min": min(inside, default=None),
            "max": max(inside, default=None),
            "distinct": len(set(inside)),
            "new": len(set(inside) - seen_before)}


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, bench, cell, spans, window, trace, device) -> None:
        self.bench, self.cell, self.spans = bench, cell, spans
        self.window, self.trace, self.device = window, trace, device

    def in_window(self, name: str) -> list[float]:
        return self.spans.between(name, self.window.open_s,
                                  self.window.close_s)

    def traced_calls(self, kernel: str) -> list[dict]:
        lo, hi = self.window.trace_window
        return [shapes for k, shapes, a, b in self.window.kernel_calls
                if k == kernel and a >= lo and b <= hi]


def main(argv=None, root: Path = ROOT, require_chip: bool = True,
         fault=None) -> int:
    args = parse(argv)
    bench = Bench(root)
    cell = bench.cell(args.workload)
    config = bench.config(cell)
    mix = bench.traffic(cell)
    limits = bench.limits(cell)
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    if require_chip:
        try:
            devices = require_tpu(jax, int(cell["chips"]))
        except NoChip as exc:
            print(f"bench: no chip: {exc}", file=sys.stderr, flush=True)
            return 2
    else:
        devices = jax.devices()
    compiles = CompileLog(jax)
    t_fleet = time.perf_counter()
    fleet = fleet_mod.fleet_spec(config, args.seed)
    gw = fleet_mod.build_gateway(fleet)
    fleet_s = time.perf_counter() - t_fleet
    if fault is not None:
        fault(gw)
    arrivals = traffic.generate(mix, fleet["keys_by_rank"], args.seed,
                                args.seconds)
    t_warm = time.perf_counter()
    warm = warm_up(gw, int(mix["quantum_cap"]))
    warm["seconds"] = time.perf_counter() - t_warm
    # the cache holds what the set-up compiles; what compiles once the
    # loop runs (programs of shapes the set-up cannot know) is compiled
    # afresh by every run and never stored, so that no run depends on
    # the runs before it and no run pays for writing the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))
    info(phase="setup", device_kind=devices[0].device_kind,
         compile_cache=str(cache), requests=len(arrivals),
         entitlements=sum(len(v) for v in fleet["entitlements"].values()),
         fleet_s=fleet_s, warmed=warm,
         build_s=time.perf_counter() - T_START)

    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    annotate = jax.profiler.TraceAnnotation if args.trace else None
    spans = loop.Spans(annotate=annotate)
    hooks = None
    if args.trace:
        from bench.harness.trace import profile_options
        opts = profile_options(jax)
        hooks = (lambda: jax.profiler.start_trace(str(trace_dir),
                                                  profiler_options=opts),
                 jax.profiler.stop_trace, TRACE_S)
    mark = len(compiles.events)
    gc_pauses = gc_recorder(spans.clock)
    window = loop.run(gw, arrivals, mix, fleet, args.seconds, spans,
                      trace=hooks)
    setup_s = spans.t0 + window.open_s - T_START
    device = describe(devices, int(cell["chips"]))
    # compiles by when they happened (on the loop's clock)
    in_window = [(n, d, hit, t - spans.t0)
                 for n, d, hit, t in compiles.events[mark:]
                 if window.open_s <= t - spans.t0 <= window.close_s]
    del gw
    gc.collect()

    lat = stats.latencies(arrivals.due, window.decided_s, window.open_s,
                          window.close_s, window.end_s)
    sel = (arrivals.due >= window.open_s) & (arrivals.due < window.close_s)
    failed = int(sum(window.code[sel] < 0))
    info(phase="window", seconds=args.seconds, attempted=int(sel.sum()),
         failed=failed,
         latency_ms={f"p{q}": 1e3 * stats.percentile(lat, q)
                     for q in (50, 90, 95, 99)},
         **window_summary(window, arrivals, spans, sel, in_window,
                          gc_pauses))

    e2e = {
        "admit_p50_ms": 1e3 * stats.percentile(lat, 50),
        "decisions_per_s": stats.rate(window.decided_s, window.open_s,
                                      window.close_s),
        "setup_s": setup_s,
    }

    metrics = {}
    breakdown = None
    if args.trace:
        from bench.harness import trace as trace_mod
        reduced = trace_mod.reduce(trace_dir)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        ctx = Context(bench, cell, spans, window, reduced, device)
        for m in bench.metrics(cell, "per_layer"):
            value = bench.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench.metrics(cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    rep = replay.compare(fleet, arrivals, window.events,
                         control=bool(args.control))
    readings = rep.program
    checks = {name: {"value": readings[name], "limit": limits[name]}
              for name in ("decision_gap", "tick_gap", "plan_gap")}
    raised = int(sum(window.code == loop.RAISED))
    correct = raised == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    info(phase="reference", seconds=time.perf_counter() - t_ref,
         compared=rep.counts)
    if args.control:
        info(phase="control", readings=rep.control,
             fails=[k for k, v in rep.control.items() if v > limits[k]])
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": int(sel.sum()),
              "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
