"""Vectorized telemetry plane: metrics registry, admission flight
recorder, SLO attainment tracking, program spans, and Prometheus / JSON
/ Chrome-trace exporters.

Quickstart::

    from repro.telemetry import Telemetry
    gw = Gateway(pool, telemetry=True)       # or telemetry=Telemetry()
    ...
    print(gw.telemetry.prometheus())         # Prometheus exposition
    print(gw.telemetry.flight.explain(rid).narrative())
    open("trace.json", "w").write(gw.telemetry.chrome_trace())
    gw.telemetry.spans.rows()                # the spans, as arrays

Spans (``repro.telemetry.spans``) are on whenever a ``Telemetry`` is
attached, and their names are :data:`SPAN_NAMES` and
:data:`LEG_SPAN_NAMES` (leg rounds and settles), nested as the calls
nest::

    gateway.quantum   handle_quantum (root)
      gateway.route     key -> legs: store reads, route_order_indexed
      gateway.round     one leg round, where the quantum holds a
                        multi-leg route (its index); the snapshot ..
                        record of each pool batch in it
      gateway.snapshot  quantum_snapshot: uploads, priority_batch, the
                        owner mask and owner_min up to its readback
      gateway.admit     padding, upload, admit_quantum, readback
      gateway.charge    ledger charges, admit_rows, demand, the 200s
      gateway.deny      Retry-After hints, register_deny_batch, the 429s
      gateway.record    flight rows, decision counters, store incr_many
    gateway.settle    on_complete_batch (root)
      pool.spill_debt   a pool's transfer_spill_debt calls, for the
                        requests it served on a spill leg
    pool.tick         TokenPool.tick or one control_tick_pools group (root)
      pool.measure      window fold and the kernel-input uploads
      pool.kernel       dispatch through the alloc/weights readback
      pool.absorb       the kernel's state adopted, buckets re-rated
    fleet.plan        Gateway.plan_quantum (root)
      fleet.kernel      plan_fleet from upload through readback
      fleet.rebalance   _rebalance: starvation masks over the store
                        columns and the tick record, then Python over
                        the starved rows alone
    compile           a backend compile, under the span that caused it

Every span is timed on one clock (``time.perf_counter``), carries its
parent and the id of its root call, and enters a
``jax.profiler.TraceAnnotation`` of its name, so a profiler trace holds
it beside the device's operations.  As a root closes its spans fold
into the histogram ``repro_span_duration_seconds{span,pool}`` and the
bytes they moved into ``repro_transfer_bytes_total{direction,span}``
(``h2d`` / ``d2h``).  Spill routing counts
``repro_spill_admits_total{from_pool,to_pool}`` and
``repro_spill_debt_moved_total{from_pool,to_pool}``; each plan sets
``repro_plan_starved_rows{pool,reason}``.  The Chrome
timeline is drawn from the same table on the same clock; a simulator's
``now`` is kept in its args.
"""
from repro.telemetry.export import (TraceBuffer, chrome_trace_json,
                                    json_snapshot, prometheus_text)
from repro.telemetry.facade import Telemetry
from repro.telemetry.flight import (DecisionTrace, FlightRecorder,
                                    FlightRow)
from repro.telemetry.registry import (Counter, Gauge, Histogram,
                                      MetricsRegistry)
from repro.telemetry.slo import SloTracker, TIER_NAMES
from repro.telemetry.spans import LEG_SPAN_NAMES, SPAN_NAMES, SpanTable

__all__ = [
    "Counter",
    "DecisionTrace",
    "FlightRecorder",
    "FlightRow",
    "Gauge",
    "Histogram",
    "LEG_SPAN_NAMES",
    "MetricsRegistry",
    "SPAN_NAMES",
    "SloTracker",
    "SpanTable",
    "TIER_NAMES",
    "Telemetry",
    "TraceBuffer",
    "chrome_trace_json",
    "json_snapshot",
    "prometheus_text",
]
