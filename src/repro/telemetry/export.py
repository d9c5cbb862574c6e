"""Exporters: Prometheus text exposition, JSON snapshot, and a
Chrome-trace-event (Perfetto-loadable) timeline.

All three are COLD paths — they read registry arrays, the span table
and the trace buffer, never the other way round.  The timeline's
slices are the program's spans (a bounded table, read out at export);
its markers are append-only Python under a hard cap (they are rare:
one per scale event, migration, tick or incident, never one per
request).

Chrome trace format notes (``chrome://tracing`` / ui.perfetto.dev):
timestamps and durations are MICROseconds; ``ph`` codes used here are
``X`` (complete slice), ``i`` (instant), ``C`` (counter) and ``M``
(metadata, for track names).
"""
from __future__ import annotations

import json
import time
from typing import Optional

from repro.telemetry.registry import (Counter, Gauge, Histogram,
                                      MetricsRegistry)
from repro.telemetry.spans import SpanTable

__all__ = ["TraceBuffer", "chrome_trace_json", "json_snapshot",
           "prometheus_text"]


# ---------------------------------------------------------------------------
# Prometheus text exposition (version 0.0.4)
# ---------------------------------------------------------------------------

def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _labels_str(names: tuple, values: tuple, extra: str = "") -> str:
    parts = [f'{n}="{_escape_label(str(v))}"'
             for n, v in zip(names, values)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def prometheus_text(registry: MetricsRegistry) -> str:
    """Render every family in the Prometheus text format.  Histograms
    emit cumulative ``_bucket{le=...}`` samples (closing with
    ``le="+Inf"``), ``_sum`` and ``_count``; callback gauges are
    evaluated at scrape time — exactly the Redis/Prometheus shape the
    paper's platform would scrape."""
    lines: list[str] = []
    for fam in registry.families():
        if fam.help:
            lines.append(f"# HELP {fam.name} {fam.help}")
        lines.append(f"# TYPE {fam.name} {fam.kind}")
        if isinstance(fam, Histogram):
            for sid, labels in enumerate(fam.series_labels):
                cum = 0
                for b, edge in enumerate(fam.edges):
                    cum += int(fam.counts[sid, b])
                    ls = _labels_str(fam.label_names, labels,
                                     f'le="{_fmt(edge)}"')
                    lines.append(f"{fam.name}_bucket{ls} {cum}")
                total = int(fam.totals[sid])
                ls = _labels_str(fam.label_names, labels, 'le="+Inf"')
                lines.append(f"{fam.name}_bucket{ls} {total}")
                ls = _labels_str(fam.label_names, labels)
                lines.append(f"{fam.name}_sum{ls} {_fmt(fam.sums[sid])}")
                lines.append(f"{fam.name}_count{ls} {total}")
        elif isinstance(fam, (Counter, Gauge)):
            for sid, labels in enumerate(fam.series_labels):
                ls = _labels_str(fam.label_names, labels)
                lines.append(f"{fam.name}{ls} {_fmt(fam.read(sid))}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# JSON snapshot
# ---------------------------------------------------------------------------

def json_snapshot(registry: MetricsRegistry) -> dict:
    """Registry state as plain JSON-serializable dicts (one entry per
    family; series keyed by their joined label values)."""
    out: dict = {}
    for fam in registry.families():
        series: dict = {}
        for sid, labels in enumerate(fam.series_labels):
            key = ",".join(str(v) for v in labels) or "_"
            if isinstance(fam, Histogram):
                series[key] = {
                    "count": int(fam.totals[sid]),
                    "sum": float(fam.sums[sid]),
                    "p50": fam.quantile(sid, 0.50),
                    "p99": fam.quantile(sid, 0.99),
                }
            else:
                series[key] = float(fam.read(sid))
        out[fam.name] = {"kind": fam.kind,
                         "labels": list(fam.label_names),
                         "series": series}
    return out


# ---------------------------------------------------------------------------
# Chrome trace events
# ---------------------------------------------------------------------------

class TraceBuffer:
    """The Chrome timeline of one ``Telemetry``: every program span of
    its :class:`~repro.telemetry.spans.SpanTable` as a complete slice,
    plus rare markers (scale and migration instants, water-fill
    counters, incident windows) under a hard cap.  One clock for all:
    ``ts`` is microseconds of ``time.perf_counter`` since the table's
    ``t0``; a caller's own clock (a simulator's ``now``) is kept in
    ``args``, never as ``ts``.  ``pid`` is always 1 (one logical
    process, the control plane); spans share one track, on which they
    nest as they ran."""

    SPAN_TRACK = "control plane"

    def __init__(self, spans: SpanTable, max_events: int = 200_000) -> None:
        self.spans = spans
        self.markers: list[dict] = []
        self.max_events = max_events
        self.dropped = 0
        self._tids: dict[str, int] = {}

    def tid(self, track: str) -> int:
        """Intern a track name → tid."""
        t = self._tids.get(track)
        if t is None:
            t = self._tids[track] = len(self._tids) + 1
        return t

    def _us(self, t: float) -> float:
        return (t - self.spans.t0) * 1e6

    def _push(self, ev: dict) -> None:
        if len(self.markers) >= self.max_events:
            self.dropped += 1
            return
        self.markers.append(ev)

    def complete(self, name: str, track: str, start: float, end: float,
                 args: Optional[dict] = None) -> None:
        """A ``ph:X`` slice from ``start`` to ``end`` (``perf_counter``
        seconds): incident windows."""
        self._push({"name": name, "ph": "X", "pid": 1,
                    "tid": self.tid(track), "ts": self._us(start),
                    "dur": max(0.0, end - start) * 1e6,
                    "args": args or {}})

    def instant(self, name: str, track: str, now: float,
                args: Optional[dict] = None) -> None:
        """A ``ph:i`` marker at this moment: scale/migration events."""
        self._push({"name": name, "ph": "i", "s": "t", "pid": 1,
                    "tid": self.tid(track),
                    "ts": self._us(time.perf_counter()),
                    "args": {"now": now, **(args or {})}})

    def counter(self, name: str, track: str, values: dict) -> None:
        """A ``ph:C`` sample at this moment: water-fill level / debt."""
        self._push({"name": name, "ph": "C", "pid": 1,
                    "tid": self.tid(track),
                    "ts": self._us(time.perf_counter()), "args": values})

    def events(self) -> list[dict]:
        """Track names, the spans, then the markers."""
        rows = self.spans.rows()
        tid = self.tid(self.SPAN_TRACK)
        slices = []
        for i in range(rows["id"].size):
            args = {"id": int(rows["id"][i]),
                    "parent": int(rows["parent"][i]),
                    "root": int(rows["root"][i])}
            if rows["pool"][i]:
                args["pool"] = rows["pool"][i]
            now = float(rows["now"][i])
            if now == now:
                args["now"] = now
            for col in ("h2d", "d2h"):
                if rows[col][i]:
                    args[f"{col}_bytes"] = int(rows[col][i])
            if rows["cache_hit"][i] >= 0:
                args["cache_hit"] = bool(rows["cache_hit"][i])
            if rows["index"][i] >= 0:
                args["index"] = int(rows["index"][i])
            start, end = float(rows["start"][i]), float(rows["end"][i])
            slices.append({"name": rows["name"][i], "ph": "X", "pid": 1,
                           "tid": tid, "ts": self._us(start),
                           "dur": (end - start) * 1e6, "args": args})
        meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": t,
                 "args": {"name": track}}
                for track, t in self._tids.items()]
        return meta + slices + self.markers


def chrome_trace_json(trace: TraceBuffer) -> str:
    """Serialize to the JSON object form Perfetto loads directly."""
    return json.dumps({"traceEvents": trace.events(),
                       "displayTimeUnit": "ms"})
