"""``settle_ms``: mean wall time of the benchmark's ``settle`` span over the
window: one ``Gateway.on_complete_batch`` call over the completions then
due, its store and SLO bookkeeping on the host when the span ends."""
from __future__ import annotations

from bench.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "settle")
