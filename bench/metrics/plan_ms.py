"""``plan_ms``: mean wall time of the benchmark's ``plan`` span over the
window (the call's outputs are on the host when the span ends)."""
from __future__ import annotations

from bench.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "plan")
