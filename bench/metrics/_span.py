"""Shared by the span readers: the mean of one host span over the
window, in milliseconds."""
from __future__ import annotations


def mean_ms(ctx, name: str):
    xs = ctx.in_window(name)
    return 1e3 * sum(xs) / len(xs) if xs else None
