"""``device_idle_share``: the share of the traced window in which no
operation ran on the chip, 100 x (1 - busy / window), from the trace."""
from __future__ import annotations


def read(ctx):
    t = ctx.trace or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
