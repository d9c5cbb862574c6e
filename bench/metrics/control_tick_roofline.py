"""``control_tick_roofline``: share of its roofline that ``control_tick`` reached
over the traced window (see ``_roofline.py``)."""
from __future__ import annotations

from bench.metrics._roofline import share


def read(ctx):
    return share(ctx, "control_tick")
