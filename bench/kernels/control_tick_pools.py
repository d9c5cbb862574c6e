"""Least work of one ``control_tick_pools`` call over ``P`` pools of at
most ``N`` entitlement rows each: the work of ``control_tick`` on every
pool's rows (``control_tick.py``).  A function of the shapes alone."""
from __future__ import annotations

from bench.kernels.control_tick import ROW_BYTES_IN, ROW_BYTES_OUT, ROW_FLOPS


def work(N: int, P: int, **_) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    rows = N * P
    return float(ROW_FLOPS * rows), float((ROW_BYTES_IN + ROW_BYTES_OUT)
                                          * rows)
