"""Least work of one ``control_tick`` call over ``N`` entitlement rows
of ``P`` pools: each state column (class, bound, three baselines, SLO,
burst, debt) and each measurement column (measured rate, KV in use,
resident, demand) read once; burst, debt, allocation and weight written
once; a fixed number of operations per row for Eq. 3, Eq. 1, one
allocation pass and Eq. 2.  A function of the shapes alone."""
from __future__ import annotations

ROW_BYTES_IN = 4 + 1 + 4 * 6 + 4 * 4
ROW_BYTES_OUT = 4 * 4
#: Eq. 3 (3 dimensions x 4), Eq. 1 (10), allocation (12), Eq. 2 (12)
ROW_FLOPS = 46


def work(N: int, P: int = 1, **_) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    rows = N * P
    return float(ROW_FLOPS * rows), float((ROW_BYTES_IN + ROW_BYTES_OUT)
                                          * rows)
