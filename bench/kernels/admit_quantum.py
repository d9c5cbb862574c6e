"""Least work of one ``admit_quantum`` call over ``N`` entitlement rows
and ``M`` requests: every input column and request field read once,
every output written once, a fixed number of operations per request
check.  A function of the shapes alone."""
from __future__ import annotations

#: bytes per entitlement row read: class code i32, bound bool, baseline
#: tps/kv/conc, SLO, burst, debt, bucket level, resident count, KV in
#: use and Eq. 1 weight, each 4 bytes
ROW_BYTES = 4 + 1 + 4 * 10
#: per request: entitlement row i32, tokens f32, KV bytes f32, live bool
#: in; admitted bool, reason i32, priority f32 out
REQUEST_BYTES = 4 + 4 + 4 + 1 + 1 + 4 + 4
#: per request: the five checks and the state updates (compares,
#: selects, one multiply for the threshold, adds for charge/KV/count)
REQUEST_FLOPS = 16


def work(M: int, N: int, **_) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    return float(REQUEST_FLOPS * M), float(ROW_BYTES * N + REQUEST_BYTES * M)
