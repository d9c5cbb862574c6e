"""Faults planted under the timed path, one per kind of fault a cell
can have (on one chip there is no exchange between chips to leave out),
for the test that sees ``correct`` come out false.  Each takes the
freshly built gateway and breaks the program beneath it."""
from __future__ import annotations

import numpy as np


def altered_answer(gw) -> None:
    """The first admission of every quantum is reported as a budget
    denial where the gateway produces it."""
    produce = gw.handle_quantum

    def patched(requests, now):
        resp = produce(requests, now)
        for k, r in enumerate(resp):
            if r.status == 200:
                resp[k] = r._replace(status=429, reason="token_budget",
                                     pool=None, entitlement=None)
                break
        return resp

    gw.handle_quantum = patched


def tick_state_unchanged(gw) -> None:
    """The accounting tick (one pool's or a group's) returns the state it
    was given: burst and debt never move."""
    from repro.core import control_plane

    def unchanged(tick):
        def patched(state, *args, **kw):
            _, alloc, weights = tick(state, *args, **kw)
            return state, alloc, weights
        return patched

    for name in ("control_tick", "control_tick_pools"):
        setattr(control_plane, name,
                unchanged(getattr(control_plane, name)))


def half_batch_left_out(gw) -> None:
    """The admission kernel decides only the first half of each quantum
    and reports the rest as budget denials."""
    import jax.numpy as jnp

    from repro.gateway import gateway

    admit = gateway.admit_quantum

    def patched(*args, **kw):
        admitted, reasons, weights = admit(*args, **kw)
        live = np.asarray(kw["req_live"])
        keep = np.arange(live.size) < (int(live.sum()) + 1) // 2
        return (jnp.where(keep, admitted, False),
                jnp.where(keep, reasons, 3), weights)

    gateway.admit_quantum = patched


FAULTS = {f.__name__: f for f in (altered_answer, tick_state_unchanged,
                                  half_batch_left_out)}
