"""Program spans: where the control plane spends its time, on one clock.

A span is one timed stretch of a control-plane call.  It records its
name (one of :data:`SPAN_NAMES` or :data:`LEG_SPAN_NAMES`), its start
and end on ``time.perf_counter``, the span open around it (its parent)
and the id of its root call: the quantum (``gateway.quantum``), the tick
(``pool.tick``), the plan (``fleet.plan``) or the settle
(``gateway.settle``) it belongs to, and an index where a span repeats
in its parent (the leg round of ``gateway.round``).  Every span of a
quantum, and so every request the quantum decides, shares that root
id.

Spans live in a :class:`SpanTable`, a bounded ring of preallocated
arrays that a ``Telemetry`` owns; they are read out at the end
(:meth:`SpanTable.rows`), and the Chrome timeline
(``Telemetry.chrome_trace``) is drawn from them.  Each span also enters
a ``jax.profiler.TraceAnnotation`` of its name, so a profiler trace
holds the program's spans on the same clock as the device's operations.

Charged to the innermost open span of the process:

* ``compile``: every backend compile, from ONE process-wide listener on
  ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
  (registered once, however many tables exist), recorded as a closed
  child from end − duration to end and marked as a persistent-cache hit
  or not.  It is the one span with no annotation: the listener hears of
  a compile only once it is over;
* bytes moved host→device (:func:`moved_to_device`) and device→host
  (:func:`readback`) at the control plane's uploads and readbacks.

Span sites: :func:`span` opens a span in a ``Telemetry``'s table (a
root when nothing of that table is open) and costs one ``None`` test
when no ``Telemetry`` is attached; :func:`child` opens one under the
innermost open span and costs one empty-stack test when none is open.
An open span costs a few microseconds: O(spans) per call, never
O(requests).  No span adds a device sync: each ends where the program
already reads back, or measures the enqueue alone.  The control plane
is single-threaded, and the stack of open spans is the process's.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["LEG_SPAN_NAMES", "SPAN_CAPACITY", "SPAN_NAMES", "SpanTable",
           "child", "moved_to_device", "readback", "span"]

#: the spans every fleet's calls record, roots first in each family
SPAN_NAMES = (
    "gateway.quantum", "gateway.route", "gateway.snapshot",
    "gateway.admit", "gateway.charge", "gateway.deny", "gateway.record",
    "pool.tick", "pool.measure", "pool.kernel", "pool.absorb",
    "fleet.plan", "fleet.kernel", "fleet.rebalance",
    "compile",
)
#: the spans of leg routing and settlement: each leg round of a
#: quantum that holds a multi-leg route (``gateway.round``, under
#: ``gateway.quantum``; with single-leg routes alone the one round's
#: pool batches sit directly under the quantum), the
#: settle of a batch of completions (``gateway.settle``, a root) and the
#: debt transfers of its requests served on a spill leg
#: (``pool.spill_debt``)
LEG_SPAN_NAMES = ("gateway.round", "gateway.settle", "pool.spill_debt")
_NAMES = SPAN_NAMES + LEG_SPAN_NAMES
_CODE = {name: i for i, name in enumerate(_NAMES)}
#: spans a ``Telemetry`` keeps: a gateway at 4000 requests/s records
#: ~1000 a minute (compiles included), so the newest half hour or so
SPAN_CAPACITY = 1 << 15
_COMPILE = _CODE["compile"]
#: what ``jax.monitoring`` reports each backend compile (or persistent
#: cache load) under, with its duration
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the process's open spans, innermost last
_OPEN: list["_Span"] = []


class _Span:
    """An open span: closes itself on ``__exit__``."""

    __slots__ = ("table", "sid", "ann")

    def __init__(self, table: "SpanTable", sid: int, ann) -> None:
        self.table, self.sid, self.ann = table, sid, ann

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.table._close(self)
        return False


#: what a span site opens when nothing records
_NO_SPAN = contextlib.nullcontext()


class SpanTable:
    """Bounded ring of spans in preallocated columns, indexed by span
    id modulo the (power-of-two) capacity: once full, the newest
    ``capacity`` spans are kept.

    ``on_root(sid)`` runs as each root span closes, when it and every
    span inside it (ids ``sid`` up to ``next_id``) are complete."""

    def __init__(self, capacity: int,
                 on_root: Callable[[int], None]) -> None:
        if capacity < 1 or capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, "
                             f"got {capacity}")
        self.capacity = capacity
        self._mask = capacity - 1
        self.name = np.zeros(capacity, np.int8)
        self.pool = np.zeros(capacity, np.int16)        # -1: no pool
        self.parent = np.zeros(capacity, np.int64)      # -1: a root
        self.root = np.zeros(capacity, np.int64)
        self.start = np.zeros(capacity, np.float64)     # perf_counter s
        self.end = np.zeros(capacity, np.float64)       # NaN while open
        self.now = np.zeros(capacity, np.float64)       # caller's clock
        self.h2d = np.zeros(capacity, np.int64)         # bytes
        self.d2h = np.zeros(capacity, np.int64)
        self.cache_hit = np.zeros(capacity, np.int8)    # -1: not a compile
        self.index = np.zeros(capacity, np.int32)       # -1: none
        self.next_id = 0
        #: the clock's origin for exports (``perf_counter`` at creation)
        self.t0 = time.perf_counter()
        #: interned pool labels (``pool`` column indexes this list)
        self.pools: list[str] = []
        self._pool_ids: dict[str, int] = {}
        self.on_root = on_root
        _listen()

    def _pool_id(self, pool: str) -> int:
        pid = self._pool_ids.get(pool)
        if pid is None:
            pid = self._pool_ids[pool] = len(self.pools)
            self.pools.append(pool)
        return pid

    def _row(self, code: int, pool: int, parent: int, root: int,
             now: float, index: int = -1) -> int:
        sid = self.next_id
        self.next_id = sid + 1
        r = sid & self._mask
        self.name[r] = code
        self.pool[r] = pool
        self.parent[r] = parent
        self.root[r] = sid if root < 0 else root
        self.now[r] = now
        self.h2d[r] = 0
        self.d2h[r] = 0
        self.cache_hit[r] = -1
        self.index[r] = index
        self.end[r] = np.nan
        return sid

    def open(self, name: str, pool: Optional[str] = None,
             now: Optional[float] = None, index: int = -1) -> _Span:
        """Open ``name`` under the innermost open span if it is this
        table's (inheriting its pool unless ``pool`` is given), else as
        a root.  Use as a context manager."""
        top = _OPEN[-1] if _OPEN else None
        if top is not None and top.table is self:
            pr = top.sid & self._mask
            parent, root = top.sid, int(self.root[pr])
            pid = int(self.pool[pr]) if pool is None else self._pool_id(pool)
        else:
            parent, root = -1, -1
            pid = -1 if pool is None else self._pool_id(pool)
        sid = self._row(_CODE[name], pid, parent, root,
                        np.nan if now is None else now, index)
        ann = TraceAnnotation(name)
        ann.__enter__()
        s = _Span(self, sid, ann)
        _OPEN.append(s)
        self.start[sid & self._mask] = time.perf_counter()
        return s

    def _close(self, s: _Span) -> None:
        r = s.sid & self._mask
        self.end[r] = time.perf_counter()
        s.ann.__exit__(None, None, None)
        _OPEN.pop()                             # ``with`` closes LIFO
        if self.parent[r] < 0:
            self.on_root(s.sid)

    def _compiled(self, parent: _Span, start: float, end: float,
                  hit: bool) -> None:
        pr = parent.sid & self._mask
        sid = self._row(_COMPILE, int(self.pool[pr]), parent.sid,
                        int(self.root[pr]), np.nan)
        r = sid & self._mask
        self.start[r] = start
        self.end[r] = end
        self.cache_hit[r] = hit

    def rows(self) -> dict[str, np.ndarray]:
        """Every closed span the ring still holds, in id order: ``id``,
        ``name`` and ``pool`` (strings; '' for no pool), ``parent`` (-1
        for a root), ``root``, ``start`` and ``end`` (``perf_counter``
        seconds), ``now`` (the caller's clock, NaN where none was
        given), ``h2d`` and ``d2h`` (bytes), ``cache_hit`` (compiles:
        1 from the persistent cache, 0 compiled; -1 for other spans) and
        ``index`` (the leg round of ``gateway.round``; -1 for none)."""
        ids = np.arange(max(self.next_id - self.capacity, 0), self.next_id)
        r = ids & self._mask
        done = ~np.isnan(self.end[r])
        ids, r = ids[done], r[done]
        pools = np.asarray(self.pools + [""], object)
        return {
            "id": ids,
            "name": np.asarray(_NAMES, object)[self.name[r]],
            "pool": pools[self.pool[r]],
            "parent": self.parent[r],
            "root": self.root[r],
            "start": self.start[r],
            "end": self.end[r],
            "now": self.now[r],
            "h2d": self.h2d[r],
            "d2h": self.d2h[r],
            "cache_hit": self.cache_hit[r],
            "index": self.index[r],
        }


def span(tel, name: str, pool: Optional[str] = None,
         now: Optional[float] = None):
    """Open ``name`` in ``tel``'s table (``tel`` a ``Telemetry`` or
    None): a root, unless a span of the same table is open."""
    if tel is None:
        return _NO_SPAN
    return tel.spans.open(name, pool, now)


def child(name: str, pool: Optional[str] = None, index: int = -1):
    """Open ``name`` under the innermost open span, in its table;
    nothing records when no span is open."""
    if not _OPEN:
        return _NO_SPAN
    top = _OPEN[-1]
    return top.table.open(name, pool, index=index)


def moved_to_device(nbytes: int) -> None:
    """Charge ``nbytes`` uploaded host→device to the innermost open
    span."""
    if _OPEN:
        top = _OPEN[-1]
        top.table.h2d[top.sid & top.table._mask] += nbytes


def readback(x) -> np.ndarray:
    """``np.asarray(x)``, its bytes charged to the innermost open span
    as moved device→host."""
    a = np.asarray(x)
    if _OPEN:
        top = _OPEN[-1]
        top.table.d2h[top.sid & top.table._mask] += a.nbytes
    return a


# -- the process-wide compile listener ---------------------------------------
_listening = False
_cache_hit = False


def _listen() -> None:
    """Register the compile listener with ``jax.monitoring``, once per
    process."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = True


def _on_event(event: str, **_kw) -> None:
    global _cache_hit
    if event == _CACHE_HIT_EVENT:
        _cache_hit = True


def _on_duration(event: str, duration: float, **_kw) -> None:
    global _cache_hit
    if event != _COMPILE_EVENT:
        return
    hit, _cache_hit = _cache_hit, False
    if _OPEN:
        top = _OPEN[-1]
        end = time.perf_counter()
        top.table._compiled(top, end - duration, end, hit)
