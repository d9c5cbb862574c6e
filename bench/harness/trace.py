"""From a profiler trace to device time, idle share and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  Device
planes are named ``/device:TPU:<n>``: their ``XLA Ops`` line holds one
event per operation run on the chip, their ``XLA Modules`` line one
event per program (``jit_<kernel>(<id>)``).  The benchmark's own host
spans (``quantum``, ``tick``, ``plan``, ``settle``, ``dispatch``,
``wait_arrival``) are ``TraceAnnotation`` events on the host plane, on
the same clock.

:func:`reduce_planes` works on plain (plane, line, name, start ns,
duration ns) rows, so a test can pin the reduction on a recorded trace
without a chip.
"""
from __future__ import annotations

import re
from pathlib import Path

HOST_SPANS = ("quantum", "tick", "plan", "settle", "dispatch",
              "wait_arrival")
_MODULE = re.compile(r"^(?:jit_)?([A-Za-z0-9_]+?)(?:\(\d+\))?$")


def profile_options(jax):
    """Device ops and the benchmark's own annotations only: the Python
    tracer would slow the loop it observes."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def rows_of(path: Path) -> list[tuple[str, str, str, int, int]]:
    """(plane, line, event name, start ns, duration ns) for the device
    planes and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                if device or ev.name in HOST_SPANS:
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def kernel_name(module: str) -> str:
    m = _MODULE.match(module)
    return m.group(1) if m else module


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce_planes(rows: list, top: int = 10) -> dict:
    """Busy time (union of op intervals, averaged over chips), the
    traced window (first to last host span or device op), per-kernel
    device seconds (``XLA Modules`` events by program), the operations
    that took most time, and the longest idle gaps, each labelled with
    the host span that covers most of it."""
    host = [(n, s, s + d) for p, l, n, s, d in rows
            if not p.startswith("/device:")]
    devices = sorted({p for p, *_ in rows if p.startswith("/device:")})
    ops = [(p, n, s, s + d) for p, l, n, s, d in rows
           if p.startswith("/device:") and l == "XLA Ops"]
    mods = [(n, d) for p, l, n, s, d in rows
            if p.startswith("/device:") and l == "XLA Modules"]
    points = [s for _, s, _ in host] + [b for _, _, b in host] \
        + [s for *_, s, _ in ops] + [b for *_, b in ops]
    if not devices or not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "kernel_s": {},
                "device_ops": [], "idle_gaps": [], "chips": len(devices)}
    lo, hi = min(points), max(points)
    busy_ns = 0
    gaps = []
    for dev in devices:
        merged = _union([(a, b) for p, _, a, b in ops if p == dev])
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    kernel_s: dict[str, float] = {}
    for n, d in mods:
        k = kernel_name(n)
        kernel_s[k] = kernel_s.get(k, 0.0) + d * 1e-9
    op_s: dict[str, float] = {}
    for _, n, a, b in ops:
        op_s[n] = op_s.get(n, 0.0) + (b - a) * 1e-9
    by_time = sorted(kernel_s.items(), key=lambda kv: -kv[1])[:top]
    if not by_time:
        by_time = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]

    def label(a: int, b: int) -> str:
        best, cover = "untraced host", 0
        for n, s, e in host:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        return best

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns * 1e-9 / len(devices),
        "window_s": (hi - lo) * 1e-9,
        "kernel_s": kernel_s,
        "device_ops": [[n, s] for n, s in by_time],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in longest],
        "chips": len(devices),
    }


def reduce(trace_dir: Path) -> dict:
    return reduce_planes(rows_of(find_xplane(trace_dir)))
