"""The chip the run holds, and what it compiled."""
from __future__ import annotations

import time


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(jax, chips: int):
    """The devices, or :class:`NoChip`.  There is no CPU fallback."""
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"JAX could not start a backend ({exc})") from exc
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX sees {len(devices)} {devices[0].platform} "
                     f"device(s), no TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")
    return devices


def describe(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


class CompileLog:
    """Backend compiles seen by this process: (function name, seconds,
    whether it came from the persistent cache, when it ended on the
    ``perf_counter`` clock)."""

    def __init__(self, jax) -> None:
        self.events: list[tuple[str, float, bool, float]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kw.get("fun_name"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.events.append((name, duration, self._hit,
                                time.perf_counter()))
            self._hit = False
