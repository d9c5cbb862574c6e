"""Shared by the roofline readers: a kernel's share of its roofline over
the traced window.

The least time the chip could take for every call of the kernel in the
traced window is the larger of its operations over peak FLOP/s and its
bytes over peak bandwidth, both counted from the calls' shapes by
``bench/kernels/<kernel>.py``; the share is that time over the device
seconds the trace gives the kernel's program.  Nothing to read (no call
traced, or no device time) gives None, never 0."""
from __future__ import annotations


def share(ctx, kernel: str):
    calls = ctx.traced_calls(kernel)
    device_s = (ctx.trace or {}).get("kernel_s", {}).get(kernel)
    if not calls or not device_s:
        return None
    work = ctx.bench.kernel(kernel).work
    flops = sum(work(**c)[0] for c in calls)
    nbytes = sum(work(**c)[1] for c in calls)
    peak = ctx.bench.peak(ctx.device["kind"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / device_s
