"""A Pallas kernel's share of its HBM roofline, from the trace."""

from __future__ import annotations

from bench import kernel_bytes


def hbm_share(ctx, kernel: str):
    """(least time the kernel's bytes take at the device's HBM bandwidth) ÷
    (the kernel's device time), in %, over every traced call of the kernel
    whose result shapes the trace states; None when there is none."""
    moved, seconds = 0, 0.0
    for e in ctx.trace.kernel_events(kernel):
        hlo = e.stats.get("hlo", "")
        result = kernel_bytes.shapes_of(hlo.split(" = ", 1)[-1].split(" custom-call(", 1)[0])
        if not result or e.dur_ns <= 0:
            continue
        moved += kernel_bytes.KERNELS[kernel](result)
        seconds += e.dur_ns / 1e9
    if not seconds:
        return None
    return 100.0 * moved / ctx.peak("hbm_bytes_per_s") / seconds
