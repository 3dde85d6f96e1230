"""Computations shared by the per-layer metric readers.

A metric's own file (``bench/metrics/<name>.py``) names what it reads: the
loop's counts it uses, the kernel's event names in the trace.  The
arithmetic common to several metrics lives here, once.  Every reader
returns ``None`` when it finds nothing to read, never 0.
"""
from __future__ import annotations

from typing import Optional, Sequence

from bench import counts, trace


def idle_frac(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on the chips:
    1 minus the union of the device operations' intervals, averaged over
    the chips."""
    if not ctx.trace.device_ops:
        return None
    return 1.0 - trace.device_busy_s(ctx.trace, ctx.lo, ctx.hi) / ctx.window_s


def mfu(ctx) -> Optional[float]:
    """Share of the chips' bf16 peak: the FLOPs one unit of the window's
    work requires (the loop's ``unit_flops``, counted from shapes) times
    the units of the traced window, over the window's length times the
    chips times the peak."""
    flops = ctx.counts.get("unit_flops")
    if not flops or not ctx.window.units:
        return None
    return 100.0 * flops * ctx.window.units / (
        ctx.window_s * ctx.cell.chips * ctx.peaks["bf16_flops"])


def kernel_roofline(ctx, names: Sequence[str], kernel: str
                    ) -> Optional[float]:
    """A kernel's share of its roofline: the least time the chip could take
    for the operations and bytes one unit of work requires of it (the
    loop's ``<kernel>_flops`` and ``<kernel>_bytes``) times the units of
    the window, over the device time of the events named by ``names``
    (regular expressions), per chip."""
    ops = ctx.trace.device_ops
    if not ops or not ctx.window.units:
        return None
    secs = sum(trace.seconds_of(v, names, ctx.lo, ctx.hi)
               for v in ops.values()) / len(ops)
    if secs <= 0:
        return None
    units = ctx.window.units
    return 100.0 * counts.roofline_seconds(
        ctx.counts[kernel + "_flops"] * units,
        ctx.counts[kernel + "_bytes"] * units, ctx.peaks) / secs
