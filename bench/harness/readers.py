"""Helpers the per-layer metric readers (``bench/metrics/*.py``) share.

A reader is ``read(ctx) -> float | None``; it returns None when the run
holds nothing to read, and the harness then leaves the metric out.
"""
from __future__ import annotations

import dataclasses
import re

from . import trace as TR

#: instruction names the chunk step's Pallas kernels carry in the device
#: trace (the name of the kernel's function; under ``vmap`` prefixed)
KERNEL_PATTERNS = {
    "capscore_agg": re.compile(r"^(vmap_)?(jit_)?capscore_agg"),
    "capscore_multi": re.compile(r"^(vmap_)?(jit_)?capscore_multi"),
    "chunksort": re.compile(r"^(vmap_)?(jit_)?sort_pairs"),
}
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|collective-permute|"
                        r"all-to-all|reduce-scatter)")


@dataclasses.dataclass
class Context:
    trace: TR.Trace | None     # the traced window (None without --trace 1)
    devices: list              # trace planes of the chips the cell uses
    counters: dict             # what the driver counted in the window
    spans: TR.Spans            # the benchmark's host spans (host clock)
    window: tuple              # (start, end) of the window, host clock
    peaks: dict                # the device kind's row of peaks.json
    config: dict
    mix: dict


def window_ns(ctx: Context):
    return ctx.trace.window()


def idle_percent(ctx: Context):
    if ctx.trace is None or not ctx.devices:
        return None
    t0, t1 = window_ns(ctx)
    busy = [TR.busy_ns(ctx.trace, d, t0, t1) for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))


def op_time_ns(ctx: Context, pattern, device=None) -> tuple[float, int]:
    """(summed device time, event count) of operations matching
    ``pattern`` on ``device`` (the first chip by default)."""
    if ctx.trace is None or not ctx.devices:
        return 0.0, 0
    t0, t1 = window_ns(ctx)
    dev = device or ctx.devices[0]
    hits = [(s, e) for n, s, e in ctx.trace.ops(dev, t0, t1)
            if pattern.search(n)]
    return sum(e - s for s, e in TR.union(hits)), len(hits)


def module_time_ns(ctx: Context, pattern, device=None) -> float:
    if ctx.trace is None or not ctx.devices:
        return 0.0
    t0, t1 = window_ns(ctx)
    dev = device or ctx.devices[0]
    return sum(e - s for n, s, e in ctx.trace.modules(dev, t0, t1)
               if pattern.search(n))


def busy_ns(ctx: Context, device=None) -> float:
    t0, t1 = window_ns(ctx)
    return TR.busy_ns(ctx.trace, device or ctx.devices[0], t0, t1)


def kernel_share(ctx: Context):
    if ctx.trace is None:
        return None
    t = 0.0
    for pat in KERNEL_PATTERNS.values():
        t += op_time_ns(ctx, pat)[0]
    busy = busy_ns(ctx)
    return 100.0 * t / busy if t > 0 and busy > 0 else None


def host_span_mean_ms(ctx: Context, name: str):
    d = ctx.spans.durations(name, *ctx.window)
    return 1e3 * sum(d) / len(d) if d else None
