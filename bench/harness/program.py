"""What the program records about itself (``repro.obs``), reduced to the
per-layer metrics' numbers: device time by stage, program spans and
set-up compile time.

* ``stage_ns``: the first chip's operations inside the modules of one
  jitted entry point in the traced window, summed by the stage the
  program's stage map (``repro.obs.stage_map``) gives each operation's HLO
  instruction; operations no stage names sum under ``None``.
* ``span_ms_per``: program spans of one name in the window, summed and
  divided by the spans of another (a step).
* ``compile_s``: the seconds compiling or loading programs under a program
  span before the window.

A program without ``repro.obs`` (one older than it) gives nothing, and the
readers then return None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from . import trace as TR


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


#: the last reduction (trace, entry, result): the five stage readers of a
#: cell read one trace
_last: list = [None, None, None]


def stage_ns(ctx, entry: str) -> dict | None:
    """{stage: summed ns} over ``entry``'s modules on the first chip."""
    obs = _obs()
    if obs is None or ctx.trace is None or not ctx.devices:
        return None
    if _last[0] is ctx.trace and _last[1] == entry:
        return _last[2]
    smap = obs.stage_map(entry)
    if smap is None:
        return None
    t0, t1 = ctx.trace.window()
    dev = ctx.devices[0]
    mods = sorted((s, e) for n, s, e in ctx.trace.modules(dev, t0, t1)
                  if n.partition("(")[0] == smap.module)
    starts = [s for s, _ in mods]
    out: dict = defaultdict(float)
    for name, s, e in ctx.trace.ops(dev, t0, t1):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1] or TR.CONTAINERS.match(name):
            continue
        out[smap.stages.get(name.split(" ")[0])] += e - s
    _last[:] = [ctx.trace, entry, dict(out) if out else None]
    return _last[2]


def stage_time(ctx, entry: str, stages) -> float | None:
    """Summed ns of ``stages`` in ``entry``'s modules; None when none of
    them ran there."""
    t = stage_ns(ctx, entry)
    if not t:
        return None
    total = sum(t.get(s, 0.0) for s in stages)
    return total if total > 0 else None


def per_chunk_us(ctx, stage: str) -> float | None:
    """One chunk-step stage's device time per chunk (per tenant-chunk in
    the bank) of the ingest program the driver names, in microseconds:
    the base of ``chunk_us``."""
    program, chunks = ctx.counters.get("program"), ctx.counters.get("chunks")
    if not program or not chunks:
        return None
    t = stage_time(ctx, program, (stage,))
    return t * 1e-3 / chunks if t else None


def span_ms_per(ctx, name: str, per: str) -> float | None:
    """Summed duration of the program spans ``name`` that lie in the
    window, over the number of ``per`` spans there, in milliseconds."""
    obs = _obs()
    if obs is None:
        return None
    t0, t1 = ctx.window
    spans = [s for s in list(obs.recorder().spans)
             if s.start >= t0 and s.end <= t1]
    n = sum(1 for s in spans if s.name == per)
    d = [s.end - s.start for s in spans if s.name == name]
    return 1e3 * sum(d) / n if d and n else None


def compile_s(ctx) -> float | None:
    """Seconds of set-up spent tracing, lowering, compiling or loading
    programs under a program span (the union of those records' intervals
    that end before the window)."""
    obs = _obs()
    if obs is None:
        return None
    t0 = ctx.window[0]
    recs = [(r.start, r.end) for r in list(obs.recorder().compiles)
            if r.span is not None and r.end <= t0]
    total = sum(e - s for s, e in TR.union(recs))
    return total if total > 0 else None
