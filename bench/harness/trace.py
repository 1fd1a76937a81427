"""Host spans, the device trace, and their reduction to numbers.

The benchmark puts a ``jax.profiler.TraceAnnotation`` around each of its
own calls into the program (``Spans``), so in a traced run the host's
spans and the device's operations share the profiler's clock.  ``Trace``
reads the profiler's ``.xplane.pb`` with JAX alone and keeps what the
reduction needs: per device, the operations and the programs (modules)
that ran, and the host spans.  All times are nanoseconds on that clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import time
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that contain others in the trace (a scan's while loop spans
#: its body's operations); left out where operation times are summed
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?( |$)")
_SHAPE = re.compile(r"^[a-z0-9]+\[[0-9,]*\]")
_KIND = re.compile(r"kind=(k\w+)")


def op_name(text: str) -> str:
    """A trace event's instruction name with its result shape and fusion
    kind: ``%fusion.12 = f32[24576]{0} fusion(...), kind=kCustom`` gives
    ``fusion.12 f32[24576] kCustom``."""
    name, _, rest = text.partition(" = ")
    parts = [name.lstrip("%")]
    shape, kind = _SHAPE.match(rest), _KIND.search(rest)
    parts += [m.group(m.lastindex or 0) for m in (shape, kind) if m]
    return " ".join(parts)


class Spans:
    """Named host spans: written into the profiler's trace when it runs,
    and kept here on the host's clock (seconds) for metrics that read
    them without a trace."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, t0: float = -1e300,
                  t1: float = 1e300) -> list[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= t0 and e <= t1]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block into ``log_dir``; python call tracing stays off,
    so the host side holds the benchmark's spans and the runtime's own."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


class Trace:
    """The parts of one profile the reduction reads."""

    def __init__(self, devices: dict, spans: list):
        # devices: {plane name: {"ops": [(name, t0, t1)],
        #                        "modules": [(name, t0, t1)]}}
        self.devices = devices
        self.spans = spans   # [(name, t0, t1)] the benchmark's host spans

    @classmethod
    def from_file(cls, path: str, span_names,
                  device_prefix: str = "/device:TPU:"):
        """``span_names``: the names of the benchmark's host spans; any
        other host event is ignored."""
        from jax.profiler import ProfileData

        span_names = set(span_names)
        data = ProfileData.from_file(path)
        devices, spans = {}, []
        for plane in data.planes:
            if plane.name.startswith(device_prefix):
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines:
                    continue
                dev = devices.setdefault(plane.name,
                                         {"ops": [], "modules": []})
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE)):
                    if name in lines:
                        dev[key] = [(op_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in lines[name].events]
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name in span_names:
                            spans.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
        return cls(devices, spans)

    @classmethod
    def from_json(cls, d: dict):
        devs = {k: {kk: [tuple(e) for e in v] for kk, v in dev.items()}
                for k, dev in d["devices"].items()}
        return cls(devs, [tuple(s) for s in d["spans"]])

    # -- the window --------------------------------------------------------

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[0] == "window"]
        if not w:
            raise ValueError("the trace holds no 'window' span")
        return w[0][1], w[0][2]

    def device_names(self) -> list[str]:
        return sorted(self.devices, key=_device_order)

    def ops(self, device: str, t0: float, t1: float) -> list:
        """Operations of ``device`` clipped to [t0, t1]."""
        return _clip(self.devices[device]["ops"], t0, t1)

    def modules(self, device: str, t0: float, t1: float) -> list:
        return _clip(self.devices[device]["modules"], t0, t1)


def _device_order(name: str):
    tail = name.rsplit(":", 1)[-1]
    return (int(tail) if tail.isdigit() else 1 << 30, name)


def _clip(events, t0, t1) -> list:
    out = []
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: Trace, device: str, t0: float, t1: float) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in
                                       trace.ops(device, t0, t1)))


def idle_gaps(trace: Trace, device: str, t0: float, t1: float):
    """[(start, end)] of the window not covered by any operation."""
    gaps, cur = [], t0
    for s, e in union((s, e) for _, s, e in trace.ops(device, t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def host_activity(trace: Trace, s: float, e: float) -> str:
    """The innermost benchmark span (other than the window) that covers
    most of [s, e]: what the host was doing during a device gap."""
    best, best_cover, best_len = "none", 0.0, float("inf")
    for name, hs, he in trace.spans:
        if name == "window":
            continue
        cover = min(he, e) - max(hs, s)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover
                                  and he - hs < best_len):
            best, best_cover, best_len = name, cover, he - hs
    return best


def breakdown(trace: Trace, device: str, top: int = 10) -> dict:
    """The device operations that took most time, summed by name, and the
    longest idle gaps named by what the host was doing (seconds)."""
    t0, t1 = trace.window()
    per_op: dict[str, float] = defaultdict(float)
    for name, s, e in trace.ops(device, t0, t1):
        if not CONTAINERS.match(name):
            per_op[name] += e - s
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, device, t0, t1),
                  key=lambda g: -(g[1] - g[0]))[:top]
    return {
        "device_ops": [[n, v * 1e-9] for n, v in ops],
        "idle_gaps": [[host_activity(trace, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def device_summary(trace: Trace, devices: list[str]) -> dict:
    """busy_s averaged over ``devices`` and the traced window's length."""
    t0, t1 = trace.window()
    busy = [busy_ns(trace, d, t0, t1) for d in devices]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (t1 - t0) * 1e-9}
