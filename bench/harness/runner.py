"""One run of one cell: set-up, the measured window, the output check.

``run_cell`` does everything but the look for the chip, which the entry
point (``bench/run.py``) makes first; tests drive it on the CPU.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from . import compare, readers, spec
from . import trace as TR

PEAKS = Path(__file__).with_name("peaks.json")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCount:
    """Programs compiled (or loaded from the persistent cache) so far."""

    def __init__(self):
        import jax

        self.n = 0

        def listen(event, duration, **kw):
            if event == BACKEND_COMPILE:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int):
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str, *, strict: bool) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        if strict:
            raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}")
        return {}
    return table[kind]


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, strict_device: bool = True,
             compiles: CompileCount | None = None) -> dict:
    """Run the cell once; returns the result line as a dict (``checks``
    last).  ``t_start`` is when the process started, on the host clock."""
    chips = int(cell.workload["chips"])
    info = device_info(chips)
    peaks = peaks_for(info["kind"], strict=strict_device)
    compiles = compiles or CompileCount()
    spans = TR.Spans()
    drv = spec.load_driver(cell.mix["driver"])(cell.config, cell.mix, seed,
                                               spans)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[bench] {cell.workload['name']}: seed={seed} setup_s={setup_s!r} "
        f"compiles_in_setup={compiles.n}")

    n0 = compiles.n
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            with TR.capture(tdir):
                with spans("window"):
                    res = drv.window(seconds)
        else:
            with spans("window"):
                res = drv.window(seconds)
        in_window = compiles.n - n0
        log(f"[bench] compiles_in_window={in_window} "
            f"counters={json.dumps(res['counters'])}")
        info["memory_peak_bytes"] = memory_peak(chips)
        got = drv.outputs()
        drv.release()
        gc.collect()

        result = {"correct": False, "attempted": drv.attempted,
                  "failed": drv.failed, "metrics": {}, "device": info}
        if trace:
            tr = TR.Trace.from_file(TR.find_xplane(tdir),
                                    {s[0] for s in spans.records})
            planes = tr.device_names()[:chips]
            info.update(TR.device_summary(tr, planes))
            result["breakdown"] = TR.breakdown(tr, planes[0])
            w = [s for s in spans.records if s[0] == "window"][0][1:]
            ctx = readers.Context(tr, planes, res["counters"], spans, w,
                                  peaks, cell.config, cell.mix)
            for m in cell.per_layer:
                value = spec.load_reader(m["name"])(ctx)
                if value is None:
                    log(f"[bench] metric {m['name']} found nothing to read "
                        "in this run and is left out")
                else:
                    result["metrics"][m["name"]] = {"value": float(value),
                                                    "unit": m["unit"]}
        else:
            values = dict(res["e2e"], setup_s=setup_s)
            for m in cell.end_to_end:
                if m["name"] in values:
                    result["metrics"][m["name"]] = {
                        "value": float(values[m["name"]]), "unit": m["unit"]}
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    t0 = time.perf_counter()
    want = drv.reference("float32")
    numbers = drv.numbers(got, want)
    correct, checks = compare.judge(numbers, cell.config["limits"])
    log(f"[bench] reference_s={time.perf_counter() - t0!r}")
    result["correct"] = bool(correct)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {correct}")
    return result
