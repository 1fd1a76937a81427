#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chips of this host.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root.  The cell (``BENCHMARK.json`` workloads) names
a configuration (``bench/configs``) and a traffic mix (``bench/traffic``);
per-layer metrics are read by ``bench/metrics/<name>.py`` from the device
trace of a ``--trace 1`` run.  The run exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.  The last line
of standard output is the result as one JSON object; the last lines of
standard error give each compared number beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from bench.harness import spec

    cell = spec.find_cell(args.workload)

    import jax

    from bench.harness.runner import CompileCount, run_cell
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    chips = int(cell.workload["chips"])
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX's first device is a {devs[0].platform!r} device",
              file=sys.stderr)
        return 3
    if len(devs) < chips:
        print(f"the cell needs {chips} chips, JAX finds {len(devs)}",
              file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START,
                      compiles=CompileCount())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
