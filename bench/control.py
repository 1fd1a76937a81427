#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] --seconds <s>
        [--control-seeds <m>]

For each seed, in one process: set up the cell, drive its window at the
cell's own load and sizes, take what the timed path produced, and compare
it with the plain reference (the lower readings).  Then put the reference
itself, computed in bfloat16, in the program's place and compare that (the
control's readings, which must fail), on the first ``--control-seeds``
seeds (all by default).  One JSON line per seed, with every number the
driver computes, compared or not.  The
benchmark's own runs never run this.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=None)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import compare, spec
    from bench.harness import trace as TR
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.find_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    limits = cell.config["limits"]
    n_control = len(args.seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(args.seeds):
        drv = spec.load_driver(cell.mix["driver"])(cell.config, cell.mix,
                                                   seed, TR.Spans())
        drv.setup()
        drv.window(args.seconds)
        got = drv.outputs()
        drv.release()
        want = drv.reference("float32")
        program = drv.numbers(got, want)
        line = {"seed": seed, "attempted": drv.attempted,
                "program": program,
                "program_correct": compare.judge(program, limits)[0]}
        if i < n_control:
            ctrl = drv.numbers(
                drv.as_outputs(drv.reference("bfloat16"), "bfloat16"), want)
            line.update(control=ctrl,
                        control_correct=compare.judge(ctrl, limits)[0])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
