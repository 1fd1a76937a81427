#!/usr/bin/env python3
"""Counts the programs the serving path compiles per scheduler step: the
``tenant-bank`` deployment under ingest from every tenant and the
``serve_synthetic`` cap x segment query mix, tenants drawn Zipf(1.1).

    python3 bench/tools/serve_compiles.py --steps 30 --queries 128 --seed 1

A serving cell may enter the benchmark only once no step compiles after
warm-up; this prints, per step, the compiles, the distinct tenants the
step's queries touched and the step's host time, as one JSON line each.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench.harness import spec
    from bench.harness.drive import stats_config
    from bench.harness.runner import CompileCount
    from bench.harness.streams import ZipfKeys, seeded_rng
    from repro.core import freqfns
    from repro.core.segments import HashBucket
    from repro.launch.compile_cache import enable_compile_cache
    from repro.stats.scheduler import ServeConfig, StatsScheduler
    from repro.stats.service import MultiTenantStats

    enable_compile_cache()
    compiles = CompileCount()
    cfg = spec.find_cell("bank-ingest").config
    T = int(cfg["n_tenants"])
    rng = seeded_rng(args.seed, 1)
    keys = ZipfKeys(seeded_rng(args.seed, 0), int(cfg["key_universe"]),
                    float(cfg["zipf_a"]))
    pop = np.arange(1, T + 1, dtype=np.float64) ** -1.1
    pop /= pop.sum()
    caps = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0]
    segments = [None] + [HashBucket(8, b) for b in range(8)]
    sched = StatsScheduler(MultiTenantStats(stats_config(cfg["service"]),
                                            n_tenants=T),
                           ServeConfig(**cfg["serve"]))
    for step in range(args.steps):
        for t in range(T):
            sched.submit_ingest(t, keys.draw(rng, 2048))
        tenants = rng.permutation(T)[rng.choice(T, args.queries, p=pop)]
        for t in tenants:
            sched.submit_query(int(t), freqfns.cap(float(rng.choice(caps))),
                               segments[int(rng.integers(len(segments)))])
        n0, t0 = compiles.n, time.perf_counter()
        done = sched.step()
        jax.effects_barrier()
        print(json.dumps({"step": step, "compiles": compiles.n - n0,
                          "tenants": len(set(tenants.tolist())),
                          "answered": len(done),
                          "step_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
