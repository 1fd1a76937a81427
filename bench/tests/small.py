"""Cells at sizes a CPU test run can hold (the shapes of the chip cells,
scaled down), shared by the tests."""
import copy
import time

from bench.harness import spec
from bench.harness.runner import run_cell


def small_cell(name: str) -> spec.Cell:
    cell = spec.find_cell(name)
    cell = copy.deepcopy(cell)
    c, m = cell.config, cell.mix
    if m["driver"] == "service_ingest":
        c["service"].update(k=128, chunk=256)
        m.update(batch=2048, pool_batches=3, warmup_batches=2)
    elif m["driver"] == "bank_ingest":
        c["service"].update(k=64, chunk=256)
        c["n_tenants"] = 8
        m.update(request=256, pool_requests=32, check_tenants=3)
    else:
        c["service"].update(k=64, chunk=256)
        c["job_elements"] = 4 * 256 * 4
    return cell


def run_small(name: str, seed: int = 2**31 + 5, seconds: float = 0.5):
    return run_cell(small_cell(name), seed=seed, seconds=seconds,
                    trace=False, t_start=time.perf_counter(),
                    strict_device=False)
