"""Finds a cell's pieces by name: ``BENCHMARK.json`` pairs a configuration
with a traffic mix, and each lives in a file of its own under ``bench/``:

* ``bench/configs/<config>.json``, the deployment (and the limits of the
  comparison with its reference);
* ``bench/traffic/<traffic>.json``, the mix's parameters, which name its
  driver;
* ``bench/drivers/<driver>.py``, the code that drives a kind of mix
  (``harness/drive.py``);
* ``bench/metrics/<metric>.py``, the per-layer reader of a metric; where
  there is none, ``bench/metrics/<family>.py`` for a metric named
  ``<family>.<split>`` (``device_idle.ingest`` reads with
  ``device_idle.py``).

A later cell, mix, driver or metric is a new file and a new entry; nothing
here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    end_to_end: list   # metric entries this cell reports with --trace 0
    per_layer: list    # metric entries this cell reports with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, workload: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_names is None or metric.get("moves") in e2e_names


def find_cell(name: str, bench: dict | None = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, names)]
    return Cell(wl, config, mix, e2e, per_layer)


def _load(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT):
    """The ``read`` function of the metric's own file, or of its family's."""
    d = root / "bench" / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.')[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} in {d}")
    return _load(path, "bench_metric_").read


def load_driver(name: str, root: Path = ROOT):
    """The ``Driver`` class of ``bench/drivers/<name>.py``."""
    return _load(root / "bench" / "drivers" / f"{name}.py",
                 "bench_driver_").Driver
