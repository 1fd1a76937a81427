"""A configuration, a traffic mix, a driver and a per-layer metric dropped
in as new files, with new entries in BENCHMARK.json, are found by name: no
file the benchmark already has is edited.  A metric split by end-to-end
metric with no file of its own reads with its family's reader."""
import hashlib
import json
import shutil
from pathlib import Path

from bench.harness import spec

ROOT = Path(__file__).resolve().parents[2]


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics", "drivers"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    before = _digest(tmp_path)

    cfg = json.loads((tmp_path / "bench/configs/adcap-forecast.json")
                     .read_text())
    cfg["name"] = "adcap-skewed"
    cfg["zipf_a"] = 1.5
    (tmp_path / "bench/configs/adcap-skewed.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/small-batches.json").write_text(json.dumps(
        {"driver": "tiny_batches", "batch": 16384, "pool_batches": 8,
         "inflight": 2, "warmup_batches": 2}))
    (tmp_path / "bench/drivers/tiny_batches.py").write_text(
        "from bench.harness.drive import Base\n\n\n"
        "class Driver(Base):\n    pass\n")
    (tmp_path / "bench/metrics/batch_count.small.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('batches')\n")

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "adcap-skewed", "source": "x",
                             "file": "bench/configs/adcap-skewed.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "skewed-small", "chips": 1,
                               "config": "adcap-skewed",
                               "traffic": "small-batches", "why": "x"})
    bench["end_to_end"][0]["workloads"].append("skewed-small")
    bench["per_layer"].append({"name": "batch_count.small", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "ingest_eps",
                               "workloads": ["skewed-small"]})
    bench["per_layer"].append({"name": "device_idle.small", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "ingest_eps",
                               "workloads": ["skewed-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.find_cell("skewed-small", root=tmp_path)
    assert cell.config["zipf_a"] == 1.5
    assert cell.mix["batch"] == 16384
    assert [m["name"] for m in cell.per_layer] == ["batch_count.small",
                                                   "device_idle.small"]
    drv = spec.load_driver(cell.mix["driver"], root=tmp_path)
    assert drv.__name__ == "Driver" and drv.__module__.endswith("tiny_batches")
    family = spec.load_reader("device_idle.small", root=tmp_path)
    assert family.__module__.endswith("device_idle")
    assert {m["name"] for m in cell.end_to_end} == {"ingest_eps", "setup_s"}
    read = spec.load_reader("batch_count.small", root=tmp_path)
    assert read(type("Ctx", (), {"counters": {"batches": 7}})()) == 7

    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_listed_piece_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))
        assert callable(spec.load_driver(cell.mix["driver"]))
        assert set(cell.config["limits"])
