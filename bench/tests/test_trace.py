"""The reduction from a device trace to busy time, idle gaps named by host
spans, kernel and collective sums, and the per-layer readers: on hand-made
events, and on a small trace recorded from the chip."""
from pathlib import Path

import pytest

from bench.harness import readers, spec
from bench.harness import trace as TR

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"


def _toy():
    ops = [("fusion.1", 100, 200), ("capscore_agg.3", 150, 250),
           ("all-reduce.2", 400, 450), ("fusion.1", 700, 900),
           ("while.4", 100, 250)]
    mods = [("jit__update_multi_impl", 100, 260)]
    spans = [("window", 0, 1000), ("observe", 0, 90), ("wait", 260, 700),
             ("observe", 900, 1000)]
    return TR.Trace({DEV: {"ops": ops, "modules": mods}}, spans)


def test_union_and_gaps_cover_the_window():
    tr = _toy()
    assert TR.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    busy = TR.busy_ns(tr, DEV, 0, 1000)
    gaps = TR.idle_gaps(tr, DEV, 0, 1000)
    assert busy == 150 + 50 + 200
    assert busy + sum(e - s for s, e in gaps) == 1000
    assert TR.device_summary(tr, [DEV]) == {
        "busy_s": pytest.approx(400e-9), "window_s": pytest.approx(1e-6)}


def test_breakdown_names_gaps_by_host_span():
    b = TR.breakdown(_toy(), DEV)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert b["idle_gaps"] == [["wait", pytest.approx(250e-9)],     # 450..700
                              ["wait", pytest.approx(150e-9)],     # 250..400
                              ["observe", pytest.approx(100e-9)],  # 0..100
                              ["observe", pytest.approx(100e-9)]]  # 900..


def test_readers_on_toy_trace():
    tr = _toy()
    ctx = readers.Context(tr, [DEV], {"chunks": 2, "chunk": 2048, "lanes": 4,
                                      "jobs": 1, "program": "update_multi"}, TR.Spans(), (0, 1), {
                              "hbm_bytes_per_s": 819e9}, {}, {})
    assert readers.idle_percent(ctx) == pytest.approx(60.0)
    assert spec.load_reader("chunk_us.ingest")(ctx) == pytest.approx(0.08)
    assert spec.load_reader("collective_ms.twopass")(ctx) == \
        pytest.approx(50e-6)
    share = spec.load_reader("kernel_share.ingest")(ctx)
    assert share == pytest.approx(100 * 100 / 400)
    roof = spec.load_reader("capscore_agg_roofline.ingest")(ctx)
    assert 0 < roof


def test_recorded_chip_trace():
    """0.4 s of the single-service ingest window on one TPU v5e (2^15-element
    batches, 4 lanes, k=4096), as ``Trace.from_file`` read it."""
    import gzip
    import json

    with gzip.open(DATA / "adcap-ingest-trace.json.gz", "rt") as f:
        tr = TR.Trace.from_json(json.load(f))
    dev, = tr.device_names()
    t0, t1 = tr.window()
    busy = TR.busy_ns(tr, dev, t0, t1)
    gaps = TR.idle_gaps(tr, dev, t0, t1)
    assert 0 < busy <= t1 - t0
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(t1 - t0)
    b = TR.breakdown(tr, dev)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    assert {n for n, _ in b["idle_gaps"]} <= {"observe", "wait", "none"}
    ctx = readers.Context(tr, [dev], {"chunks": 16 * 12, "chunk": 2048,
                                      "lanes": 4, "program": "update_multi"}, TR.Spans(), (0, 1),
                          {"hbm_bytes_per_s": 819e9}, {}, {})
    share = spec.load_reader("kernel_share.ingest")(ctx)
    roof = spec.load_reader("capscore_agg_roofline.ingest")(ctx)
    idle = spec.load_reader("device_idle.ingest")(ctx)
    assert 0 < share < 100 and 0 < roof <= 100 and 0 <= idle < 100
    t, calls = readers.op_time_ns(ctx, readers.KERNEL_PATTERNS["chunksort"])
    assert calls > 0 and t > 0
    assert spec.load_reader("chunk_us.ingest")(ctx) > 0
