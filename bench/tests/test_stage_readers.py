"""The readers of the program's own records (``repro.obs``): device time by
stage on a hand-made trace with a given stage map, program spans and
compile records; each reads nothing where its scope, span or record is
absent, and nothing from a program without ``repro.obs``."""
import pytest

from bench.harness import program, readers, spec
from bench.harness import trace as TR
from repro import obs

DEV = "/device:TPU:0"
STAGE_METRICS = {"sort_us": "chunk_sort", "score_us": "score",
                 "merge_us": "merge", "evict_us": "evict",
                 "fold_us": "fold"}


@pytest.fixture
def rec(monkeypatch):
    r = obs.Recorder()
    monkeypatch.setattr(obs, "_recorder", r)
    return r


def _ctx(ops, mods, counters, window=(0.0, 10.0)):
    tr = TR.Trace({DEV: {"ops": ops, "modules": mods}},
                  [("window", 0, 10_000)])
    return readers.Context(tr, [DEV], counters, TR.Spans(), window, {}, {},
                           {})


def _chunk_ctx(program_name="update_multi"):
    ops = [("sort_pairs.3 s32[8]", 100, 150),         # chunk_sort
           ("capscore_agg.4 f32[8]", 150, 170),       # score
           ("fusion.1 s32[8] kCustom", 170, 370),     # merge
           ("fusion.2 f32[8] kCustom", 370, 520),     # evict
           ("fusion.5 f32[8] kLoop", 520, 700),       # fold
           ("copy.6 f32[8]", 700, 720),               # no stage
           ("while.7", 100, 720),                     # container
           ("fusion.1 s32[8] kCustom", 1000, 1005)]   # another module
    mods = [("jit__update_multi_impl(123)", 100, 800),
            ("jit__lambda(9)", 1000, 1010)]
    return _ctx(ops, mods, {"program": program_name, "chunks": 2})


CHUNK_MAP = obs.StageMap("jit__update_multi_impl", {
    "sort_pairs.3": "chunk_sort", "capscore_agg.4": "score",
    "fusion.1": "merge", "fusion.2": "evict", "fusion.5": "fold"})


@pytest.mark.parametrize("metric,us", [("sort_us", 0.025),
                                       ("score_us", 0.010),
                                       ("merge_us", 0.100),
                                       ("evict_us", 0.075),
                                       ("fold_us", 0.090)])
@pytest.mark.parametrize("split", ["ingest", "bank"])
def test_stage_readers_sum_by_stage(rec, metric, us, split):
    rec.stage_maps["update_multi"] = CHUNK_MAP
    value = spec.load_reader(f"{metric}.{split}")(_chunk_ctx())
    assert value == pytest.approx(us)


def test_stages_cover_the_program_but_its_unnamed_ops(rec):
    rec.stage_maps["update_multi"] = CHUNK_MAP
    t = program.stage_ns(_chunk_ctx(), "update_multi")
    assert t == {"chunk_sort": 50, "score": 20, "merge": 200, "evict": 150,
                 "fold": 180, None: 20}


@pytest.mark.parametrize("metric", sorted(STAGE_METRICS))
def test_stage_reader_reads_nothing_without_its_scope(rec, metric):
    stages = {k: v for k, v in CHUNK_MAP.stages.items()
              if v != STAGE_METRICS[metric]}
    rec.stage_maps["update_multi"] = obs.StageMap(CHUNK_MAP.module, stages)
    assert spec.load_reader(f"{metric}.ingest")(_chunk_ctx()) is None


def test_stage_readers_read_nothing_without_a_stage_map(rec):
    # the entry never compiled in this process: no signature, no map
    for metric in STAGE_METRICS:
        assert spec.load_reader(f"{metric}.bank")(
            _chunk_ctx("update_bank")) is None


def _two_pass_ctx():
    ops = [("fusion.10 u32[8]", 0, 400),            # pass1_score
           ("fusion.11 s32[8] kCustom", 400, 900),  # pass1_fold
           ("collective-permute.1 s32[8]", 900, 910),
           ("fusion.12 f32[8]", 910, 1000),         # pass2
           ("all-reduce.3 f32[8]", 1000, 1005)]     # pass2 (its psum)
    return _ctx(ops, [("jit_program(77)", 0, 1010)], {"jobs": 2})


TWO_PASS_MAP = obs.StageMap("jit_program", {
    "fusion.10": "pass1_score", "fusion.11": "pass1_fold",
    "collective-permute.1": "exchange", "fusion.12": "pass2",
    "all-reduce.3": "pass2"})


def test_two_pass_readers(rec):
    rec.stage_maps["two_pass_multi"] = TWO_PASS_MAP
    ctx = _two_pass_ctx()
    assert spec.load_reader("pass1_ms.twopass")(ctx) == pytest.approx(
        900 * 1e-6 / 2)
    assert spec.load_reader("pass2_ms.twopass")(ctx) == pytest.approx(
        95 * 1e-6 / 2)


@pytest.mark.parametrize("metric,gone", [
    ("pass1_ms.twopass", ("pass1_score", "pass1_fold")),
    ("pass2_ms.twopass", ("pass2",))])
def test_two_pass_reader_reads_nothing_without_its_scope(rec, metric, gone):
    rec.stage_maps["two_pass_multi"] = obs.StageMap(TWO_PASS_MAP.module, {
        k: v for k, v in TWO_PASS_MAP.stages.items() if v not in gone})
    assert spec.load_reader(metric)(_two_pass_ctx()) is None


def _spans(rec, names):
    ids = iter(range(1, 100))
    for name, s, e in names:
        rec.spans.append(obs.Span(name, s, e, next(ids), None, None))


def test_span_readers(rec):
    _spans(rec, [("scheduler.step", 1.0, 1.01),
                 ("scheduler.admit", 1.0, 1.004),
                 ("scheduler.tick", 1.004, 1.01),
                 ("scheduler.step", 2.0, 2.02),
                 ("scheduler.admit", 2.0, 2.002),
                 ("scheduler.tick", 2.002, 2.02),
                 ("scheduler.step", 20.0, 20.5),          # after the window
                 ("scheduler.admit", 20.0, 20.5)])
    ctx = _ctx([], [], {})
    assert spec.load_reader("admit_ms.bank")(ctx) == pytest.approx(3.0)
    assert spec.load_reader("tick_ms.bank")(ctx) == pytest.approx(12.0)


@pytest.mark.parametrize("metric", ["admit_ms.bank", "tick_ms.bank"])
def test_span_reader_reads_nothing_without_its_span(rec, metric):
    _spans(rec, [("scheduler.step", 1.0, 1.01)])
    assert spec.load_reader(metric)(_ctx([], [], {})) is None


def test_compile_reader(rec):
    C = obs.CompileRecord
    rec.compiles.extend([
        C("trace", "f", -9.0, -8.0, "service.build", None),
        C("compile", "g", -8.5, -7.5, "service.observe", 3),  # overlaps
        C("compile", "marker", -7.0, -6.0, None, None),       # no span
        C("compile", "h", 5.0, 6.0, "service.observe", 9)])   # in window
    for split in ("ingest", "bank", "twopass"):
        assert spec.load_reader(f"compile_s.{split}")(
            _ctx([], [], {})) == pytest.approx(1.5)


def test_compile_reader_reads_nothing_without_records(rec):
    rec.compiles.append(obs.CompileRecord("compile", "marker", -2.0, -1.0,
                                          None, None))
    assert spec.load_reader("compile_s.ingest")(_ctx([], [], {})) is None


NEW_METRICS = ([f"{m}.{s}" for m in STAGE_METRICS for s in ("ingest", "bank")]
               + ["pass1_ms.twopass", "pass2_ms.twopass", "admit_ms.bank",
                  "tick_ms.bank", "compile_s.ingest", "compile_s.bank",
                  "compile_s.twopass"])


def test_a_program_without_obs_gives_nothing(rec, monkeypatch):
    """The readers run over the parent's program too, which has no
    ``repro.obs``: they read nothing there, and do not raise."""
    rec.stage_maps["update_multi"] = CHUNK_MAP
    rec.stage_maps["two_pass_multi"] = TWO_PASS_MAP
    _spans(rec, [("scheduler.step", 1.0, 1.01),
                 ("scheduler.admit", 1.0, 1.004)])
    monkeypatch.setattr(program, "_obs", lambda: None)
    assert len(NEW_METRICS) == 17
    for metric in NEW_METRICS:
        ctx = _two_pass_ctx() if "twopass" in metric else _chunk_ctx()
        assert spec.load_reader(metric)(ctx) is None
