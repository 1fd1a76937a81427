"""The program's own measurements (``repro.obs``): spans and their nesting,
the bounded ring, counters, compile records, the spans in a profile, and
the stage maps of the ingest programs at small sizes."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.stats.scheduler import ServeConfig, StatsScheduler
from repro.stats.service import (MultiTenantStats, StatsConfig,
                                 StreamStatsService)


@pytest.fixture
def rec(monkeypatch):
    """A fresh process recorder for the test (the program records into
    ``obs.recorder()``)."""
    r = obs.Recorder()
    r.listen()
    monkeypatch.setattr(obs, "_recorder", r)
    yield r
    r.close()


def test_spans_nest_with_parent_and_request_ids():
    r = obs.Recorder()
    with r.span("outer", request=7):
        with r.span("inner"):
            pass
        with r.span("other", request=9):
            pass
    with r.span("alone"):
        pass
    by = {s.name: s for s in r.spans}
    assert [s.name for s in r.spans] == ["inner", "other", "outer", "alone"]
    assert by["inner"].parent == by["outer"].id
    assert by["other"].parent == by["outer"].id
    assert by["outer"].parent is None and by["alone"].parent is None
    assert by["inner"].request == 7 and by["other"].request == 9
    assert by["alone"].request is None
    o, i = by["outer"], by["inner"]
    assert o.start <= i.start <= i.end <= o.end


def test_span_ends_when_its_block_raises():
    r = obs.Recorder()
    with pytest.raises(ValueError):
        with r.span("fails"):
            raise ValueError
    with r.span("next"):
        pass
    assert [s.name for s in r.spans] == ["fails", "next"]
    assert r.spans[1].parent is None


def test_ring_is_bounded_and_counts_what_it_drops():
    r = obs.Recorder(capacity=4)
    for i in range(10):
        with r.span(f"s{i}"):
            pass
    assert [s.name for s in r.spans] == ["s6", "s7", "s8", "s9"]
    assert r.counters["obs.spans_dropped"] == 6


def test_counters_are_monotone_sums():
    r = obs.Recorder()
    r.count("a")
    r.count("a", 4)
    r.count("b", 0)
    assert r.counters == {"a": 5, "b": 0}


def test_compile_inside_a_span_is_attributed_to_it(rec):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    with rec.span("build", request=11):
        f(jnp.arange(5.0)).block_until_ready()
    f(jnp.arange(5.0)).block_until_ready()          # cached: no record
    mine = [c for c in rec.compiles if "<lambda>" in c.fun]
    assert {c.kind for c in mine} == {"trace", "lower", "compile"}
    assert all(c.span == "build" and c.request == 11 for c in mine)
    assert all(c.start <= c.end for c in mine)
    rec.close()
    jax.jit(lambda x: x - 2.0)(jnp.arange(3.0)).block_until_ready()
    assert len([c for c in rec.compiles if "<lambda>" in c.fun]) \
        == len(mine)


def test_span_name_appears_in_a_cpu_profile(tmp_path):
    from jax.profiler import ProfileData

    r = obs.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with r.span("obs_test.span"):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    assert "obs_test.span" in names


def test_stage_of_reads_the_innermost_stage_scope():
    assert obs.stage_of("jit(f)/while/body/merge/gather") == "merge"
    assert obs.stage_of("jit(f)/vmap(evict)/vmap(jit(sort))/sort") == "evict"
    assert obs.stage_of("jit(f)/to_keysorted/while/body/fold/x") == "fold"
    assert obs.stage_of("jit(f)/while/body/add") is None
    with pytest.raises(ValueError):
        obs.stage("not_a_stage")


def test_service_stage_map_names_every_chunk_stage(rec):
    cfg = StatsConfig(k=64, ls=(1.0, 16.0), chunk=256)
    svc = StreamStatsService(cfg)
    keys = np.arange(1024, dtype=np.int64) % 300
    svc.observe(keys)
    sig = rec.signatures["update_multi"]
    svc.observe(keys)                     # same shapes: no new signature
    svc.observe(keys[:100])               # held in the remainder
    assert rec.signatures["update_multi"] is sig
    m = rec.stage_map("update_multi")
    assert m.module == "jit__update_multi_impl"
    assert set(m.stages.values()) == set(obs.CHUNK_STAGES) | {
        "to_keysorted", "from_keysorted"}
    assert rec.stage_map("update_multi") is m
    # elements held in the remainder: elements - chunk * chunks
    assert rec.counters["ingest.elements"] == 2148
    assert rec.counters["ingest.chunks"] == 8
    names = [s.name for s in rec.spans]
    assert names.count("service.build") == 1
    assert names.count("service.observe") == 3
    builds = [c for c in rec.compiles if c.span == "service.build"]
    observes = [c for c in rec.compiles if c.span == "service.observe"
                and c.kind == "compile"
                and "_update_multi_impl" in c.fun]
    assert builds and len(observes) == 1


def test_scheduler_spans_counters_and_bank_stage_map(rec):
    T = 4
    bank = MultiTenantStats(StatsConfig(k=32, ls=(1.0, 8.0), chunk=128),
                            n_tenants=T)
    sched = StatsScheduler(bank, ServeConfig())
    for step in range(2):
        for t in range(T if step == 0 else 2):   # step 2: two tenants
            sched.submit_ingest(t, np.arange(128) + 1000 * t)
        sched.step()
    assert rec.counters["bank.lanes_run"] == 2 * T
    assert rec.counters["bank.lanes_real"] == T + 2
    steps = [s for s in rec.spans if s.name == "scheduler.step"]
    assert [s.request for s in steps] == [1, 2]
    for s in rec.spans:
        if s.name in ("scheduler.admit", "scheduler.tick"):
            parent = next(p for p in steps if p.id == s.parent)
            assert parent.request == s.request
    tick = next(s for s in rec.spans if s.name == "bank.tick")
    assert next(s for s in rec.spans if s.id == tick.parent).name \
        == "scheduler.tick"
    assert [s.name for s in rec.spans].count("bank.build") == 1
    m = rec.stage_map("update_bank")
    assert m.module == "jit__update_bank_impl"
    assert set(m.stages.values()) == set(obs.CHUNK_STAGES) | {
        "to_keysorted", "from_keysorted", "mask_tenants"}


def test_merge_sort_route_counts_lane_chunks_on_the_sort_form(
        rec, force_merge_form):
    """``merge.sort_route`` counts the lane-chunk merges dispatched on the
    sort form: chunks x lanes a service dispatch, tenants run x lanes a bank
    tick.  The CPU's own form is the gather form, so it stays 0 there."""
    cfg = StatsConfig(k=32, ls=(1.0, 8.0, 64.0), chunk=128)
    svc = StreamStatsService(cfg)
    T = 3
    bank = MultiTenantStats(StatsConfig(k=32, ls=(1.0, 8.0), chunk=128),
                            n_tenants=T)
    svc.observe(np.arange(512))           # 4 chunks, 3 lanes
    bank.observe(0, np.arange(128))
    bank.tick()
    assert rec.counters["merge.sort_route"] == 0
    force_merge_form("sort")
    svc.observe(np.arange(512) + 7)
    assert rec.counters["merge.sort_route"] == 4 * 3
    svc.observe(np.arange(100))           # held in the remainder
    assert rec.counters["merge.sort_route"] == 4 * 3
    bank.observe(1, np.arange(128))
    bank.tick()                           # every tenant's lanes run
    assert rec.counters["merge.sort_route"] == 4 * 3 + T * 2
    assert rec.counters["ingest.chunks"] == 8


def test_two_pass_program_is_a_job_span_with_a_stage_map(rec):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import distributed as DD

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    fn = DD.make_distributed_two_pass_multi(mesh, ls=(1.0, 16.0), salt=3,
                                            k=32, chunk=128)
    sh = NamedSharding(mesh, PartitionSpec("data"))
    keys = jax.device_put(np.arange(512, dtype=np.int32) % 97, sh)
    w = jax.device_put(np.ones(512, np.float32), sh)
    jax.block_until_ready(fn(keys, w))
    assert [s.name for s in rec.spans] == ["twopass.job"]
    stages = set(rec.stage_map("two_pass_multi").stages.values())
    # one device: the butterfly exchange has no hop, so no operation
    assert {"pass1_score", "pass1_fold", "pass2"} <= stages
    assert stages <= set(obs.TWO_PASS_STAGES)
