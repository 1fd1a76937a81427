#!/usr/bin/env python3
"""Bring-up smoke run of the frequency-cap statistics service on a TPU.

    python chip_smoke.py [--seed N]                # one chip, phases a-e
    python chip_smoke.py --four-chips [--seed N]   # the distributed two-pass
                                                   # program on four chips only

One process, no child processes; all data is made from ``--seed``.

  a. device        platform, device kind, device count, JAX version; exits
                   non-zero when the first device is not a TPU.
  b. single        ``StreamStatsService(StatsConfig())`` at the shipped
                   defaults observes a Zipf(1.3) stream of 2^22 elements over
                   2^20 int32 keys, answers cap/distinct/total queries over
                   all keys and hash-bucket segments (each within 4 stated
                   stderr of ``freqfns.exact_statistic``), reconciles (pass
                   II weights equal the exact per-key totals), and checks the
                   compiled Pallas routes against their XLA duals.
  c. query plane   ``QueryEngine`` answers equal the host estimator loop bit
                   for bit, over the service's sketches and over a mixed set
                   of every estimator path and sketch kind.
  d. serving       ``MultiTenantStats`` with 256 tenants behind
                   ``StatsScheduler``, driven by ``launch.stats_serve``'s
                   serve loop; three tenants checked against the exact
                   reference.
  e. shard tier    an in-process 4-shard ``ShardTier``: a shard is killed
                   and recovered under load; exact-mode answers equal a
                   fault-free tier's.

``--four-chips`` runs only ``core.distributed.make_distributed_two_pass_multi``
on a 4-device mesh and compares it with the exact reference, with the same
program on a 1-device mesh, and with its shard body run for the same four
shards on one device.

Any failed check raises (non-zero exit, no result line).  The times printed
are bring-up readings of one run, not benchmark numbers.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.core import distributed as DD  # noqa: E402
from repro.core import estimators as E  # noqa: E402
from repro.core import freqfns as F  # noqa: E402
from repro.core import incremental as I  # noqa: E402
from repro.core import segments as SEG  # noqa: E402
from repro.core import vectorized as V  # noqa: E402
from repro.core.samplers import SampleResult  # noqa: E402
from repro.kernels.capscore.ops import capscore_agg  # noqa: E402
from repro.kernels.chunksort.ops import sort_with_perm  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.stats_serve import serve_synthetic  # noqa: E402
from repro.stats.query import Query, QueryEngine  # noqa: E402
from repro.stats.scheduler import ServeConfig, StatsScheduler  # noqa: E402
from repro.stats.service import (  # noqa: E402
    MultiTenantStats,
    StatsConfig,
    StreamStatsService,
)
from repro.stats.shardtier import ShardTier, TierConfig  # noqa: E402

EMPTY = int(SEG.EMPTY)
CAPS = (1.0, 4.0, 16.0, 64.0, 256.0, 4096.0)
SEGMENTS = (None, SEG.HashBucket(4, 0), SEG.HashBucket(4, 3),
            SEG.HashBucket(16, 5))
Z_MAX = 4.0  # estimates must lie within this many stated stderr of truth


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def reading(phase: str, **kv) -> None:
    """A bring-up reading: one run's wall clock on the chip's host."""
    body = ", ".join(f"{k}={v}" for k, v in kv.items())
    say(f"[{phase}] bring-up reading, not a benchmark number: {body}")


def zipf_stream(rng, n: int, n_keys: int, a: float = 1.3) -> np.ndarray:
    """``n`` elements, Zipf(a) over ``n_keys`` distinct int32 keys: rank r
    drawn with probability proportional to r^-a, mapped to a key id by a
    seeded bijection onto [0, 2^31 - 1) (never the EMPTY sentinel)."""
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(p)
    ranks = np.minimum(np.searchsorted(cdf / cdf[-1], rng.random(n)),
                       n_keys - 1)
    prime = 2**31 - 1
    ids = (rng.permutation(n_keys).astype(np.int64) * 1_000_003
           + int(rng.integers(prime))) % prime
    return ids[ranks].astype(np.int32)


def truth_of(stream: np.ndarray):
    return np.unique(stream, return_counts=True)


def check_within_stderr(label: str, queries, res, ukeys, counts) -> float:
    """Every estimate within Z_MAX stated stderr of the exact statistic (an
    estimate with zero stated stderr must be exact).  Returns the max |z|."""
    worst = 0.0
    for q, est, se in zip(queries, res.estimates, res.stderr):
        truth = F.exact_statistic(q.fn, counts, q.segment, keys=ukeys)
        seg = SEG.as_segment(q.segment).describe()
        if se > 0:
            z = abs(est - truth) / se
        else:
            z = 0.0 if est == truth else math.inf
        check(z <= Z_MAX, f"{label}: {q.fn.name} over {seg}: estimate "
              f"{est!r} vs exact {truth!r} is {z:.2f} stderr ({se!r}) away")
        worst = max(worst, z)
    return worst


def stat_queries(caps=CAPS, segments=SEGMENTS):
    fns = [F.cap(T) for T in caps] + [F.distinct(), F.total()]
    return [Query(fn, seg) for fn in fns for seg in segments]


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------


def phase_device(n_chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    say(f"[a] platform={d.platform} device_kind={d.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        say(f"[a] FAIL: this run needs a TPU; JAX's first device is a "
            f"{d.platform!r} device")
        sys.exit(2)
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    say(f"[a] compile cache: {enable_compile_cache()}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ---------------------------------------------------------------------------
# b. single service at the shipped defaults
# ---------------------------------------------------------------------------


def phase_single(rng, *, cfg: StatsConfig, n_elements: int, n_keys: int,
                 batch: int) -> tuple[StreamStatsService, np.ndarray]:
    stream = zipf_stream(rng, n_elements, n_keys)
    ukeys, counts = truth_of(stream)
    say(f"[b] StatsConfig k={cfg.k} ls={tuple(cfg.ls)} chunk={cfg.chunk}; "
        f"stream {n_elements} elements, key universe {n_keys}, "
        f"{len(ukeys)} distinct keys observed")
    svc = StreamStatsService(cfg)

    t0 = time.perf_counter()
    svc.observe(stream[:batch])
    svc.state_dict()  # device -> host copy: waits for the dispatch
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(batch, n_elements, batch):
        svc.observe(stream[i:i + batch])
    svc.state_dict()
    t_rest = time.perf_counter() - t0
    check(svc.n_observed == n_elements, "service lost elements")
    reading("b", first_batch_incl_compile_s=t_first, ingest_s=t_rest,
            elements_per_s=(n_elements - batch) / t_rest,
            resident_bytes=svc.resident_bytes)

    # the chunk step observe ran: the compiled Pallas routes are inside it
    state, spec = svc._sampler.state, svc._sampler.spec
    lowered = I._update_multi_donated.lower(
        state, jnp.zeros(batch, jnp.int32), jnp.ones(batch, jnp.float32),
        spec)
    n_custom = lowered.as_text().count("tpu_custom_call")
    say(f"[b] chunk step (backend={spec.backend}, sort_backend="
        f"{spec.sort_route}): tpu_custom_call count = {n_custom}")
    check(n_custom > 0, "the chunk step holds no Mosaic kernel")

    qs = stat_queries()
    t0 = time.perf_counter()
    res = svc.query_batch(qs)
    t_q = time.perf_counter() - t0
    z1 = check_within_stderr("b/1-pass", qs, res, ukeys, counts)
    say(f"[b] 1-pass: {len(qs)} queries within {Z_MAX} stderr of exact "
        f"(max |z| {z1:.3f})")

    t0 = time.perf_counter()
    for i in range(0, n_elements, batch):
        svc.reconcile(stream[i:i + batch])
    ex = svc.query_batch(qs, exact=True)
    t_rec = time.perf_counter() - t0
    z2 = check_within_stderr("b/2-pass", qs, ex, ukeys, counts)
    for l, lane in svc.exact_sketches().items():
        pos = np.searchsorted(ukeys, lane.keys)
        check(np.array_equal(ukeys[pos], lane.keys),
              f"lane l={l}: sampled a key that never occurred")
        check(np.array_equal(lane.counts, counts[pos].astype(np.float64)),
              f"lane l={l}: pass-II weights differ from the exact totals")
    say(f"[b] 2-pass: pass-II weights equal the exact per-key totals on "
        f"every lane; {len(qs)} queries within {Z_MAX} stderr "
        f"(max |z| {z2:.3f})")
    reading("b", first_query_batch_incl_compile_s=t_q,
            reconcile_and_query_s=t_rec)
    return svc, stream


def phase_kernels(rng, svc: StreamStatsService, stream: np.ndarray) -> None:
    """Compiled Pallas routes vs their XLA duals, on the chip."""
    cfg = svc.config
    sk = svc.sketches()
    ls = jnp.asarray(cfg.ls, jnp.float32)
    taus = jnp.asarray([sk[float(l)].tau for l in cfg.ls], jnp.float32)
    salt = jnp.uint32(cfg.salt)
    for C in (cfg.chunk, 2 * cfg.chunk, 8 * cfg.chunk):
        start = int(rng.integers(len(stream) - C))
        keys = stream[start:start + C].copy()
        keys[rng.random(C) < 0.05] = EMPTY
        keys = jnp.asarray(keys)
        want = SEG.stable_sort_with_perm(keys)
        got = sort_with_perm(keys, backend="pallas")
        for w_, g_, nm in zip(want, got, ("keys", "perm")):
            check(np.array_equal(np.asarray(w_), np.asarray(g_)),
                  f"chunksort C={C}: {nm} differ from the argsort dual")
        eids = jnp.arange(start, start + C, dtype=jnp.int32)
        ws = jnp.asarray(rng.exponential(1.0, C) + 0.1, jnp.float32)
        o = SEG.chunk_order(keys, eids, ws, sort_backend="xla")
        args = (o.ks, o.eids, o.ws, o.seg, ls, taus, salt)
        ref = capscore_agg(*args, backend="xla")
        out = capscore_agg(*args, backend="pallas")
        names = ("w_total", "entered", "contrib", "kb_min", "min_score")
        for nm, g, r in zip(names, out, ref):
            g, r = np.asarray(g), np.asarray(r)
            if nm in ("w_total", "contrib"):
                # in-block reassociated f32 sums: tests/test_ingest_order.py
                check(np.allclose(g, r, rtol=2e-6, atol=1e-6),
                      f"capscore_agg C={C}: {nm} beyond f32 reassociation "
                      f"(max |diff| {np.max(np.abs(g - r))!r})")
            else:
                check(np.array_equal(g, r),
                      f"capscore_agg C={C}: {nm} not bit-identical "
                      f"({int(np.sum(g != r))} of {g.size} differ)")
        say(f"[b] C={C}: chunksort bit-identical to the argsort dual; "
            f"capscore_agg entered/kb_min/min_score bit-identical, sums "
            f"within f32 reassociation")


# ---------------------------------------------------------------------------
# c. query plane bit-identity
# ---------------------------------------------------------------------------


def mixed_lanes(rng) -> dict:
    """One sketch per estimator path x sketch kind, plus the tau=inf edge
    (the set the query-plane contract is pinned on in the test suite)."""
    s = (rng.zipf(1.5, size=20000) % 5000).astype(np.int64)
    return {
        2.0: V.sample_two_pass(s, None, k=200, l=2.0, kind="continuous",
                               salt=1),
        3.0: V.sample_two_pass(s, None, k=150, l=3.0, kind="discrete",
                               salt=2),
        1.0: V.sample_two_pass(s, None, k=100, l=1, kind="distinct", salt=3),
        9.0: V.sample_two_pass(s, None, k=100, l=1e9, kind="sh", salt=4),
        5.0: V.sample_fixed_k(s, None, k=300, l=5.0, salt=5),
        7.0: V.sample_fixed_tau(s, None, tau=0.02, l=7, kind="discrete",
                                salt=6),
        8.0: V.sample_fixed_tau(s, None, tau=0.05, l=1, kind="distinct",
                                salt=7),
        6.0: V.sample_fixed_tau(s, None, tau=0.01, l=1e9, kind="sh", salt=8),
        4.0: V.sample_fixed_k(np.array([1, 1, 2, 3, 3, 3]), None, k=100,
                              l=5.0, salt=0, chunk=8),
    }


def check_bit_identical(label: str, answer, sketches: dict, queries) -> int:
    res = answer(queries)
    bad = []
    for q, est in zip(queries, res.estimates):
        want = E.estimate(sketches[q.l], q.fn, q.segment)
        if float(est) != want:
            bad.append((q.fn.name, q.l, SEG.as_segment(q.segment).describe(),
                        float(est), want))
    check(not bad, f"{label}: {len(bad)} of {len(queries)} answers differ "
          f"from the host estimator loop, e.g. {bad[:3]}")
    return len(queries)


def phase_query_plane(rng, svc: StreamStatsService) -> None:
    t0 = time.perf_counter()
    fns = [F.cap(T) for T in CAPS] + [F.distinct(), F.total(), F.moment(1.5),
                                      F.log1p(), F.threshold(4.0)]
    qs = [Query(fn, seg, float(l)) for l in svc.config.ls for fn in fns
          for seg in SEGMENTS]
    n1 = check_bit_identical(
        "c/service 1-pass", functools.partial(svc.query_batch, exact=False),
        svc.sketches(), qs)
    n1 += check_bit_identical(
        "c/service 2-pass", functools.partial(svc.query_batch, exact=True),
        svc.exact_sketches(), qs)

    lanes = mixed_lanes(rng)
    segs = [None, lambda keys: keys % 3 == 0, np.arange(0, 5000, 11),
            SEG.HashBucket(8, 3)]
    fns = [F.cap(5), F.cap(20), F.distinct(), F.total(), F.threshold(4.0),
           F.moment(1.5), F.log1p()]
    qs = [Query(fn, seg, l) for l in lanes for seg in segs for fn in fns]
    n2 = check_bit_identical("c/mixed", QueryEngine(lanes).query_batch,
                             lanes, qs)
    say(f"[c] QueryEngine == host estimator loop bit for bit: {n1} queries "
        f"over the service's 1-pass and 2-pass sketches, {n2} over every "
        f"estimator path")
    reading("c", wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# d. multi-tenant serving
# ---------------------------------------------------------------------------


def phase_serving(rng, *, n_tenants: int, steps: int, requests: int) -> None:
    # the serving configuration launch.stats_serve ships with
    cfg = StatsConfig(k=512, ls=(1.0, 8.0, 64.0), chunk=2048)
    svc = MultiTenantStats(cfg, n_tenants=n_tenants)
    sched = StatsScheduler(svc, ServeConfig(max_ingest_per_step=16,
                                            max_queries_per_step=256))
    t0 = time.perf_counter()
    lat, finished, streams = serve_synthetic(
        sched, rng, steps=steps, requests=requests, stream_batch=2048,
        ingest_per_step=16, record=range(n_tenants),
        log=lambda m: say(f"[d] {m}"))
    wall = time.perf_counter() - t0
    check(finished > 0 and finished == sched.n_queries_answered
          and sched.pending_queries == 0,
          f"serve loop answered {finished} of "
          f"{sched.n_queries_answered + sched.pending_queries} queries")
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    reading("d", wall_s=wall, queries=finished,
            elements=sched.n_elements_ingested,
            latency_p50_ms=float(np.percentile(lat_ms, 50)),
            latency_p99_ms=float(np.percentile(lat_ms, 99)))

    busiest = sorted(streams, key=lambda t: -len(streams[t]))[:3]
    caps = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
    segs = (None, SEG.HashBucket(8, 0), SEG.HashBucket(8, 5))
    for t in busiest:
        stream = streams[t]
        check(svc.n_observed(t) == len(stream),
              f"tenant {t}: observed {svc.n_observed(t)} of {len(stream)}")
        ukeys, counts = truth_of(stream)
        qs = stat_queries(caps, segs)
        res = svc.query_batch([(t, q.fn, q.segment) for q in qs])
        z = check_within_stderr(f"d/tenant {t}", qs, res, ukeys, counts)
        say(f"[d] tenant {t}: {len(stream)} elements, {len(qs)} queries "
            f"within {Z_MAX} stderr of exact (max |z| {z:.3f})")
    say(f"[d] {n_tenants} tenants, resident_bytes={svc.resident_bytes}")


# ---------------------------------------------------------------------------
# e. shard tier with a kill under load
# ---------------------------------------------------------------------------


def phase_shard_tier(rng, *, cfg: StatsConfig, n_elements: int, n_keys: int,
                     n_batches: int) -> None:
    stream = zipf_stream(rng, n_elements, n_keys)
    ukeys, counts = truth_of(stream)
    batches = np.array_split(stream, n_batches)
    tcfg = TierConfig(n_shards=4, checkpoint_every=4, retain_wal=True,
                      auto_recover=False)
    kill_at, recover_at, victim = n_batches // 3, 2 * n_batches // 3, 2
    qs = stat_queries()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_tier_") as root:
        ref = ShardTier(cfg, tcfg, Path(root) / "ref")
        live = ShardTier(cfg, tcfg, Path(root) / "live")
        for i, b in enumerate(batches):
            ref.ingest(b)
            if i == kill_at:
                live.kill_shard(victim)
            live.ingest(b)
            if i == kill_at:
                check(live.membership()[victim] == "down",
                      "the killed shard was not detected")
                deg = live.query_batch(qs, mode="approx")
                check(deg.degraded and deg.coverage < 1.0,
                      "answers with a shard down are not stamped degraded")
                say(f"[e] shard {victim} killed at batch {i}: approx "
                    f"answers degraded, coverage {deg.coverage:.4f}")
            if i == recover_at:
                check(live.recover_shard(victim), "recover_shard failed")
                say(f"[e] shard {victim} recovered at batch {i} (WAL "
                    f"replay), ingest continuing")
        check(all(v == "up" for v in live.membership().values()),
              f"tier not whole after recovery: {live.membership()}")
        a = ref.query_batch(qs, mode="exact")
        b = live.query_batch(qs, mode="exact")
    for nm in ("estimates", "stderr"):
        check(np.array_equal(getattr(a, nm), getattr(b, nm)),
              f"exact {nm} after kill+recover differ from the fault-free "
              f"tier")
    z = check_within_stderr("e/exact", qs, a, ukeys, counts)
    say(f"[e] exact answers after kill+recover np.array_equal to the "
        f"fault-free tier ({len(qs)} queries, max |z| vs exact {z:.3f})")
    reading("e", wall_s=time.perf_counter() - t0,
            elements_per_tier=n_elements)


# ---------------------------------------------------------------------------
# four chips: the distributed two-pass program
# ---------------------------------------------------------------------------


def lane_results(keys, seeds, w, ls, k) -> dict:
    """Per-lane 2-pass SampleResults from the program's bottom-(k+1)
    summaries (the k smallest seeds; tau the (k+1)-th)."""
    out = {}
    for j, l in enumerate(ls):
        kk, ss, ww = keys[j], seeds[j], w[j]
        live = kk != EMPTY
        kk, ss, ww = kk[live], ss[live], ww[live]
        order = np.argsort(ss, kind="stable")
        tau = float(ss[order[k]]) if len(kk) > k else math.inf
        top = np.sort(order[:k])
        out[float(l)] = SampleResult(
            keys=kk[top], counts=ww[top].astype(np.float64), tau=tau,
            l=float(l), kind="continuous", exact_weights=True)
    return out


def check_two_pass(label, out, ls, k, ukeys, counts) -> float:
    keys, seeds, w = (np.asarray(a) for a in out)
    for j in range(len(ls)):
        live = keys[j] != EMPTY
        pos = np.searchsorted(ukeys, keys[j][live])
        check(np.array_equal(ukeys[pos], keys[j][live]),
              f"{label}: lane {j} sampled a key that never occurred")
        check(np.array_equal(w[j][live], counts[pos].astype(np.float32)),
              f"{label}: lane {j} pass-2 weights differ from exact totals")
    lanes = lane_results(keys, seeds, w, ls, k)
    qs = [Query(F.cap(T), seg, l) for l in lanes for T in CAPS
          for seg in SEGMENTS[:2]]
    return check_within_stderr(label, qs, QueryEngine(lanes).query_batch(qs),
                               ukeys, counts)


def phase_four_chips(rng, *, n_elements: int, n_keys: int) -> None:
    cfg = StatsConfig()
    ls, k = tuple(cfg.ls), cfg.k
    kw = dict(ls=ls, salt=cfg.salt, k=k, chunk=cfg.chunk)
    devs = jax.devices()[:4]
    stream = zipf_stream(rng, n_elements, n_keys)
    ukeys, counts = truth_of(stream)
    w = np.ones(n_elements, np.float32)
    say(f"[4] stream {n_elements} elements over {n_keys} keys "
        f"({len(ukeys)} observed); k={k} ls={ls} chunk={cfg.chunk}")

    mesh4 = make_mesh((4,), ("data",), devices=devs)
    shard4 = NamedSharding(mesh4, PartitionSpec("data"))
    kd, wd = jax.device_put(stream, shard4), jax.device_put(w, shard4)
    on = {s.device for s in kd.addressable_shards}
    check(on == set(devs), f"input shards on {on}, not on {devs}")
    fn4 = DD.make_distributed_two_pass_multi(mesh4, **kw)
    t0 = time.perf_counter()
    compiled = fn4.lower(kd, wd).compile()
    t_compile = time.perf_counter() - t0
    hlo = compiled.as_text()
    colls = {op: hlo.count(op) for op in ("all-gather", "collective-permute",
                                          "all-reduce")}
    say(f"[4] 4-chip program: collectives in the compiled HLO {colls}, "
        f"tpu_custom_call {hlo.count('tpu_custom_call')}")
    check(sum(colls.values()) > 0, "the 4-chip program has no collectives")
    t0 = time.perf_counter()
    out4 = jax.block_until_ready(compiled(kd, wd))
    t_run = time.perf_counter() - t0
    on = {s.device for s in out4[0].addressable_shards}
    check(on == set(devs), f"output replicas on {on}, not on {devs}")
    reps = [np.asarray(a) for a in out4]
    for a in reps:
        check(all(np.array_equal(a[0], a[d]) for d in range(1, 4)),
              "the four replicas of the merged sample disagree")
    out4 = tuple(a[0] for a in reps)
    z4 = check_two_pass("4/4-chip", out4, ls, k, ukeys, counts)
    say(f"[4] 4-chip mesh: shards on {len(on)} devices, pass-2 weights "
        f"equal the exact totals, cap estimates within {Z_MAX} stderr "
        f"(max |z| {z4:.3f})")
    reading("4", compile_s=t_compile, run_s=t_run,
            elements_per_s=n_elements / t_run)

    # the same shard body for the same four shards, on one device: the
    # cross-device merges must change nothing
    body = functools.partial(DD.two_pass_multi_shard, axis_name="data", **kw)
    one = jax.jit(jax.vmap(body, axis_name="data"))
    kd1 = jax.device_put(stream.reshape(4, -1), devs[0])
    wd1 = jax.device_put(w.reshape(4, -1), devs[0])
    emu = tuple(np.asarray(a)[0] for a in one(kd1, wd1))
    for nm, a, b in zip(("keys", "seeds", "weights"), out4, emu):
        check(np.array_equal(a, b), f"4-chip {nm} differ from the same four "
              "shards run on one device")
    say("[4] 4-chip result bit-identical to the same four shards on one "
        "device")

    mesh1 = make_mesh((1,), ("data",), devices=devs[:1])
    shard1 = NamedSharding(mesh1, PartitionSpec("data"))
    fn1 = DD.make_distributed_two_pass_multi(mesh1, **kw)
    t0 = time.perf_counter()
    out1 = jax.block_until_ready(fn1(jax.device_put(stream, shard1),
                                     jax.device_put(w, shard1)))
    t1 = time.perf_counter() - t0
    out1 = tuple(np.asarray(a)[0] for a in out1)
    z1 = check_two_pass("4/1-device mesh", out1, ls, k, ukeys, counts)
    say(f"[4] same program on a 1-device mesh: pass-2 weights exact, cap "
        f"estimates within {Z_MAX} stderr (max |z| {z1:.3f})")
    reading("4", one_device_mesh_incl_compile_s=t1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed two-pass on 4 chips")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    t_start = time.perf_counter()
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips(rng, n_elements=1 << 22, n_keys=1 << 20)
    else:
        cfg = StatsConfig()
        t0 = time.perf_counter()
        svc, stream = phase_single(rng, cfg=cfg, n_elements=1 << 22,
                                   n_keys=1 << 20, batch=1 << 18)
        phase_kernels(rng, svc, stream)
        reading("b", wall_s=time.perf_counter() - t0)
        phase_query_plane(rng, svc)
        t0 = time.perf_counter()
        phase_serving(rng, n_tenants=256, steps=12, requests=300)
        reading("d", phase_wall_s=time.perf_counter() - t0)
        phase_shard_tier(rng, cfg=cfg, n_elements=1 << 20, n_keys=1 << 18,
                         n_batches=12)
    reading("all", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
