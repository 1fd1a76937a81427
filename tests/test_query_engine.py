"""Batched query plane: bit-identity vs the scalar estimator loop, segment
semantics, key validation, variance/CI calibration, pick_l grid warning."""
import math
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import estimators as E
from repro.core import freqfns as F
from repro.core import segments as SEG
from repro.core import vectorized as V
from repro.core.incremental import normalize_keys
from repro.stats.query import Query, QueryEngine
from repro.stats.service import StatsConfig, StreamStatsService

SEGMENTS = [None,
            lambda keys: keys % 3 == 0,
            np.arange(0, 5000, 11),       # id-list
            SEG.HashBucket(8, 3)]
FNS = [F.cap(5), F.cap(20), F.distinct(), F.total(), F.threshold(4.0),
       F.moment(1.5), F.log1p()]


@pytest.fixture(scope="module")
def lanes(zipf_stream):
    """One sketch per estimator path x scheme kind, plus the tau=inf edge."""
    s = zipf_stream
    return {
        # 2-pass (exact_weights) paths, every kind
        2.0: V.sample_two_pass(s, None, k=200, l=2.0, kind="continuous", salt=1),
        3.0: V.sample_two_pass(s, None, k=150, l=3.0, kind="discrete", salt=2),
        1.0: V.sample_two_pass(s, None, k=100, l=1, kind="distinct", salt=3),
        9.0: V.sample_two_pass(s, None, k=100, l=1e9, kind="sh", salt=4),
        # 1-pass paths: continuous coefficient form + discrete-spectrum tables
        5.0: V.sample_fixed_k(s, None, k=300, l=5.0, salt=5),
        7.0: V.sample_fixed_tau(s, None, tau=0.02, l=7, kind="discrete", salt=6),
        8.0: V.sample_fixed_tau(s, None, tau=0.05, l=1, kind="distinct", salt=7),
        6.0: V.sample_fixed_tau(s, None, tau=0.01, l=1e9, kind="sh", salt=8),
        # tau = inf: fewer than k+1 keys ever qualified
        4.0: V.sample_fixed_k(np.array([1, 1, 2, 3, 3, 3]), None, k=100,
                              l=5.0, salt=0, chunk=8),
    }


def test_query_batch_bit_identical_across_kinds(lanes):
    """The core contract: one 252-query mixed batch == the scalar loop,
    bit for bit, across 2-pass/1-pass x all kinds x segments x statistics
    (incl. the transcendental ones) and the tau=inf edge."""
    eng = QueryEngine(lanes)
    qs = [Query(fn, seg, l) for l in lanes for seg in SEGMENTS for fn in FNS]
    res = eng.query_batch(qs)
    for q, est in zip(qs, res.estimates):
        assert float(est) == E.estimate(lanes[q.l], q.fn, q.segment), \
            (q.fn.name, q.l, q.segment)
    # answers are stable across repeated batches (bank/plan caches)
    res2 = eng.query_batch(qs)
    np.testing.assert_array_equal(res.estimates, res2.estimates)


def test_query_batch_matches_singleton_batches(lanes):
    """Batching is pure vectorization: a 64-query batch == 64 one-query
    batches, bit for bit."""
    eng = QueryEngine(lanes)
    qs = [Query(fn, seg, l) for l in lanes for seg in SEGMENTS[:2]
          for fn in FNS[:4]][:64]
    big = eng.query_batch(qs)
    for i, q in enumerate(qs):
        one = eng.query_batch([q])
        assert float(one.estimates[0]) == float(big.estimates[i])


@pytest.fixture(scope="module")
def service(zipf_stream):
    svc = StreamStatsService(StatsConfig(k=512, ls=(1.0, 8.0, 64.0), chunk=1024))
    for i in range(0, len(zipf_stream), 7000):  # unaligned batches
        svc.observe(zipf_stream[i: i + 7000])
    return svc


def test_service_wrappers_bit_compatible(service):
    """query_cap/query_distinct/query_total are thin query_batch wrappers,
    bit-compatible with the scalar estimator on the picked lane."""
    sk = service.sketches()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for T in (1, 4, 8, 64):
            for seg in SEGMENTS:
                assert service.query_cap(T, seg) == E.estimate(
                    sk[service.pick_l(T)], F.cap(T), seg)
        assert service.query_distinct() == E.estimate(
            sk[service.pick_l(1.0)], F.distinct())
        assert service.query_total() == E.estimate(sk[64.0], F.total())


def test_service_exact_path_bit_identical(zipf_stream):
    """Exact (reconciled) query_batch == scalar loop over exact_sketches,
    and the jitted multi-lane pass II == the historical numpy accumulation."""
    svc = StreamStatsService(StatsConfig(k=256, ls=(1.0, 8.0), chunk=1024,
                                         host_id=0))
    svc.observe(zipf_stream)
    svc.reconcile(zipf_stream[:9000])
    svc.reconcile(zipf_stream[9000:])
    ek = svc.exact_sketches()
    qs = [Query(fn, seg) for fn in (F.cap(8), F.distinct(), F.total())
          for seg in SEGMENTS]
    res = svc.query_batch(qs, exact=True)
    for q, est in zip(qs, res.estimates):
        rq = svc._resolve_lane(q)
        assert float(est) == E.estimate(ek[rq.l], q.fn, q.segment)
    # jitted pass-II accumulators == np.searchsorted / np.add.at reference
    w = np.ones(len(zipf_stream), np.float64)
    k32 = zipf_stream.astype(np.int32)
    for lane in ek.values():
        ref = np.zeros(len(lane.keys), np.float64)
        loc = np.clip(np.searchsorted(lane.keys, k32), 0, len(lane.keys) - 1)
        m = lane.keys[loc] == k32
        np.add.at(ref, loc[m], w[m])
        np.testing.assert_array_equal(ref, lane.counts)


@settings(max_examples=12, deadline=None)
@given(T=st.floats(min_value=0.5, max_value=200),
       salt=st.integers(min_value=0, max_value=2**31 - 1),
       seg_mod=st.integers(min_value=1, max_value=7))
def test_property_engine_matches_scalar(zipf_stream, T, salt, seg_mod):
    """Property form of the contract on a fresh 1-pass sketch: arbitrary
    cap_T, salt and predicate segment."""
    res = V.sample_fixed_k(zipf_stream[:8192], None, k=128, l=8.0, salt=salt)
    eng = QueryEngine({8.0: res})
    seg = (lambda keys: keys % seg_mod == 0)
    batch = eng.query_batch([Query(F.cap(T), seg, 8.0),
                             Query(F.threshold(T), seg, 8.0)])
    assert float(batch.estimates[0]) == E.estimate(res, F.cap(T), seg)
    assert float(batch.estimates[1]) == E.estimate(res, F.threshold(T), seg)


def test_variance_ci_monte_carlo(zipf_stream, zipf_truth):
    """The HT plug-in variance must be calibrated: across independent
    sampler randomness the normal 95% CI covers the truth most of the time
    and the stderr tracks the empirical spread within a small factor."""
    _, cnts = zipf_truth
    truth = F.exact_statistic(F.cap(8), cnts)
    ests, covered, stderrs = [], 0, []
    reps = 40
    for r in range(reps):
        res = V.sample_fixed_k(zipf_stream, None, k=200, l=8.0, salt=900 + r)
        b = QueryEngine({8.0: res}).query_batch([Query(F.cap(8), None, 8.0)])
        ests.append(float(b.estimates[0]))
        stderrs.append(float(b.stderr[0]))
        covered += int(b.ci_low[0] <= truth <= b.ci_high[0])
    emp_sd = float(np.std(ests))
    med_se = float(np.median(stderrs))
    assert covered / reps >= 0.6, f"CI95 coverage {covered}/{reps}"
    assert med_se > 0
    assert 1 / 4 < med_se / emp_sd < 4, (med_se, emp_sd)


def test_exact_lane_variance_zero_when_everything_sampled():
    res = V.sample_fixed_k(np.array([1, 1, 2, 3]), None, k=64, l=2.0, chunk=8)
    assert math.isinf(res.tau)
    b = QueryEngine({2.0: res}).query_batch([Query(F.total(), None, 2.0)])
    assert float(b.variances[0]) == 0.0  # p = 1: the sample IS the data


# -- segment semantics (satellite: one Segment abstraction everywhere) -------


def test_segments_unified_across_surfaces(zipf_truth):
    ukeys, cnts = zipf_truth
    mask = ukeys % 5 == 0
    ids = ukeys[mask]
    pred = lambda keys: keys % 5 == 0
    ref = float(np.sum(np.minimum(cnts[mask], 7)))
    # exact_statistic: mask (historical), predicate, id-list, Segment
    assert F.exact_statistic(F.cap(7), cnts, mask) == pytest.approx(ref)
    for seg in (pred, ids, SEG.IdSet(ids), SEG.Predicate(pred)):
        assert F.exact_statistic(F.cap(7), cnts, seg, keys=ukeys) == pytest.approx(ref)
    # key-based segments need keys=
    with pytest.raises(ValueError, match="keys"):
        F.exact_statistic(F.cap(7), cnts, ids)
    # positional masks must match length
    with pytest.raises(ValueError, match="[Mm]ask"):
        SEG.Mask(mask[:10]).mask_np(ukeys)


def test_hash_bucket_segments_partition(lanes):
    """HashBucket segments partition every lane: bucket estimates sum to the
    all-keys estimate (same per-key values, disjoint masks)."""
    eng = QueryEngine(lanes)
    fn = F.cap(5)
    full = eng.query_batch([Query(fn, None, 5.0)]).estimates[0]
    parts = eng.query_batch(
        [Query(fn, SEG.HashBucket(4, b), 5.0) for b in range(4)]).estimates
    assert float(np.sum(parts)) == pytest.approx(float(full), rel=1e-12)


def test_adhoc_lane_key_differs_from_sketch_l(zipf_stream):
    """The dict key addressing a lane is just an address: the Thm 5.3
    coefficients must come from the sketch's own l (regression: d1 was
    computed from the dict key, silently corrupting ad-hoc engines)."""
    res = V.sample_fixed_k(zipf_stream, None, k=200, l=8.0, salt=11)
    eng = QueryEngine({5.0: res})  # address != res.l on purpose
    b = eng.query_batch([Query(F.cap(8), None, 5.0)])
    assert float(b.estimates[0]) == E.estimate(res, F.cap(8))


def test_bank_reset_keeps_answers_bit_identical(zipf_stream):
    """Overflowing the segment bank resets it wholesale; answers before and
    after the reset stay bit-identical to the scalar path."""
    res = V.sample_fixed_k(zipf_stream, None, k=100, l=5.0, salt=12)
    eng = QueryEngine({5.0: res})
    eng._seg_rows_max = 4  # force resets quickly
    ref = {}
    for mod in range(2, 12):
        seg = SEG.Predicate((lambda m: lambda keys: keys % m == 0)(mod),
                            f"mod{mod}")
        got = float(eng.query_batch([Query(F.cap(5), seg, 5.0)]).estimates[0])
        ref[mod] = E.estimate(res, F.cap(5), seg)
        assert got == ref[mod], mod
    # revisit an early (evicted) segment: recompiled mask, same bits
    seg2 = SEG.Predicate(lambda keys: keys % 2 == 0, "mod2b")
    assert float(eng.query_batch([Query(F.cap(5), seg2, 5.0)]).estimates[0]) \
        == ref[2]
    # a batch of NEW segments straddling the cap must reset upfront, never
    # mid-plan (regression: a mid-batch reset stranded earlier rows)
    while len(eng._seg_rows) < eng._seg_rows_max - 1:
        eng._seg_row(0, SEG.HashBucket(64, len(eng._seg_rows)))
    straddle = [Query(F.cap(5), SEG.HashBucket(128, b), 5.0) for b in (17, 18)]
    got = eng.query_batch(straddle)
    for q, e in zip(straddle, got.estimates):
        assert float(e) == E.estimate(res, F.cap(5), q.segment)
    # the cached plan must stay valid on replay
    np.testing.assert_array_equal(
        got.estimates, eng.query_batch(straddle).estimates)


def test_segment_equality_and_caching():
    a, b = SEG.IdSet([3, 1, 2]), SEG.IdSet(np.array([1, 2, 3]))
    assert a == b and hash(a) == hash(b)
    assert SEG.HashBucket(8, 1) == SEG.HashBucket(8, 1)
    assert SEG.HashBucket(8, 1) != SEG.HashBucket(8, 2)
    f = lambda k: k > 0
    assert SEG.Predicate(f) == SEG.Predicate(f)
    assert SEG.as_segment(None) == SEG.AllKeys()


# -- key validation (satellite: no silent int32 wrapping) --------------------


def test_normalize_keys_rejects_bad_inputs():
    with pytest.raises(TypeError, match="integers"):
        normalize_keys(np.array([1.5, 2.5]))
    with pytest.raises(ValueError, match="int32"):
        normalize_keys(np.array([2**40], dtype=np.int64))
    with pytest.raises(ValueError, match="EMPTY"):
        normalize_keys(np.array([2**31 - 1], dtype=np.int64))
    out = normalize_keys(np.array([[1, 2], [3, 4]], dtype=np.int64))
    assert out.dtype == np.int32 and out.tolist() == [1, 2, 3, 4]


def test_service_observe_and_reconcile_validate_keys(zipf_stream):
    svc = StreamStatsService(StatsConfig(k=64, ls=(1.0,), chunk=512, host_id=0))
    with pytest.raises(TypeError, match="integers"):
        svc.observe(np.array([1.0, 2.0]))
    with pytest.raises(ValueError, match="int32"):
        svc.observe(np.array([2**31], dtype=np.int64))
    svc.observe(zipf_stream[:4096])
    with pytest.raises(ValueError, match="int32"):
        svc.reconcile(np.array([-2**35], dtype=np.int64))
    svc.reconcile(zipf_stream[:4096])
    assert svc.query_distinct(exact=True) > 0


# -- pick_l grid warning (satellite) ----------------------------------------


def test_pick_l_warns_once_outside_sqrt2_factor():
    svc = StreamStatsService(StatsConfig(ls=(1.0, 8.0, 64.0)))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # within sqrt(2): silent
        assert svc.pick_l(8.0) == 8.0
        assert svc.pick_l(10.0) == 8.0
    with pytest.warns(RuntimeWarning, match="sqrt"):
        assert svc.pick_l(500.0) == 64.0
    with warnings.catch_warnings():  # second offence: silent (warn once)
        warnings.simplefilter("error")
        assert svc.pick_l(2000.0) == 64.0


def test_x64_scope_keeps_f64_on_an_ieee_device(monkeypatch, lanes):
    """On a TPU (emulated f64) the query plane's f64 steps run on the host
    CPU device; the engine's answers stay bit-identical to the scalar loop."""
    import jax
    import jax.numpy as jnp

    from repro.core.x64 import x64_scope

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cpu = jax.devices("cpu")[0]
    with x64_scope():
        x = jnp.asarray(np.arange(3, dtype=np.float64))
    assert x.dtype == jnp.float64 and x.devices() == {cpu}
    eng = QueryEngine(lanes)
    qs = [Query(fn, None, l) for l in lanes for fn in FNS]
    res = eng.query_batch(qs)
    for q, est in zip(qs, res.estimates):
        assert float(est) == E.estimate(lanes[q.l], q.fn, q.segment)
