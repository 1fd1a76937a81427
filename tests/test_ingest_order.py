"""Single-sort + score-in-key-order ingest (DESIGN.md §8-§9): bit-identity.

Contracts under test:

* ``chunk_order`` / ``merge_sorted_runs`` / the scatter-form
  ``compact_valid`` reproduce the historical sort-based forms bit-for-bit;
* eviction threshold selection (top_k / rank-select / full sort) is one
  order statistic however it is lowered;
* the restructured chunk steps (shared ChunkOrder + ordered scoring +
  sorted-runs table merge + selected-threshold evict) are bit-identical to
  the pre-restructure reference path across kinds, chunk sizes, lane
  counts, and the tau=inf edge;
* the fused ``capscore_agg`` (score in key order, reduce in the same pass)
  equals score-then-gather-then-reduce: exactly on the XLA path, exactly on
  min/max/entered and to f32-reassociation on sums for the Pallas kernel;
* element scoring is permutation-covariant (the keystone of ordered
  scoring): scoring a permuted chunk with permuted eids == permuting the
  scores;
* the key-sorted bottom-(k+1) summary carry reproduces the seed-sorted
  iterated merge bit-for-bit (tables AND summaries, all L lanes);
* the sorted-table invariant holds after every step;
* ``evict_every > 1`` (amortized lazy eviction) keeps the sample a valid
  fixed-k SH_l sample: size <= k, Thm 5.2 count law (PIT + KS), unbiased
  cap estimates (Monte Carlo);
* the one-shot samplers validate keys through ``normalize_keys``;
* the capscore interpret default derives from the backend with env override;
* the kernel pad helper: padded-vs-aligned outputs slice bit-identically.
"""
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import estimators as EST
from repro.core import freqfns as F
from repro.core import incremental as I
from repro.core import vectorized as V
from repro.kernels.capscore.ops import _pad_tile, capscore, capscore_agg, capscore_multi
from repro.core.segments import (
    EMPTY,
    chunk_order,
    compact_valid,
    kth_smallest,
    merge_sorted_runs,
    merge_sorted_runs_gather,
    segment_ids,
    sort_by_key,
)


def _stream(n=16000, n_keys=3000, seed=0):
    rng = np.random.default_rng(seed)
    keys = (rng.zipf(1.4, size=n) % n_keys).astype(np.int64)
    w = (rng.exponential(1.0, n) + 0.1).astype(np.float32)
    return keys, w


# ---------------------------------------------------------------------------
# primitives: shared order, sorted-runs merge, sort-free compaction
# ---------------------------------------------------------------------------


def test_chunk_order_matches_sort_by_key():
    rng = np.random.default_rng(1)
    for n, n_keys in [(64, 7), (256, 300), (1024, 50)]:
        keys = rng.integers(0, n_keys, n).astype(np.int32)
        keys[rng.uniform(size=n) < 0.2] = int(EMPTY)  # padding interspersed
        keys = jnp.asarray(keys)
        o = chunk_order(keys)
        ks_ref, (perm_ref,) = sort_by_key(keys, jnp.arange(n))
        seg_ref, _ = segment_ids(ks_ref)
        np.testing.assert_array_equal(np.asarray(o.ks), np.asarray(ks_ref))
        np.testing.assert_array_equal(np.asarray(o.perm), np.asarray(perm_ref))
        np.testing.assert_array_equal(np.asarray(o.seg), np.asarray(seg_ref))
        # ukeys: ascending uniques compacted to the front, EMPTY padded
        uk = np.asarray(o.ukeys)
        expect = np.unique(np.asarray(keys))
        np.testing.assert_array_equal(uk[: len(expect)], expect)
        assert (uk[len(expect):] == int(EMPTY)).all()


def test_merge_sorted_runs_matches_stable_concat_sort():
    rng = np.random.default_rng(2)
    for na, nb in [(16, 16), (128, 32), (5, 200)]:
        a = np.sort(rng.integers(0, 60, na)).astype(np.int32)
        b = np.sort(rng.integers(0, 60, nb)).astype(np.int32)
        a[-na // 4 or -1:] = int(EMPTY)  # EMPTY tails like real tables
        b[-nb // 4 or -1:] = int(EMPTY)
        pos_a, pos_b = merge_sorted_runs(jnp.asarray(a), jnp.asarray(b))
        merged = np.zeros(na + nb, np.int32)
        merged[np.asarray(pos_a)] = a
        merged[np.asarray(pos_b)] = b
        concat = np.concatenate([a, b])
        order = np.argsort(concat, kind="stable")
        np.testing.assert_array_equal(merged, concat[order])
        # positions form a permutation, and ties keep run-a entries first
        assert sorted(np.concatenate([np.asarray(pos_a), np.asarray(pos_b)]).tolist()) \
            == list(range(na + nb))


def test_compact_valid_matches_stable_argsort_reference():
    rng = np.random.default_rng(3)
    for n in (8, 100, 257):
        valid = jnp.asarray(rng.uniform(size=n) < 0.6)
        vals = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
        fvals = jnp.asarray(rng.normal(size=n), jnp.float32)
        got_i, got_f = compact_valid(valid, vals, fvals,
                                     fills=(EMPTY, jnp.float32(jnp.inf)))
        # historical form: stable argsort on ~valid, then fill the tail
        order = np.argsort(~np.asarray(valid), kind="stable")
        v = np.asarray(valid)[order]
        ref_i = np.where(v, np.asarray(vals)[order], int(EMPTY))
        ref_f = np.where(v, np.asarray(fvals)[order], np.inf)
        np.testing.assert_array_equal(np.asarray(got_i), ref_i)
        np.testing.assert_array_equal(np.asarray(got_f), ref_f)


def test_evict_threshold_selection_routes_agree():
    """tau* from lax.top_k == rank-select == the full descending sort, and
    the whole evicted table agrees bitwise (max_evict both bounded and
    None) — the selection is one order statistic however it is lowered."""
    rng = np.random.default_rng(4)
    cap, k = 256, 64
    for trial in range(5):
        n_valid = int(rng.integers(k + 1, cap))
        keys = np.full(cap, int(EMPTY), np.int32)
        keys[:n_valid] = np.sort(rng.choice(10**6, n_valid, replace=False)).astype(np.int32)
        counts = np.where(keys != int(EMPTY),
                          rng.exponential(5.0, cap).astype(np.float32), 0.0)
        kb = np.where(keys != int(EMPTY),
                      rng.uniform(0, 0.3, cap).astype(np.float32), np.inf)
        seed = np.where(keys != int(EMPTY),
                        rng.uniform(0, 1, cap).astype(np.float32), np.inf)
        for tau in (np.inf, 0.5, 0.01):
            args = (jnp.asarray(keys), jnp.asarray(counts, jnp.float32),
                    jnp.asarray(kb, jnp.float32), jnp.asarray(seed, jnp.float32),
                    jnp.float32(tau), k, jnp.float32(8.0), jnp.uint32(9),
                    jnp.int32(trial + 1))
            ref = V._evict_to_k_ref(*args)
            for me in (None, cap - k):
                for select in ("auto", "topk", "rank"):
                    got = V._evict_to_k(*args, max_evict=me, select=select)
                    for g, r in zip(got, ref):
                        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_kth_smallest_matches_sort():
    """Rank selection == np.sort order statistic, incl. infinities, ties and
    a traced rank."""
    rng = np.random.default_rng(44)
    for n, r in [(1, 0), (7, 3), (100, 0), (100, 99), (513, 200), (4096, 2048)]:
        x = rng.normal(size=n).astype(np.float32)
        if n > 8:
            x[rng.integers(0, n, 3)] = np.inf
            x[rng.integers(0, n, 2)] = -np.inf
            x[rng.integers(0, n, 2)] = x[0]  # duplicates
        got = jax.jit(kth_smallest)(jnp.asarray(x), jnp.int32(r))
        assert np.asarray(got) == np.sort(x)[r], (n, r)


# ---------------------------------------------------------------------------
# restructured chunk steps == pre-restructure reference, bit for bit
# ---------------------------------------------------------------------------


def _extract(table):
    """Order-independent table content: (sorted keys, their counts/kb/seed, tau)."""
    keys = np.asarray(table.keys)
    valid = keys != int(EMPTY)
    order = np.argsort(keys[valid], kind="stable")
    return (keys[valid][order], np.asarray(table.counts)[valid][order],
            np.asarray(table.kb)[valid][order],
            np.asarray(table.seed)[valid][order], float(table.tau))


def _assert_tables_equal(a, b):
    ka, ca, kba, sda, ta = _extract(a)
    kb_, cb, kbb, sdb, tb = _extract(b)
    np.testing.assert_array_equal(ka, kb_)
    np.testing.assert_array_equal(ca, cb)   # bitwise: same reductions, same order
    np.testing.assert_array_equal(kba, kbb)
    np.testing.assert_array_equal(sda, sdb)
    assert ta == tb


def _assert_sorted_invariant(table):
    keys = np.asarray(table.keys)
    n_valid = int((keys != int(EMPTY)).sum())
    assert (keys[n_valid:] == int(EMPTY)).all(), "EMPTY not compacted to back"
    assert (np.diff(keys[:n_valid]) > 0).all(), "keys not strictly ascending"


@pytest.mark.parametrize("chunk,k,l", [(64, 16, 0.5), (256, 32, 16.0), (128, 64, 5.0)])
def test_fixed_k_step_bit_identity_vs_reference(chunk, k, l):
    keys, w = _stream(n=chunk * 12, seed=chunk + k)
    new = V.init_table(k + chunk)
    ref = V.init_table(k + chunk)
    for i in range(12):
        ck = jnp.asarray(keys[i * chunk:(i + 1) * chunk], jnp.int32)
        cw = jnp.asarray(w[i * chunk:(i + 1) * chunk])
        eids = jnp.arange(i * chunk, (i + 1) * chunk, dtype=jnp.int32)
        score, delta, entry, kb = jax.tree.map(
            lambda x: x[0],
            capscore_multi(ck, eids, cw, jnp.asarray([l], jnp.float32),
                           ref.tau[None], jnp.uint32(3)))
        new = V.fixed_k_step(new, ck, cw, eids, jnp.float32(l), jnp.uint32(3), k=k)
        ref = V.fixed_k_step_scored_ref(ref, ck, cw, score, delta, entry, kb,
                                        k=k, l=jnp.float32(l), salt=jnp.uint32(3))
        _assert_tables_equal(new, ref)
        _assert_sorted_invariant(new)


def test_fixed_k_step_tau_inf_edge():
    """Stream smaller than k: tau stays inf, nothing ever evicts, and the
    sorted path still matches the reference merge exactly."""
    rng = np.random.default_rng(8)
    chunk, k = 64, 512
    new = V.init_table(k + chunk)
    ref = V.init_table(k + chunk)
    for i in range(6):
        ck = jnp.asarray(rng.integers(0, 40, chunk), jnp.int32)
        cw = jnp.ones(chunk, jnp.float32)
        eids = jnp.arange(i * chunk, (i + 1) * chunk, dtype=jnp.int32)
        agg = V.aggregate_continuous_ref(ck, cw, eids, ref.tau, jnp.float32(4.0),
                                         jnp.uint32(1))
        keys_c, counts_c, kb_c, seed_c, _ = V._merge_table(ref, agg)
        cap = ref.keys.shape[0]
        keys_e, counts_e, kb_e, seed_e, tau_e = V._evict_to_k_ref(
            keys_c[:cap], counts_c[:cap], kb_c[:cap], seed_c[:cap],
            ref.tau, k, jnp.float32(4.0), jnp.uint32(1), ref.step + 1)
        ref = V.TableState(keys_e, counts_e, kb_e, seed_e, tau_e,
                           ref.step + 1, ref.overflow)
        new = V.fixed_k_step(new, ck, cw, eids, jnp.float32(4.0), jnp.uint32(1), k=k)
        assert float(new.tau) == math.inf
        _assert_tables_equal(new, ref)


@pytest.mark.parametrize("kind", ["continuous", "discrete", "distinct", "sh"])
def test_fixed_tau_step_bit_identity_vs_reference(kind):
    keys, w = _stream(n=4096, seed=17)
    l = {"continuous": 5.0, "discrete": 5.0, "distinct": 1.0, "sh": 1e9}[kind]
    chunk, capacity = 256, 4096
    new = V.init_table(capacity, 0.05)
    ref = V.init_table(capacity, 0.05)
    for i in range(16):
        ck = jnp.asarray(keys[i * chunk:(i + 1) * chunk], jnp.int32)
        cw = jnp.asarray(w[i * chunk:(i + 1) * chunk])
        eids = jnp.arange(i * chunk, (i + 1) * chunk, dtype=jnp.int32)
        # reference: verbatim pre-PR aggregate + legacy concat-and-sort merge
        if kind == "continuous":
            agg = V.aggregate_continuous_ref(ck, cw, eids, ref.tau,
                                             jnp.float32(l), jnp.uint32(5))
        else:
            agg = V.aggregate_discrete_ref(ck, cw, eids, ref.tau, kind,
                                           jnp.float32(l), jnp.uint32(5))
        keys_c, counts_c, kb_c, seed_c, n_valid = V._merge_table(ref, agg)
        over = ref.overflow + jnp.maximum(n_valid - capacity, 0)
        ref = V.TableState(keys_c[:capacity], counts_c[:capacity],
                           kb_c[:capacity], seed_c[:capacity],
                           ref.tau, ref.step + 1, over)
        new = V.fixed_tau_step(new, ck, cw, eids, jnp.float32(l), jnp.uint32(5),
                               kind=kind)
        _assert_tables_equal(new, ref)
        _assert_sorted_invariant(new)


def test_merge_sorted_runs_gather_out_len_prefix():
    """Truncated interleave == the first out_len slots of the full merge."""
    rng = np.random.default_rng(21)
    for na, nb, ol in [(16, 16, 8), (128, 32, 128), (5, 200, 60), (64, 64, 128)]:
        a = np.sort(rng.integers(0, 300, na)).astype(np.int32)
        b = np.sort(rng.integers(0, 300, nb)).astype(np.int32)
        concat = np.concatenate([a, b])
        ref = concat[np.argsort(concat, kind="stable")]
        for out_len in (None, ol):
            fb, ia, ib = merge_sorted_runs_gather(jnp.asarray(a), jnp.asarray(b),
                                                  out_len)
            merged = np.where(np.asarray(fb), b[np.asarray(ib)], a[np.asarray(ia)])
            np.testing.assert_array_equal(merged, ref[: len(merged)])


# ---------------------------------------------------------------------------
# the table merge's two forms: gather (XLA:CPU) and two batched sorts (TPU)
# ---------------------------------------------------------------------------


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _zipf_chunk(rng, chunk, universe=10**8):
    """A Zipf(1.3) chunk of int32 keys over a 10^8-key universe."""
    return jnp.asarray((rng.zipf(1.3, chunk) % universe).astype(np.int32))


_aggregate_jit = jax.jit(V.aggregate_continuous)
_fixed_k_step_jit = jax.jit(V.fixed_k_step, static_argnames=("k",))
_fixed_tau_step_jit = jax.jit(V.fixed_tau_step, static_argnames=("kind",))
_merge_table_jit = jax.jit(V._merge_table)


@functools.lru_cache(maxsize=None)
def _merge_inputs(case):
    """The (table, chunk aggregate) pairs one merge-form case feeds."""
    return tuple(_merge_input_gen(case))


def _merge_input_gen(case):
    rng = np.random.default_rng(31)
    l, salt = jnp.float32(4.0), jnp.uint32(3)

    def agg_of(table, ck, i):
        chunk = ck.shape[0]
        eids = jnp.arange(i * chunk, (i + 1) * chunk, dtype=jnp.int32)
        return _aggregate_jit(ck, jnp.ones(chunk, jnp.float32), eids,
                              table.tau, l, salt), eids

    if case.startswith("fixed_k"):
        k, chunk = map(int, case.split("-")[1:])
        table = V.init_table(k + chunk)
        for i in range(8):
            ck = _zipf_chunk(rng, chunk)
            agg, eids = agg_of(table, ck, i)
            yield table, agg
            table = _fixed_k_step_jit(table, ck, jnp.ones(chunk, jnp.float32),
                                      eids, l, salt, k=k)
    elif case == "fixed_tau_overflow":
        # every key enters (tau far above any score): the union outgrows cap
        table = V.init_table(256, 10.0)
        for i in range(4):
            ck = jnp.asarray(rng.integers(0, 10**8, 128), jnp.int32)
            agg, eids = agg_of(table, ck, i)
            yield table, agg
            table = _fixed_tau_step_jit(table, ck, jnp.ones(128, jnp.float32),
                                        eids, l, salt, kind="continuous")
    elif case == "empty_table":
        table = V.init_table(4096 + 2048)
        yield table, agg_of(table, _zipf_chunk(rng, 2048), 0)[0]
    else:
        # a table of live keys, then a chunk of its own keys, or a chunk
        # under tau = 0, where no key enters
        table = V.init_table(512 + 256, 0.5)
        for i in range(3):
            ck = _zipf_chunk(rng, 256, universe=5000)
            table = _fixed_tau_step_jit(table, ck, jnp.ones(256, jnp.float32),
                                        jnp.arange(i * 256, (i + 1) * 256,
                                                   dtype=jnp.int32),
                                        l, salt, kind="continuous")
        live = np.asarray(table.keys)[np.asarray(V.is_live(table.keys))]
        if case == "chunk_in_table":
            ck = jnp.asarray(rng.choice(live, 256), jnp.int32)
            yield table, agg_of(table, ck, 3)[0]
        else:
            ck = jnp.asarray(np.concatenate([live[:128],
                                             rng.integers(0, 10**8, 128)]),
                             jnp.int32)
            agg, _ = agg_of(table._replace(tau=jnp.float32(0.0)), ck, 3)
            assert not np.asarray(agg.entered).any()
            yield table, agg


@pytest.mark.parametrize("form", ["gather", "sort"])
@pytest.mark.parametrize("case", [
    "fixed_k-16-64", "fixed_k-512-2048", "fixed_k-4096-2048",
    "fixed_tau_overflow", "empty_table", "chunk_in_table", "none_enter"])
def test_merge_forms_bit_identical_to_concat_sort_merge(case, form):
    """Both forms of the sorted-runs merge equal the concat-and-re-sort
    oracle on all five outputs: keys, counts, kb, seed (the first ``cap``
    slots, bit for bit) and ``n_valid``."""
    merge = jax.jit(lambda t, a: V._merge_table_sorted(t, a, form=form))
    over = False
    for table, agg in _merge_inputs(case):
        cap = table.keys.shape[0]
        ref = _merge_table_jit(table, agg)
        got = merge(table, agg)
        for g, r in zip(got[:4], ref[:4]):
            np.testing.assert_array_equal(_bits(g), _bits(r[:cap]))
        assert int(got[4]) == int(ref[4])
        over |= int(got[4]) > cap
    # the fixed-tau case reaches truncation: the union outgrew the table
    assert over == (case == "fixed_tau_overflow")


def _tree_bits(tree):
    return [_bits(x) for x in jax.tree.leaves(tree)]


def _run_update_multi():
    keys, w = _stream(n=256 * 12, n_keys=900, seed=41)
    st, spec = I.init_multi_state((1.0, 8.0, 64.0), k=96, chunk=256, salt=5)
    for b in range(3):
        sl = slice(b * 1024, (b + 1) * 1024)
        st = I.update_multi(st, keys[sl].astype(np.int32), w[sl], spec)
    return st


def _run_update_bank():
    T, chunk = 3, 128
    keys, w = _stream(n=T * chunk * 6, n_keys=700, seed=43)
    keys = keys.astype(np.int32).reshape(6, T, chunk)
    w = w.reshape(6, T, chunk)
    st, spec = I.init_bank_state((1.0, 16.0), n_tenants=T, k=48, chunk=chunk,
                                 salts=(1, 2, 3))
    for tick in range(6):
        active = np.arange(T) != tick % T        # one tenant idles a tick
        st = I.update_bank(st, keys[tick], w[tick], active, spec)
    return st


@pytest.mark.parametrize("run", [_run_update_multi, _run_update_bank],
                         ids=["update_multi", "update_bank"])
def test_sort_merge_form_end_to_end_bit_identity(run, force_merge_form):
    """The whole multi-lane batch and the bank tick, compiled with the merge
    resolver steered to the TPU's sort form, equal the gather form bit for
    bit: tables (keys, counts, kb, seed, tau) and bottom-(k+1) summaries."""
    force_merge_form("gather")
    gathered = run()
    force_merge_form("sort")
    sorted_ = run()
    assert sorted_.table.keys.shape == gathered.table.keys.shape
    for g, s in zip(_tree_bits(gathered), _tree_bits(sorted_)):
        np.testing.assert_array_equal(s, g)


# ---------------------------------------------------------------------------
# score-in-key-order: covariance, the fused aggregate, ordered fixed-tau
# ---------------------------------------------------------------------------


def test_element_scoring_permutation_covariance():
    """The keystone of ordered scoring: element randomness hangs off the
    (key, eid, weight) VALUES, so scoring a permuted chunk with permuted
    eids equals permuting the scores — bitwise, for every lane and output."""
    rng = np.random.default_rng(23)
    C, L = 1024, 5
    keys = jnp.asarray(rng.integers(0, 200, C), jnp.int32)
    eids = jnp.asarray(rng.permutation(C * 7)[:C], jnp.int32)
    w = jnp.asarray(rng.exponential(1.0, C) + 0.1, jnp.float32)
    ls = jnp.asarray(np.geomspace(1.0, 16.0, L), jnp.float32)
    taus = jnp.asarray(rng.uniform(0.05, 2.0, L), jnp.float32)
    perm = jnp.asarray(rng.permutation(C))
    base = capscore_multi(keys, eids, w, ls, taus, jnp.uint32(9))
    permuted = capscore_multi(keys[perm], eids[perm], w[perm], ls, taus,
                              jnp.uint32(9))
    for b, p in zip(base, permuted):
        np.testing.assert_array_equal(np.asarray(b)[:, np.asarray(perm)],
                                      np.asarray(p))


def _agg_via_gather_path(keys, eids, w, ls, taus, salt, order):
    """The score-then-gather-then-reduce chain the fused op replaces."""
    score, delta, entry, kb = capscore_multi(keys, eids, w, ls, taus, salt)
    return jax.vmap(
        lambda s_, d_, e_, b_: V.aggregate_continuous_scored(
            keys, w, s_, d_, e_, b_, order)
    )(score, delta, entry, kb)


@pytest.mark.parametrize("C,n_keys,L", [(300, 40, 3), (1024, 5000, 1),
                                        (2048, 150, 8)])
def test_capscore_agg_xla_bit_identity(C, n_keys, L):
    """Fused score+aggregate == score, gather x4L, segment-reduce — bitwise,
    EMPTY padding and tau=inf lanes included."""
    rng = np.random.default_rng(C + L)
    keys = rng.integers(0, n_keys, C).astype(np.int32)
    keys[rng.uniform(size=C) < 0.2] = int(EMPTY)
    keys = jnp.asarray(keys)
    eids = jnp.asarray(rng.permutation(10 * C)[:C], jnp.int32)
    w = jnp.asarray(rng.exponential(1.0, C) + 0.1, jnp.float32)
    ls = jnp.asarray(np.geomspace(1.0, 2.0 ** (L - 1), L), jnp.float32)
    taus = jnp.asarray(rng.uniform(0.05, 2.0, L), jnp.float32)
    taus = taus.at[0].set(jnp.inf)  # tau=inf lane rides along
    salt = jnp.uint32(7)
    order = chunk_order(keys, eids, w)
    w_total, entered, contrib, kb_min, min_score = capscore_agg(
        order.ks, order.eids, order.ws, order.seg, ls, taus, salt,
        backend="xla")
    ref = _agg_via_gather_path(keys, eids, w, ls, taus, salt, order)
    np.testing.assert_array_equal(np.asarray(order.ukeys), np.asarray(ref.ukeys[0]))
    np.testing.assert_array_equal(np.asarray(w_total), np.asarray(ref.w_total[0]))
    np.testing.assert_array_equal(np.asarray(entered), np.asarray(ref.entered))
    np.testing.assert_array_equal(np.asarray(contrib), np.asarray(ref.contrib))
    np.testing.assert_array_equal(np.asarray(kb_min), np.asarray(ref.kb))
    np.testing.assert_array_equal(np.asarray(min_score), np.asarray(ref.min_score))


def test_capscore_agg_pallas_matches_xla():
    """The Pallas kernel (interpret mode on CPU) agrees with the XLA path:
    exactly on entered/min/max columns, to f32-reassociation on the sums
    (the in-block one-hot reduce sums in a different order)."""
    rng = np.random.default_rng(31)
    for C, n_keys, n_l in [(300, 40, 3), (1024, 200, 1), (2048, 3000, 4)]:
        keys = rng.integers(0, n_keys, C).astype(np.int32)
        keys[rng.uniform(size=C) < 0.15] = int(EMPTY)
        w = jnp.asarray(rng.exponential(1.0, C) + 0.1, jnp.float32)
        eids = jnp.asarray(np.arange(C), jnp.int32)
        ls = jnp.asarray(np.geomspace(1.0, 8.0, n_l), jnp.float32)
        taus = jnp.asarray(rng.uniform(0.05, 2.0, n_l), jnp.float32)
        o = chunk_order(jnp.asarray(keys), eids, w)
        args = (o.ks, o.eids, o.ws, o.seg, ls, taus, jnp.uint32(7))
        ref = capscore_agg(*args, backend="xla")
        got = capscore_agg(*args, backend="pallas")
        for nm, g, r in zip(("w_total", "entered", "contrib", "kb", "min_score"),
                            got, ref):
            if nm in ("w_total", "contrib"):
                np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                           rtol=2e-6, atol=1e-6, err_msg=nm)
            else:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(r), nm)


@pytest.mark.parametrize("kind", ["continuous", "discrete", "distinct", "sh"])
def test_ordered_discrete_continuous_aggregates_match_ref(kind):
    """aggregate_continuous/_discrete on the pre-gathered view == the
    verbatim pre-ChunkOrder reducers, across kinds and chunk sizes."""
    rng = np.random.default_rng(57)
    l = {"continuous": 5.0, "discrete": 5.0, "distinct": 1.0, "sh": 1e9}[kind]
    for C in (64, 256, 1000):
        keys = rng.integers(0, max(8, C // 8), C).astype(np.int32)
        keys[rng.uniform(size=C) < 0.1] = int(EMPTY)
        keys = jnp.asarray(keys)
        w = jnp.asarray(rng.exponential(1.0, C) + 0.1, jnp.float32)
        eids = jnp.asarray(np.arange(C), jnp.int32)
        for tau in (jnp.float32(jnp.inf), jnp.float32(0.2)):
            order = chunk_order(keys, eids, w)
            if kind == "continuous":
                got = V.aggregate_continuous(keys, w, eids, tau, jnp.float32(l),
                                             jnp.uint32(5), order)
                ref = V.aggregate_continuous_ref(keys, w, eids, tau,
                                                 jnp.float32(l), jnp.uint32(5))
            else:
                got = V.aggregate_discrete(keys, w, eids, tau, kind,
                                           jnp.float32(l), jnp.uint32(5), order)
                ref = V.aggregate_discrete_ref(keys, w, eids, tau, kind,
                                               jnp.float32(l), jnp.uint32(5))
            for g, r in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_pass1_fold_keysorted_matches_seed_sorted_merge():
    """The key-sorted summary carry == iterated merge_bottomk_summary after
    conversion, chunk by chunk (the in-scan form of the §3.1 losslessness).

    Scores are coarsely quantized, so seeds TIE at the bottom-cap threshold
    constantly — pinning the fold's tie-break (every seed strictly below the
    threshold survives; the remaining quota goes to tied entries
    smallest-key-first) to ``bottom_k_by``'s exact semantics."""
    rng = np.random.default_rng(71)
    C, cap, rounds = 512, 129, 18
    sk = jnp.full((cap,), EMPTY, jnp.int32)
    ss = jnp.full((cap,), jnp.inf, jnp.float32)
    kk, vv = V.summary_to_keysorted(sk, ss)
    for t in range(rounds):
        keys = jnp.asarray(rng.integers(0, 300 if t % 2 else 2**30, C), jnp.int32)
        scores = jnp.asarray(
            np.round(rng.uniform(0, 1, C), [2, 1, 3][t % 3]).astype(np.float32))
        order = chunk_order(keys)
        live = order.ks != EMPTY
        mins = jax.ops.segment_min(
            jnp.where(live, scores[order.perm], jnp.float32(jnp.inf)),
            order.seg, num_segments=C)
        mins = jnp.where(order.ukeys != EMPTY, mins, jnp.inf)
        sk, ss = V.merge_bottomk_summary(sk, ss, order.ukeys, mins, cap)
        kk, vv = V.pass1_fold_keysorted(kk, vv, order.ukeys, mins, cap)
        got_k, got_s = V.summary_from_keysorted(kk, vv, cap)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(sk))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ss))


@pytest.mark.parametrize("L,chunk", [(1, 1024), (4, 1024), (8, 256)])
def test_update_multi_bit_identity_vs_reference_path(L, chunk):
    keys, w = _stream(n=chunk * 10, seed=100 + L)
    ls = tuple(float(2.0 ** j) for j in range(L))
    st_new, spec = I.init_multi_state(ls, k=128, chunk=chunk, salt=11)
    st_ref, _ = I.init_multi_state(ls, k=128, chunk=chunk, salt=11)
    kk = keys.astype(np.int32)
    st_new = I.update_multi(st_new, kk, w, spec, donate=False)
    st_ref = I.update_multi(st_ref, kk, w, spec, donate=False, reference=True)
    # identical per-lane samples, thresholds, and lossless summaries
    rn = I.finalize_multi(st_new, spec, ls=ls)
    rr = I.finalize_multi(st_ref, spec, ls=ls)
    for l in ls:
        np.testing.assert_array_equal(rn[l].keys, rr[l].keys)
        np.testing.assert_array_equal(rn[l].counts, rr[l].counts)
        assert rn[l].tau == rr[l].tau
    np.testing.assert_array_equal(np.asarray(st_new.bk_keys), np.asarray(st_ref.bk_keys))
    np.testing.assert_array_equal(np.asarray(st_new.bk_seeds), np.asarray(st_ref.bk_seeds))


# ---------------------------------------------------------------------------
# amortized eviction (evict_every = E > 1)
# ---------------------------------------------------------------------------


def test_evict_every_capacity_and_schedule():
    keys, w = _stream(n=8192, seed=31)
    E, k, chunk = 4, 64, 512
    s = I.IncrementalSampler(8.0, k=k, chunk=chunk, salt=2, evict_every=E)
    assert s.state.capacity == k + E * chunk
    per_chunk_valid = []
    for i in range(0, len(keys), chunk):
        s.observe(keys[i:i + chunk], w[i:i + chunk])
        per_chunk_valid.append(int((np.asarray(s.state.table.keys) != int(EMPTY)).sum()))
    # between scheduled evictions the table legitimately exceeds k...
    assert max(per_chunk_valid) > k
    assert max(per_chunk_valid) <= k + E * chunk
    # ...and right after each E-th chunk it is back to <= k
    assert all(v <= k for v in per_chunk_valid[E - 1::E])
    # finalize projects down to a valid fixed-k sample, repeatably
    r1, r2 = s.finalize(), s.finalize()
    assert len(r1.keys) <= k
    np.testing.assert_array_equal(r1.keys, r2.keys)
    np.testing.assert_array_equal(r1.counts, r2.counts)


def test_evict_every_multi_matches_capacity_contract():
    keys, _ = _stream(n=6144, seed=32)
    m = I.MultiSampler((1.0, 16.0), k=64, chunk=512, salt=3, evict_every=3)
    m.observe(keys)
    res = m.finalize()
    for l, r in res.items():
        assert len(r.keys) <= 64, (l, len(r.keys))
    # summaries are eviction-independent: identical to an E=1 run
    m1 = I.MultiSampler((1.0, 16.0), k=64, chunk=512, salt=3, evict_every=1)
    m1.observe(keys)
    bkE, bsE = m.bottomk_summaries()
    bk1, bs1 = m1.bottomk_summaries()
    np.testing.assert_array_equal(bkE, bk1)
    np.testing.assert_array_equal(bsE, bs1)


def test_load_state_dict_rejects_capacity_mismatch():
    """A blob written under a different evict_every (hence table capacity)
    must refuse to load: silently truncated merges / overflowed top_k windows
    would corrupt the sample with no error."""
    keys, _ = _stream(n=2048, seed=33)
    m1 = I.MultiSampler((1.0, 16.0), k=64, chunk=512, salt=4, evict_every=1)
    m1.observe(keys)
    blob = m1.state_dict()
    m4 = I.MultiSampler((1.0, 16.0), k=64, chunk=512, salt=4, evict_every=4)
    with pytest.raises(ValueError, match="capacity"):
        m4.load_state_dict(blob)


def _ks_uniform(us):
    us = np.sort(np.asarray(us))
    n = len(us)
    grid = np.arange(1, n + 1) / n
    return max(np.max(np.abs(grid - us)), np.max(np.abs(us - (grid - 1.0 / n))))


def test_evict_every_unbiased_and_count_law(zipf_stream):
    """E>1 changes the eviction randomness *schedule*, not the sampling law:
    cap estimates stay unbiased (MC over salts) and sampled counts follow the
    Thm 5.2 conditional law (PIT + KS), exactly like the E=1 path."""
    ukeys, cnts = np.unique(zipf_stream, return_counts=True)
    wmap = dict(zip(ukeys.tolist(), cnts.tolist()))
    truth = F.exact_statistic(F.cap(5), cnts)
    top = [int(x) for x in ukeys[np.argsort(-cnts)[:30]]]
    l, k, period = 5.0, 100, 3
    rate_pit, ests = [], []
    for r in range(120):
        s = I.IncrementalSampler(l, k=k, chunk=1024, salt=95000 + r,
                                 evict_every=period)
        s.observe(zipf_stream)
        res = s.finalize()
        assert len(res.keys) <= k
        ests.append(EST.estimate(res, F.cap(5)))
        rate = max(1.0 / l, res.tau)
        d = res.asdict()
        for x in top:
            if x in d:
                w = wmap[x]
                phi = w - d[x]
                u = -np.expm1(-rate * phi) / -np.expm1(-rate * w)
                rate_pit.append(min(max(u, 0.0), 1.0))
    m, se = np.mean(ests), np.std(ests) / math.sqrt(len(ests))
    assert abs(m - truth) < 4 * se + 0.001 * truth, \
        f"bias {(m-truth)/truth:+.2%} se {se/truth:.2%}"
    assert len(rate_pit) > 300
    assert _ks_uniform(rate_pit) < 2.2 / math.sqrt(len(rate_pit)), \
        f"KS {_ks_uniform(rate_pit):.3f} n={len(rate_pit)}"


# ---------------------------------------------------------------------------
# satellites: one-shot key validation, interpret default
# ---------------------------------------------------------------------------


def test_one_shot_samplers_validate_keys():
    for call in (
        lambda ks: V.sample_fixed_k(ks, None, k=8, l=2.0, chunk=64),
        lambda ks: V.sample_fixed_tau(ks, None, tau=0.5, l=2.0, chunk=64),
        lambda ks: V.sample_two_pass(ks, None, k=8, l=2.0, chunk=64),
    ):
        with pytest.raises(TypeError, match="integers"):
            call(np.asarray([1.5, 2.0]))
        with pytest.raises(ValueError, match="int32 range"):
            call(np.asarray([2**40, 3], np.int64))
        with pytest.raises(ValueError, match="EMPTY"):
            call(np.asarray([int(EMPTY)], np.int64))
    # valid int64 ids keep working
    res = V.sample_fixed_k(np.asarray([1, 2, 3, 1], np.int64), None, k=8,
                           l=2.0, chunk=64)
    assert set(res.keys.tolist()) <= {1, 2, 3}


def test_pad_tile_padded_vs_aligned_bit_identical():
    """The shared kernel pad helper: a non-aligned chunk scored through the
    padded kernel slices bit-identically to the aligned prefix computation,
    and aligned inputs pass through without any concatenate."""
    rng = np.random.default_rng(91)
    n = 1000  # not a multiple of the 1024 kernel tile
    keys = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    eids = jnp.asarray(np.arange(n), jnp.int32)
    w = jnp.asarray(rng.exponential(1.0, n) + 0.1, jnp.float32)
    # aligned reference: compute on a 1024-aligned superset, slice to n
    keys_al = jnp.concatenate([keys, jnp.zeros((24,), jnp.int32)])
    eids_al = jnp.concatenate([eids, jnp.zeros((24,), jnp.int32)])
    w_al = jnp.concatenate([w, jnp.ones((24,), jnp.float32)])
    for backend in ("xla", "pallas"):
        got = capscore(keys, eids, w, 4.0, 0.3, 3, backend=backend)
        ref = capscore(keys_al, eids_al, w_al, 4.0, 0.3, 3, backend=backend)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r)[:n])
    # aligned input: helper is a no-op passthrough (same objects, pad=0)
    out = _pad_tile(1024, (keys_al, 0), (w_al, 1.0))
    assert out[-1] == 0 and out[0] is keys_al and out[1] is w_al
    # padded: fills applied, arrays extended to the tile
    k2, w2, pad = _pad_tile(1024, (keys, int(EMPTY)), (w, 0.0))
    assert pad == 24 and k2.shape[0] == 1024
    assert (np.asarray(k2[-24:]) == int(EMPTY)).all()
    assert (np.asarray(w2[-24:]) == 0.0).all()


def test_update_multi_tau_inf_edge():
    """Stream smaller than k: tau stays inf in every lane, nothing evicts,
    and the fused path still matches the reference bit for bit."""
    rng = np.random.default_rng(92)
    ls = (1.0, 8.0)
    st_new, spec = I.init_multi_state(ls, k=512, chunk=256, salt=13)
    st_ref, _ = I.init_multi_state(ls, k=512, chunk=256, salt=13)
    keys = rng.integers(0, 60, 1024).astype(np.int32)
    w = np.ones(1024, np.float32)
    st_new = I.update_multi(st_new, keys, w, spec, donate=False)
    st_ref = I.update_multi(st_ref, keys, w, spec, donate=False, reference=True)
    assert np.isinf(np.asarray(st_new.table.tau)).all()
    rn = I.finalize_multi(st_new, spec, ls=ls)
    rr = I.finalize_multi(st_ref, spec, ls=ls)
    for l in ls:
        np.testing.assert_array_equal(rn[l].keys, rr[l].keys)
        np.testing.assert_array_equal(rn[l].counts, rr[l].counts)
        assert rn[l].tau == rr[l].tau == math.inf
    np.testing.assert_array_equal(np.asarray(st_new.bk_keys), np.asarray(st_ref.bk_keys))
    np.testing.assert_array_equal(np.asarray(st_new.bk_seeds), np.asarray(st_ref.bk_seeds))


def test_default_interpret_follows_platform(monkeypatch):
    """Interpret mode is decided by the platform alone: compiled Pallas on
    TPU/GPU, interpret everywhere else."""
    from repro.kernels.capscore import capscore as K

    # this suite runs on CPU: the default must pick interpret mode
    assert jax.default_backend() == "cpu"
    assert K.default_interpret() is True
    for platform, interpret in (("tpu", False), ("gpu", False), ("cpu", True)):
        monkeypatch.setattr(K.jax, "default_backend", lambda p=platform: p)
        assert K.default_interpret() is interpret, platform
