"""TPU-native chunked stream samplers (the hardware adaptation of Algs 1-5).

The paper's cache machines are recast as dataflow (see DESIGN.md §3):

    score elements  ->  per-chunk segment reduce  ->  merge with carried
    fixed-size state  ->  (fixed-k only) batched eviction.

The whole sampler is a ``jax.lax.scan`` over stream chunks with O(k + chunk)
state, so it jit-compiles, shards (each device samples its shard; states
merge — see core/distributed.py), and checkpoints.

Faithfulness contract, verified in tests/test_equivalence.py:

* fixed-threshold samplers are *element-exact* reimplementations of
  Algorithms 2/4: identical per-element randomness (same hashes) => identical
  samples and counts (up to float32-vs-float64 rounding of the oracle).
* the fixed-k continuous sampler implements Algorithm 5 with the paper's own
  batched-eviction variant (§5.2); equality is distributional (Thm 5.2 count
  law + unbiased estimates), not per-run.
* the 2-pass sampler is exact bottom-k by seed (merging bottom-k summaries is
  lossless, §3.1) + exact pass-2 weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing as H
from .samplers import (
    SALT_BUCKET,
    SALT_ELEM,
    SALT_EVICT_R,
    SALT_EVICT_U,
    SALT_KEYBASE,
    SALT_SHARD,
    SampleResult,
)
from .segments import (
    EMPTY,
    ChunkOrder,
    bottom_k_by,
    chunk_order,
    compact_valid,
    is_empty,
    is_live,
    kth_smallest,
    merge_sorted_runs_gather,
    normalize_keys,
    searchsorted,
    scatter_unique,
    segment_ids,
    sort_by_key,
)

INF = jnp.float32(jnp.inf)


# ---------------------------------------------------------------------------
# Element scoring (jnp; mirrors samplers.*_np)
# ---------------------------------------------------------------------------


def keybase(keys, l, salt):
    u = H.uniform01(H.hash_combine(keys, jnp.uint32(SALT_KEYBASE), jnp.uint32(salt)))
    return u / jnp.float32(l)


def elem_uniform(eids, salt):
    return H.uniform01(H.hash_combine(eids, jnp.uint32(SALT_ELEM), jnp.uint32(salt)))


def shard_eids(shard_no, idx):
    """Element ids for positions ``idx`` of shard/host ``shard_no``.

    Hash-derived, so ids from distinct shards never systematically alias —
    the arithmetic form ``shard_no * n + idx`` overflows int32 once
    P*n > 2^31 and silently reuses the same element randomness on different
    shards.  Downstream hashing casts to uint32, so the int32 bit pattern
    returned here matches samplers.shard_eids_np exactly.
    """
    return H.hash_combine(jnp.uint32(SALT_SHARD), shard_no, idx).astype(jnp.int32)


def element_scores(kind: str, keys, eids, weights, l, salt):
    """ElementScore(h) for each scheme; EMPTY-keyed elements get +inf."""
    if kind == "distinct":
        s = H.uniform01(H.hash_combine(keys, jnp.uint32(salt)))
    elif kind == "sh":
        s = elem_uniform(eids, salt)
    elif kind == "discrete":
        u = H.uniform01(H.hash_combine(eids, jnp.uint32(SALT_BUCKET), jnp.uint32(salt)))
        bucket = jnp.minimum((u * l).astype(jnp.int32), (jnp.float32(l) - 1).astype(jnp.int32))
        s = H.uniform01(H.hash_combine(keys, bucket, jnp.uint32(salt)))
    elif kind == "continuous":
        u = elem_uniform(eids, salt)
        v = -jnp.log1p(-u) / weights
        kb = keybase(keys, l, salt)
        s = jnp.where(v <= 1.0 / l, kb, v)
    else:
        raise ValueError(kind)
    return jnp.where(is_empty(keys), INF, s.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Per-chunk aggregation
# ---------------------------------------------------------------------------


class ChunkAgg(NamedTuple):
    ukeys: jax.Array      # [C] unique keys (EMPTY padded)
    w_total: jax.Array    # [C] total chunk weight per key
    entered: jax.Array    # [C] bool: an entry event occurred in this chunk
    contrib: jax.Array    # [C] count contribution from entry onward
    kb: jax.Array         # [C] KeyBase(x) (continuous) or min score (others)
    min_score: jax.Array  # [C] min element score (for seed/bottom-k schemes)


def _aggregate_ordered(order: ChunkOrder, weights, entry, at_entry_count,
                       scores, kb_elem) -> ChunkAgg:
    """Shared segment machinery on a precomputed chunk sort (``ChunkOrder``).

    ``entry``: per-element entry-event flag; ``at_entry_count``: count value
    contributed by the entry element itself (w - Delta for continuous, 1 for
    discrete); elements after the first entry contribute their full weight.
    All per-element arrays arrive in *stream order*; the shared permutation
    gathers them into key order (O(C) gathers — the sort itself was paid once
    per chunk, not once per lane) and the reduction proper is shared with the
    pre-ordered path below.  Bit-identical to sorting inline.
    """
    p = order.perm
    return _aggregate_preordered(
        order._replace(ws=weights[p]), entry[p], at_entry_count[p],
        scores[p], kb_elem[p])


def _aggregate(keys, weights, entry, at_entry_count, scores, kb_elem,
               order: ChunkOrder | None = None):
    """Group a chunk by key and reduce (sorts inline unless ``order`` given)."""
    if order is None:
        order = chunk_order(keys)
    return _aggregate_ordered(order, weights, entry, at_entry_count, scores, kb_elem)


def _aggregate_preordered(order: ChunkOrder, entry, at_entry_count, scores,
                          kb_elem) -> ChunkAgg:
    """``_aggregate_ordered`` when the per-element columns are ALREADY in key
    order — i.e. they were computed on the pre-gathered ``ChunkOrder`` view
    (``order.ks/eids/ws``), so the per-lane gathers vanish entirely.

    Bit-identical to ``_aggregate_ordered`` on the stream-order columns:
    element scoring is elementwise in (key, eid, weight), hence permutation-
    covariant, so the segment reductions receive exactly the same values in
    exactly the same (sorted) positions.
    """
    C = order.ks.shape[0]
    ks, seg, ws = order.ks, order.seg, order.ws
    es, aec = entry, at_entry_count
    sc, kbe = scores, kb_elem
    idx = jnp.arange(C)
    entry_idx = jnp.where(es, idx, C)
    first_entry = jax.ops.segment_min(entry_idx, seg, num_segments=C)
    fe = first_entry[seg]
    after = idx > fe
    at = (idx == fe) & es
    contrib_elem = jnp.where(after, ws, 0.0) + jnp.where(at, aec, 0.0)
    live = is_live(ks)
    w_live = jnp.where(live, ws, 0.0)
    contrib = jax.ops.segment_sum(jnp.where(live, contrib_elem, 0.0), seg, num_segments=C)
    w_total = jax.ops.segment_sum(w_live, seg, num_segments=C)
    entered = jax.ops.segment_max(jnp.where(live, es, False).astype(jnp.int32), seg, num_segments=C) > 0
    min_score = jax.ops.segment_min(jnp.where(live, sc, INF), seg, num_segments=C)
    kb_min = jax.ops.segment_min(jnp.where(live, kbe, INF), seg, num_segments=C)
    return ChunkAgg(
        ukeys=order.ukeys,
        w_total=w_total,
        entered=entered,
        contrib=contrib,
        kb=kb_min,
        min_score=min_score,
    )


def _aggregate_ref(keys, weights, entry, at_entry_count, scores, kb_elem):
    """The pre-ChunkOrder aggregate, verbatim (inline ``sort_by_key`` of the
    payload columns) — the bit-identity oracle for ``_aggregate_ordered``,
    used only by the reference chunk step.  Not on any production path."""
    C = keys.shape[0]
    ks, (ws, es, aec, sc, kbe, _pos) = sort_by_key(
        keys, weights, entry, at_entry_count, scores, kb_elem, jnp.arange(C)
    )
    seg, _ = segment_ids(ks)
    idx = jnp.arange(C)
    entry_idx = jnp.where(es, idx, C)
    first_entry = jax.ops.segment_min(entry_idx, seg, num_segments=C)
    fe = first_entry[seg]
    after = idx > fe
    at = (idx == fe) & es
    contrib_elem = jnp.where(after, ws, 0.0) + jnp.where(at, aec, 0.0)
    live = is_live(ks)
    w_live = jnp.where(live, ws, 0.0)
    contrib = jax.ops.segment_sum(jnp.where(live, contrib_elem, 0.0), seg, num_segments=C)
    w_total = jax.ops.segment_sum(w_live, seg, num_segments=C)
    entered = jax.ops.segment_max(jnp.where(live, es, False).astype(jnp.int32), seg, num_segments=C) > 0
    min_score = jax.ops.segment_min(jnp.where(live, sc, INF), seg, num_segments=C)
    kb_min = jax.ops.segment_min(jnp.where(live, kbe, INF), seg, num_segments=C)
    ukeys, _ = scatter_unique(ks, seg, 0.0)
    return ChunkAgg(ukeys=ukeys, w_total=w_total, entered=entered,
                    contrib=contrib, kb=kb_min, min_score=min_score)


def _continuous_entry(keys, weights, eids, tau, l, salt):
    """Per-element entry/at-entry-count/score/kb of Algorithm 4 under the
    *current* threshold tau (shared by the fast and reference aggregates)."""
    u = elem_uniform(eids, salt)
    rate = jnp.maximum(jnp.float32(1.0 / l), tau)
    delta = -jnp.log1p(-u) / rate  # rate=inf (tau=inf) -> delta=0
    kb = keybase(keys, l, salt)
    ok_regime = jnp.where(tau * l > 1.0, True, kb < tau)
    entry = (delta < weights) & ok_regime & is_live(keys)
    v = -jnp.log1p(-u) / weights
    scores = jnp.where(v <= 1.0 / l, kb, v)
    scores = jnp.where(is_empty(keys), INF, scores)
    return entry, weights - delta, scores, kb


def aggregate_continuous(keys, weights, eids, tau, l, salt,
                         order: ChunkOrder | None = None) -> ChunkAgg:
    """Entry semantics of Algorithm 4 under the *current* threshold tau.

    When ``order`` carries the pre-gathered view (or is omitted, in which
    case it is built with one), the elements are scored directly in key order
    and reduced in the same pass — the score-in-key-order path (DESIGN.md
    §9), bit-identical to score-then-gather by permutation covariance.  An
    ``order`` without the view falls back to gathering the scored columns.
    """
    if order is None:
        order = chunk_order(keys, eids, weights)
    if order.eids is not None:
        entry, aec, scores, kb = _continuous_entry(
            order.ks, order.ws, order.eids, tau, l, salt)
        return _aggregate_preordered(order, entry, aec, scores, kb)
    entry, aec, scores, kb = _continuous_entry(keys, weights, eids, tau, l, salt)
    return _aggregate(keys, weights, entry, aec, scores, kb, order)


def aggregate_continuous_ref(keys, weights, eids, tau, l, salt) -> ChunkAgg:
    """``aggregate_continuous`` through the verbatim pre-ChunkOrder reducer
    (bit-identity oracle; tests only)."""
    entry, aec, scores, kb = _continuous_entry(keys, weights, eids, tau, l, salt)
    return _aggregate_ref(keys, weights, entry, aec, scores, kb)


def aggregate_discrete(keys, weights, eids, tau, kind, l, salt,
                       order: ChunkOrder | None = None) -> ChunkAgg:
    """Entry semantics of Algorithm 2: first element whose score < tau.

    Scores in key order when the pre-gathered view is available (every
    ``element_scores`` kind is elementwise, hence permutation-covariant);
    see ``aggregate_continuous``.
    """
    if order is None:
        order = chunk_order(keys, eids, weights)
    if order.eids is not None:
        scores = element_scores(kind, order.ks, order.eids, order.ws, l, salt)
        entry = (scores < tau) & is_live(order.ks)
        return _aggregate_preordered(order, entry, order.ws, scores, scores)
    scores = element_scores(kind, keys, eids, weights, l, salt)
    entry = (scores < tau) & is_live(keys)
    return _aggregate(keys, weights, entry, weights, scores, scores, order)


def aggregate_discrete_ref(keys, weights, eids, tau, kind, l, salt) -> ChunkAgg:
    """``aggregate_discrete`` through the verbatim pre-ChunkOrder reducer
    (bit-identity oracle; tests only)."""
    scores = element_scores(kind, keys, eids, weights, l, salt)
    entry = (scores < tau) & is_live(keys)
    return _aggregate_ref(keys, weights, entry, weights, scores, scores)


def aggregate_continuous_scored(keys, weights, score, delta, entry, kb,
                                order: ChunkOrder | None = None) -> ChunkAgg:
    """``aggregate_continuous`` on precomputed per-element scoring outputs.

    ``score/delta/entry`` are exactly what the fused capscore kernel emits
    (kernels/capscore), so the multi-l update can score every l lane in one
    device pass and feed each lane through the same segment machinery.  Pass
    the chunk's shared ``order`` so the L lanes reuse one key sort.
    """
    entry = entry.astype(bool) & is_live(keys)
    score = jnp.where(is_empty(keys), INF, score)
    return _aggregate(keys, weights, entry, weights - delta, score, kb, order)


# ---------------------------------------------------------------------------
# State merge (state table + chunk aggregates -> combined table)
# ---------------------------------------------------------------------------


class TableState(NamedTuple):
    keys: jax.Array    # [cap]
    counts: jax.Array  # [cap] float32
    kb: jax.Array      # [cap] KeyBase / min-score payload
    seed: jax.Array    # [cap] running min element score (the key's bottom-k
    #                    seed over observed elements) — the coordinated-merge
    #                    handle of core.distributed.merge_fixed_k
    tau: jax.Array     # scalar float32
    step: jax.Array    # scalar int32 (eviction round counter)
    overflow: jax.Array  # scalar int32 (fixed-tau capacity overflow count)


def _merge_reduce(ks, st, cn, wt, en, ct, kb, sd):
    """Shared tail of both table merges: segment-reduce the key-ordered union
    columns and compact the combined entries to the front.

    cached key:   count += chunk total weight (Alg 2/4/5 cached branch)
    new key:      insert iff an entry event happened, count = contrib
    seed:         running min element score (both branches)
    """
    N = ks.shape[0]
    seg, _ = segment_ids(ks)
    present = jax.ops.segment_max(st.astype(jnp.int32), seg, num_segments=N) > 0
    s_count = jax.ops.segment_sum(cn, seg, num_segments=N)
    c_w = jax.ops.segment_sum(wt, seg, num_segments=N)
    c_ent = jax.ops.segment_max(en.astype(jnp.int32), seg, num_segments=N) > 0
    c_ctr = jax.ops.segment_sum(ct, seg, num_segments=N)
    kb_m = jax.ops.segment_min(kb, seg, num_segments=N)
    sd_m = jax.ops.segment_min(sd, seg, num_segments=N)
    ukeys, _ = scatter_unique(ks, seg, 0.0)

    new_count = jnp.where(present, s_count + c_w, jnp.where(c_ent, c_ctr, 0.0))
    valid = is_live(ukeys) & (present | c_ent)
    keys_c, counts_c, kb_c, seed_c = compact_valid(
        valid, ukeys, new_count, kb_m, sd_m,
        fills=(EMPTY, 0.0, jnp.float32(jnp.inf), jnp.float32(jnp.inf)),
    )
    n_valid = jnp.sum(valid.astype(jnp.int32))
    return keys_c, counts_c, kb_c, seed_c, n_valid


def _merge_table(state: TableState, agg: ChunkAgg):
    """Combine the cached table with chunk aggregates (reference form).

    Concatenates table + aggregate and re-sorts all ``cap + C`` entries per
    call.  Makes no assumption about the table's key order, so it remains the
    bit-identity oracle for ``_merge_table_sorted`` (tests/test_ingest_order)
    and the baseline of the ingest benchmark; the hot paths use the
    sorted-runs form below.
    """
    cap = state.keys.shape[0]
    C = agg.ukeys.shape[0]
    keys2 = jnp.concatenate([state.keys, agg.ukeys])
    is_state = jnp.concatenate([is_live(state.keys), jnp.zeros((C,), bool)])
    cnt2 = jnp.concatenate([state.counts, jnp.zeros((C,), state.counts.dtype)])
    wtot2 = jnp.concatenate([jnp.zeros((cap,)), agg.w_total])
    ent2 = jnp.concatenate([jnp.zeros((cap,), bool), agg.entered])
    ctr2 = jnp.concatenate([jnp.zeros((cap,)), agg.contrib])
    kb2 = jnp.concatenate([state.kb, agg.kb])
    sd2 = jnp.concatenate([state.seed, agg.min_score])

    ks, (st, cn, wt, en, ct, kb, sd) = sort_by_key(
        keys2, is_state, cnt2, wtot2, ent2, ctr2, kb2, sd2
    )
    return _merge_reduce(ks, st, cn, wt, en, ct, kb, sd)


def merge_form() -> str:
    """The table merge's form on this platform, decided at trace time:
    ``'sort'`` on the TPU, ``'gather'`` everywhere else (see
    ``_merge_table_sorted``)."""
    return "sort" if jax.default_backend() == "tpu" else "gather"


def _merge_table_sorted(state: TableState, agg: ChunkAgg, *,
                        form: str = "auto"):
    """``_merge_table`` as a pairwise two-sorted-runs merge — no segment ops.

    Requires the **sorted-table invariant**: ``state.keys`` ascending, unique,
    with all EMPTY slots compacted to the back (established at init, preserved
    by every step function below), and ``agg.ukeys`` ascending unique
    EMPTY-padded (which ``scatter_unique`` guarantees by construction).

    Because BOTH runs hold unique keys, every "segment" of the merged union
    has at most two members — one table entry, one chunk aggregate — so the
    general segment-reduce machinery of ``_merge_reduce`` collapses to a
    pairwise combine.  ``form`` picks how the runs are matched and
    interleaved (``'auto'``: ``merge_form()``):

    * ``'gather'``: match the runs against each other with two
      ``searchsorted`` rank passes, add/min the matched payloads directly,
      compact the genuinely new keys, and gather both runs into their merged
      positions.  O(N) gathers/scatters + O(C log cap) binary searches per
      lane per chunk — the fast form on XLA:CPU, where gathers are cheap.
    * ``'sort'``: two batched sorts with the payloads as sort operands
      (``_merge_runs_by_sort``) — the TPU's form, where XLA lowers
      element-wise dynamic indexing slowly and sorts natively.

    Both forms are bit-identical to ``_merge_table`` (the reductions they
    replace touch at most two values per key: float adds against 0.0 and
    mins against inf are exact).
    """
    if form == "auto":
        form = merge_form()
    if form == "sort":
        return _merge_runs_by_sort(state, agg)
    if form != "gather":
        raise ValueError(f"unknown merge form {form!r}: use 'auto', 'gather' "
                         "or 'sort'")
    cap = state.keys.shape[0]
    C = agg.ukeys.shape[0]
    inf = jnp.float32(jnp.inf)
    a_keys, b_keys = state.keys, agg.ukeys
    a_live = is_live(a_keys)
    b_live = is_live(b_keys)

    # table entries matched against the chunk aggregate (cached-key branch:
    # count += chunk total weight, kb/seed min with the chunk's)
    loc_ab = jnp.clip(searchsorted(b_keys, a_keys), 0, C - 1)
    hit_a = (b_keys[loc_ab] == a_keys) & a_live
    counts_a = state.counts + jnp.where(hit_a, agg.w_total[loc_ab], 0.0)
    kb_a = jnp.minimum(state.kb, jnp.where(hit_a, agg.kb[loc_ab], inf))
    sd_a = jnp.minimum(state.seed, jnp.where(hit_a, agg.min_score[loc_ab], inf))

    # chunk keys not in the table: inserted iff an entry event happened
    loc_ba = jnp.clip(searchsorted(a_keys, b_keys), 0, cap - 1)
    in_table = a_keys[loc_ba] == b_keys
    new = b_live & ~in_table & agg.entered
    newk, newcnt, newkb, newsd = compact_valid(
        new, b_keys, agg.contrib, agg.kb, agg.min_score,
        fills=(EMPTY, 0.0, inf, inf))

    # interleave the (still sorted) table run with the compacted new keys —
    # gather form: one searchsorted, then a cheap gather per payload column.
    # Only the first ``cap`` merged positions are built: every caller slices
    # the merge to table capacity anyway (fixed-k capacity never overflows by
    # construction; fixed-tau counts the overflow separately from n_valid).
    from_b, ia, ib = merge_sorted_runs_gather(a_keys, newk, out_len=cap)
    pick = lambda av, bv: jnp.where(from_b, bv[ib], av[ia])
    keys_c = pick(a_keys, newk)
    counts_c = pick(counts_a, newcnt)
    kb_c = pick(kb_a, newkb)
    sd_c = pick(sd_a, newsd)
    n_valid = (jnp.sum(a_live.astype(jnp.int32))
               + jnp.sum(new.astype(jnp.int32)))
    return keys_c, counts_c, kb_c, sd_c, n_valid


def _merge_runs_by_sort(state: TableState, agg: ChunkAgg):
    """The sort form of ``_merge_table_sorted``: no gathers, no scatters.

    1. One sort of the concatenated runs by (key, run), the payload columns
       riding as sort operands.  ``run`` is 0 for a table entry, 1 for a
       chunk key that entered and 2 for one that did not, so a key held by
       both runs sits as a (table, chunk) pair of neighbours.
    2. Combine with neighbour comparisons (shifted slices): a table entry
       followed by its chunk match takes ``count + w_total`` and the mins.
    3. Drop chunk entries that matched or did not enter, and EMPTY slots:
       their key becomes EMPTY and their payload the fill (0, inf, inf).
    4. A second sort by key compacts the kept entries to the front.

    Exact without a stable sort: live (key, run) pairs are unique, and every
    dropped entry carries the same fill.
    """
    cap = state.keys.shape[0]
    inf = jnp.float32(jnp.inf)
    run = jnp.concatenate([jnp.zeros((cap,), jnp.int32),
                           jnp.where(agg.entered, 1, 2).astype(jnp.int32)])
    # reprolint: disable=RPL002 -- the TPU's merge form, taken only where
    # merge_form() says 'sort'; XLA:CPU keeps the gather form
    ks, run, cn, wt, kb, sd = jax.lax.sort(
        (jnp.concatenate([state.keys, agg.ukeys]), run,
         jnp.concatenate([state.counts, agg.contrib]),
         jnp.concatenate([jnp.zeros((cap,), jnp.float32), agg.w_total]),
         jnp.concatenate([state.kb, agg.kb]),
         jnp.concatenate([state.seed, agg.min_score])),
        num_keys=2, is_stable=False)

    def nxt(x, fill):
        return jnp.concatenate([x[1:], jnp.full((1,), fill, x.dtype)])

    live = is_live(ks)
    from_table = run == 0
    eq = ks[1:] == ks[:-1]
    same_next = jnp.concatenate([eq, jnp.zeros((1,), bool)])
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), eq])
    # table entries matched by the chunk (cached-key branch: count += chunk
    # total weight, kb/seed min with the chunk's); new chunk keys keep their
    # own columns (inserted iff an entry event happened)
    hit = from_table & live & same_next
    counts = cn + jnp.where(hit, nxt(wt, 0.0), 0.0)
    kb_m = jnp.minimum(kb, jnp.where(hit, nxt(kb, inf), inf))
    sd_m = jnp.minimum(sd, jnp.where(hit, nxt(sd, inf), inf))
    keep = live & (from_table | ((run == 1) & ~same_prev))
    # reprolint: disable=RPL002 -- the TPU's merge form (see above)
    out = jax.lax.sort(
        (jnp.where(keep, ks, EMPTY), jnp.where(keep, counts, 0.0),
         jnp.where(keep, kb_m, inf), jnp.where(keep, sd_m, inf)),
        num_keys=1, is_stable=False)
    keys_c, counts_c, kb_c, sd_c = (x[:cap] for x in out)
    return keys_c, counts_c, kb_c, sd_c, jnp.sum(keep.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Single-chunk streaming steps (shared by the scan bodies below and by the
# incremental state API in core/incremental.py — same function, same jit)
# ---------------------------------------------------------------------------


def init_table(capacity: int, tau=jnp.inf) -> TableState:
    """Fresh O(capacity) sampler table (the scan carry / streaming state)."""
    return TableState(
        keys=jnp.full((capacity,), EMPTY, dtype=jnp.int32),
        counts=jnp.zeros((capacity,), jnp.float32),
        kb=jnp.full((capacity,), jnp.inf, jnp.float32),
        seed=jnp.full((capacity,), jnp.inf, jnp.float32),
        tau=jnp.float32(tau),
        step=jnp.int32(0),
        overflow=jnp.int32(0),
    )


def fixed_tau_step(state: TableState, keys, weights, eids, l, salt, *, kind,
                   order: ChunkOrder | None = None) -> TableState:
    """Advance a fixed-threshold sampler (Alg 2/4) by one chunk of elements."""
    capacity = state.keys.shape[0]
    if order is None:
        order = chunk_order(keys, eids, weights)
    if kind == "continuous":
        agg = aggregate_continuous(keys, weights, eids, state.tau, l, salt, order)
    else:
        agg = aggregate_discrete(keys, weights, eids, state.tau, kind, l, salt, order)
    keys_c, counts_c, kb_c, seed_c, n_valid = _merge_table_sorted(state, agg)
    over = state.overflow + jnp.maximum(n_valid - capacity, 0)
    return TableState(keys_c[:capacity], counts_c[:capacity], kb_c[:capacity],
                      seed_c[:capacity], state.tau, state.step + 1, over)


def fixed_k_merge(state: TableState, agg: ChunkAgg) -> TableState:
    """Fold a chunk aggregate into a fixed-k table WITHOUT evicting.

    Increments the eviction-round/step counter; the caller is responsible for
    running ``evict_table`` before the table's capacity can overflow (the
    incremental spec sizes capacity as ``k + evict_every * chunk`` for exactly
    this reason).  Preserves the sorted-table invariant.
    """
    capacity = state.keys.shape[0]
    keys_c, counts_c, kb_c, seed_c, _ = _merge_table_sorted(state, agg)
    return TableState(keys_c[:capacity], counts_c[:capacity], kb_c[:capacity],
                      seed_c[:capacity], state.tau, state.step + 1, state.overflow)


def evict_table(table: TableState, *, k, l, salt, max_evict=None,
                select: str = "auto") -> TableState:
    """Batched eviction of a merged table back down to <= k valid keys, then
    re-compaction so the sorted-table invariant survives the EMPTY holes the
    eviction punches.  ``max_evict`` bounds the eviction count and ``select``
    the threshold-selection lowering (see ``_evict_to_k``); the round number
    is the table's step counter."""
    keys_e, counts_e, kb_e, seed_e, tau_e = _evict_to_k(
        table.keys, table.counts, table.kb, table.seed, table.tau, k, l, salt,
        table.step, max_evict=max_evict, select=select)
    keys_c, counts_c, kb_c, seed_c = compact_valid(
        is_live(keys_e), keys_e, counts_e, kb_e, seed_e,
        fills=(EMPTY, 0.0, jnp.float32(jnp.inf), jnp.float32(jnp.inf)),
    )
    return TableState(keys_c, counts_c, kb_c, seed_c, tau_e, table.step,
                      table.overflow)


def fixed_k_step(state: TableState, keys, weights, eids, l, salt, *, k,
                 order: ChunkOrder | None = None) -> TableState:
    """Advance a fixed-k continuous sampler (Alg 5) by one chunk: aggregate
    under the current threshold, merge, batch-evict back down to <= k.

    Precondition (holds by construction inside the scan loops): the incoming
    table carries <= k valid keys, so at most ``chunk`` keys can be evicted.
    """
    if order is None:
        order = chunk_order(keys, eids, weights)
    agg = aggregate_continuous(keys, weights, eids, state.tau, l, salt, order)
    merged = fixed_k_merge(state, agg)
    return evict_table(merged, k=k, l=l, salt=salt, max_evict=keys.shape[0])


def fixed_k_step_scored(state: TableState, keys, weights, score, delta, entry, kb,
                        *, k, l, salt, order: ChunkOrder | None = None) -> TableState:
    """``fixed_k_step`` on precomputed capscore outputs (multi-l fused path)."""
    if order is None:
        order = chunk_order(keys)
    agg = aggregate_continuous_scored(keys, weights, score, delta, entry, kb, order)
    merged = fixed_k_merge(state, agg)
    return evict_table(merged, k=k, l=l, salt=salt, max_evict=keys.shape[0])


def fixed_k_step_scored_ref(state: TableState, keys, weights, score, delta,
                            entry, kb, *, k, l, salt) -> TableState:
    """The pre-single-sort chunk step, kept verbatim as the bit-identity
    oracle: per-lane inline key sort (``_aggregate_ref``), concat-and-re-sort
    table merge, and a full descending sort in the eviction.  Used by
    tests/test_ingest_order and the `reference` path of the ingest benchmark
    — not by production."""
    capacity = state.keys.shape[0]
    e = entry.astype(bool) & is_live(keys)
    s = jnp.where(is_empty(keys), INF, score)
    agg = _aggregate_ref(keys, weights, e, weights - delta, s, kb)
    keys_c, counts_c, kb_c, seed_c, _ = _merge_table(state, agg)
    keys_e, counts_e, kb_e, seed_e, tau_e = _evict_to_k_ref(
        keys_c[:capacity], counts_c[:capacity], kb_c[:capacity], seed_c[:capacity],
        state.tau, k, l, salt, state.step + 1,
    )
    return TableState(keys_e, counts_e, kb_e, seed_e, tau_e, state.step + 1,
                      state.overflow)


def chunk_bottomk_summary(keys, eids, weights, l, salt, *, kind):
    """Per-chunk (unique key, min element score) summary for pass-1 bottom-k."""
    chunk = keys.shape[0]
    scores = element_scores(kind, keys, eids, weights, l, salt)
    ks, (sc,) = sort_by_key(keys, scores)
    seg, _ = segment_ids(ks)
    mins = jax.ops.segment_min(jnp.where(is_live(ks), sc, INF), seg, num_segments=chunk)
    ukeys, _ = scatter_unique(ks, seg, 0.0)
    return ukeys, jnp.where(is_live(ukeys), mins, INF)


def merge_bottomk_summary(skeys, sseeds, ukeys, useeds, cap):
    """Merge two (key, seed) summaries: min-seed per duplicate key, bottom-cap.

    Lossless for the bottom-cap of the union (paper §3.1) — the building
    block of pass-1 chunk accumulation, the incremental per-lane summaries
    and every cross-shard merge in core.distributed.
    """
    keys2 = jnp.concatenate([skeys, ukeys])
    seeds2 = jnp.concatenate([sseeds, useeds])
    ks2, (sd2,) = sort_by_key(keys2, seeds2)
    seg2, _ = segment_ids(ks2)
    N = ks2.shape[0]
    sd_m = jax.ops.segment_min(sd2, seg2, num_segments=N)
    uk2, _ = scatter_unique(ks2, seg2, 0.0)
    sd_m = jnp.where(is_live(uk2), sd_m, INF)
    sd_k, uk_k = bottom_k_by(sd_m, cap, uk2, fills=(EMPTY,))
    return uk_k, sd_k


def pass1_step(carry, keys, weights, eids, l, salt, *, kind, cap):
    """Advance a bottom-k-by-seed summary (Alg 1 pass I) by one chunk."""
    skeys, sseeds = carry
    ukeys, mins = chunk_bottomk_summary(keys, eids, weights, l, salt, kind=kind)
    return merge_bottomk_summary(skeys, sseeds, ukeys, mins, cap)


def chunk_bottomk_summary_scored(keys, scores, order: ChunkOrder | None = None):
    """Per-lane (unique key, min element score) chunk summaries from
    precomputed multi-lane scores [L, C] (the fused capscore pass-1 path).

    One sort of the chunk by key is shared by all lanes (pass the chunk's
    ``ChunkOrder`` to share it with the sketch advance too); the per-lane
    work is a single segment_min.  Returns (ukeys [C], mins [L, C]).
    """
    C = keys.shape[0]
    if order is None:
        order = chunk_order(keys)
    live = is_live(order.ks)
    mins = jax.vmap(
        lambda s: jax.ops.segment_min(jnp.where(live, s[order.perm], INF),
                                      order.seg, num_segments=C)
    )(scores)
    return order.ukeys, jnp.where(is_live(order.ukeys), mins, INF)


def pass1_step_multi(carry, keys, scores, *, cap, order: ChunkOrder | None = None):
    """Advance stacked per-lane bottom-cap summaries ([L, cap] keys/seeds) by
    one chunk whose multi-lane scores were already computed (capscore_multi)."""
    skeys, sseeds = carry
    ukeys, mins = chunk_bottomk_summary_scored(keys, scores, order)
    return jax.vmap(
        lambda sk, ss, mn: merge_bottomk_summary(sk, ss, ukeys, mn, cap)
    )(skeys, sseeds, mins)


# -- key-sorted summary carry: the in-scan form of the bottom-cap summaries --
#
# ``merge_bottomk_summary`` pays an argsort of (cap + C) keys plus three
# scatter-shaped segment ops and a TopK per lane per chunk — the single most
# expensive stage of the multi-lane ingest step on CPU.  Inside a scan the
# summary can instead be carried KEY-sorted (ascending, unique, EMPTY last —
# the same invariant as the sampler table), which turns the whole advance
# into searchsorted + gather/cumsum primitives:
#
#   * duplicate keys min-merge by two searchsorted rank passes (pairwise,
#     since both runs are unique — exactly the _merge_table_sorted trick);
#   * the bottom-cap truncation selects the cap-th smallest seed with a
#     plain VALUE sort (no TopK, no argsort) and compacts survivors in key
#     order.
#
# Bit-identity with the seed-sorted iterated form (property-tested): bottom-k
# sketches are exactly composable (paper §3.1) — any entry dropped by a
# truncation can never re-enter the final bottom-cap, and a surviving key's
# stored seed is its true min — so the final bottom-cap (set, seeds) is
# invariant to the carry layout.  Ties at the truncation threshold break the
# same way too: ``bottom_k_by``'s top_k prefers lower indices, and its input
# array is key-ascending, so tied entries survive smallest-key-first — which
# is precisely what compacting a key-sorted carry keeps.  Converting the
# final carry through ``summary_from_keysorted`` therefore reproduces the
# reference arrays bit for bit (same multiset, same seed-ascending order,
# same index tie-break).


def summary_to_keysorted(skeys, sseeds):
    """Re-lay a bottom-cap summary (seed-sorted, the state/checkpoint form)
    as the key-sorted scan carry: ascending unique keys, EMPTY (+inf) last."""
    # reprolint: disable=RPL002 -- once-per-restore boundary conversion (state
    # checkpoint -> scan carry), not on the per-chunk path; a full argsort is fine
    o = jnp.argsort(skeys, stable=True)
    return skeys[o], sseeds[o]


def summary_from_keysorted(skeys, sseeds, cap):
    """Back to the state/checkpoint layout: seed-ascending via the same
    ``bottom_k_by`` selection every ``merge_bottomk_summary`` call ends with
    (a no-op selection here — the carry already holds <= cap entries)."""
    sd_k, uk_k = bottom_k_by(sseeds, cap, skeys, fills=(EMPTY,))
    return uk_k, sd_k


def pass1_fold_keysorted(skeys, sseeds, ukeys, mins, cap):
    """One chunk of bottom-cap summary advance on the key-sorted carry.

    ``skeys``/``sseeds``: the [cap] key-sorted carry.  ``ukeys``/``mins``:
    the chunk's unique keys (ascending, EMPTY-padded — ``ChunkOrder.ukeys``)
    and their per-key min element scores (e.g. the fused aggregate's
    ``min_score`` column, which equals the pass-1 chunk summary because
    element scores are tau-independent).  No sort of the union, no TopK, no
    segment ops — see the block comment above for the bit-identity argument.
    """
    C = ukeys.shape[0]
    cap_s = skeys.shape[0]
    a_keys, a_live = skeys, is_live(skeys)
    b_keys, b_live = ukeys, is_live(ukeys)

    # rank passes (kept UNclipped: the raw rank is also the count of
    # other-run keys below, which the position formulas below need even at
    # the array-end edge)
    loc_ab_raw = searchsorted(b_keys, a_keys)
    loc_ba_raw = searchsorted(a_keys, b_keys)

    # carried keys matched against the chunk summary: seed = min of both
    loc_ab = jnp.minimum(loc_ab_raw, C - 1)
    hit_a = (b_keys[loc_ab] == a_keys) & a_live
    sd_a = jnp.minimum(sseeds, jnp.where(hit_a, mins[loc_ab], INF))

    # chunk keys not yet carried: candidate insertions
    loc_ba = jnp.minimum(loc_ba_raw, cap_s - 1)
    new = b_live & ~(a_keys[loc_ba] == b_keys)

    # bottom-cap threshold: cap-th smallest seed of the union, by rank
    # selection (``kth_smallest`` — no sort, no argsort, no TopK, all of
    # which XLA:CPU lowers as scalar comparator loops)
    sd_a_live = jnp.where(a_live, sd_a, INF)
    sd_b_new = jnp.where(new, mins, INF)
    thr = kth_smallest(jnp.concatenate([sd_a_live, sd_b_new]), cap - 1)

    # selection must match ``bottom_k_by`` exactly under seed TIES at thr:
    # every seed strictly below thr survives (value order dominates), and
    # the remaining quota goes to thr-tied entries smallest-key-first
    # (top_k's lowest-index tie-break on a key-ascending array).  The tied
    # key-order rank is assembled from the same cross-run prefix counts as
    # the merge positions below.
    below_a = a_live & (sd_a < thr)
    below_b = new & (mins < thr)
    tied_a = a_live & (sd_a == thr)
    tied_b = new & (mins == thr)
    quota = cap - (jnp.sum(below_a.astype(jnp.int32))
                   + jnp.sum(below_b.astype(jnp.int32)))
    cst_a = jnp.cumsum(tied_a)
    cst_b = jnp.cumsum(tied_b)
    tb_lt = jnp.where(loc_ab_raw > 0, cst_b[jnp.maximum(loc_ab_raw - 1, 0)], 0)
    ta_lt = jnp.where(loc_ba_raw > 0, cst_a[jnp.maximum(loc_ba_raw - 1, 0)], 0)
    keep_a = below_a | (tied_a & (cst_a - 1 + tb_lt < quota))
    keep_b = below_b | (tied_b & (cst_b - 1 + ta_lt < quota))

    # every survivor's merged position is already determined by the ranks in
    # hand (kept keys of the two runs are disjoint and each run is sorted):
    #   pos = (kept same-run entries before it) + (kept other-run keys below
    #   it, read off the loc_ab/loc_ba ranks) — so the merged carry
    # assembles with two direct scatters per column, no compaction passes
    # and no interleave rank pass.  Overflow survivors (the > cap tail that
    # only a seed tie at thr can produce) land on the sacrificial slot.
    csa = jnp.cumsum(keep_a)
    csb = jnp.cumsum(keep_b)
    nb_lt = jnp.where(loc_ab_raw > 0, csb[jnp.maximum(loc_ab_raw - 1, 0)], 0)
    na_lt = jnp.where(loc_ba_raw > 0, csa[jnp.maximum(loc_ba_raw - 1, 0)], 0)
    pos_a = jnp.where(keep_a, csa - 1 + nb_lt, cap_s)
    pos_b = jnp.where(keep_b, csb - 1 + na_lt, cap_s)
    pos_a = jnp.minimum(pos_a, cap_s)
    pos_b = jnp.minimum(pos_b, cap_s)
    kk = (jnp.full((cap_s + 1,), EMPTY, a_keys.dtype)
          .at[pos_a].set(a_keys).at[pos_b].set(b_keys)[:cap_s])
    ss = (jnp.full((cap_s + 1,), INF, sd_a.dtype)
          .at[pos_a].set(sd_a).at[pos_b].set(mins)[:cap_s])
    return kk, ss


# ---------------------------------------------------------------------------
# Fixed-threshold samplers (exact Algorithm 2 / 4)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kind", "capacity", "chunk"))
def _run_fixed_tau(keys, weights, l, salt, tau, *, kind, capacity, chunk):
    n = keys.shape[0]
    n_chunks = n // chunk
    keys = keys.reshape(n_chunks, chunk)
    weights = weights.reshape(n_chunks, chunk)
    eids = jnp.arange(n, dtype=jnp.int32).reshape(n_chunks, chunk)

    init = init_table(capacity, tau)

    def body(state: TableState, xs):
        ck, cw, ce = xs
        return fixed_tau_step(state, ck, cw, ce, l, salt, kind=kind), None

    state, _ = jax.lax.scan(body, init, (keys, weights, eids))
    return state


def sample_fixed_tau(keys, weights=None, *, tau, l, kind="continuous", salt=0,
                     chunk=2048, capacity=8192) -> SampleResult:
    keys, weights = _prep(keys, weights, chunk)
    st = _run_fixed_tau(keys, weights, jnp.float32(l), jnp.uint32(salt), jnp.float32(tau),
                        kind=kind, capacity=capacity, chunk=chunk)
    overflow = int(jax.device_get(st.overflow))
    if overflow > 0:
        raise RuntimeError(f"fixed-tau capacity overflow ({overflow}); raise capacity")
    return _to_result(st, l=l, kind=kind, tau=float(tau))


# ---------------------------------------------------------------------------
# Fixed-k continuous sampler (Algorithm 5, batched evictions)
# ---------------------------------------------------------------------------


def _evict_z(state_keys, counts, kb, tau, l, salt, round_no):
    """Per-key eviction race scores z (§5.2) + the pieces the survivor-count
    adjustment needs.  Shared by the top_k and reference eviction forms."""
    valid = is_live(state_keys)
    ux = H.uniform01(H.hash_combine(state_keys, jnp.uint32(SALT_EVICT_U),
                                    round_no.astype(jnp.uint32), jnp.uint32(salt)))
    rx = H.uniform01(H.hash_combine(state_keys, jnp.uint32(SALT_EVICT_R),
                                    round_no.astype(jnp.uint32), jnp.uint32(salt)))
    ex = -jnp.log1p(-rx)
    inv_l = jnp.float32(1.0 / l)
    safe_counts = jnp.maximum(counts, 1e-30)
    race = jnp.where(ex / safe_counts >= inv_l, ex / safe_counts, kb)
    seed_part = tau * ux  # tau=inf -> inf
    # Score-collapse correction (see samplers.py): entry branch threshold
    # becomes KeyBase(x) when the resampled entry score drops below 1/l.
    entry_thresh = jnp.where(seed_part >= inv_l, seed_part, kb)
    z_hi = jnp.minimum(entry_thresh, race)     # tau*l > 1 regime
    z_lo = kb                                  # tau*l <= 1 regime (distinct-like)
    z = jnp.where(tau * l > 1.0, z_hi, z_lo)
    z = jnp.where(valid, z, -INF)
    return valid, z, entry_thresh, ex, inv_l


def _evict_apply(state_keys, counts, kb, seed, tau, l, delta, tau_star,
                 valid, z, entry_thresh, ex, inv_l):
    """Apply an eviction threshold tau*: drop z >= tau*, adjust survivors."""
    evict = valid & (z >= tau_star) & (delta > 0)

    # survivor count adjustment (tau*l>1 regime only; see samplers.py notes)
    new_rate = jnp.maximum(inv_l, tau_star)
    guard = (entry_thresh >= tau_star) & (tau * l > 1.0)
    adj = counts - ex / new_rate
    counts = jnp.where(valid & ~evict & guard & (delta > 0), adj, counts)

    keys_o = jnp.where(evict, EMPTY, state_keys)
    counts_o = jnp.where(evict, 0.0, counts)
    kb_o = jnp.where(evict, INF, kb)
    seed_o = jnp.where(evict, INF, seed)
    tau_o = jnp.where(delta > 0, tau_star, tau)
    return keys_o, counts_o, kb_o, seed_o, tau_o


def _evict_to_k(state_keys, counts, kb, seed, tau, k, l, salt, round_no, *,
                max_evict: int | None = None, select: str = "auto"):
    """Batched eviction (§5.2): tau* = delta-th largest z; drop z >= tau*.

    Only the THRESHOLD is needed, so the selection is a pure lowering
    decision — every route returns the same value:

    * ``'topk'``: ``jax.lax.top_k`` over the ``max_evict`` largest z (native
      partial selection on TPU; valid whenever the caller can bound
      delta = n_valid - k — the chunk steps pass the chunk size, since a
      table that was <= k valid gains at most ``chunk`` keys per merge;
      ``max_evict=None`` keeps the full width, the cross-host merge path).
    * ``'rank'``: ``segments.kth_smallest`` bit-prefix rank selection — no
      sort at all.  XLA:CPU lowers both TopK and full sorts at ~250ns/elem
      (scalar comparator loops), which made threshold selection the
      hottest primitive of the whole ingest step; the rank select is ~15x
      cheaper there.
    * ``'auto'``: backend-derived at trace time (top_k on TPU, rank
      elsewhere).

    Bit-identical across routes and to the reference full descending sort:
    the delta-th largest z is the same multiset order statistic however it
    is found (tests/test_ingest_order.py pins all three).
    """
    n = state_keys.shape[0]
    valid, z, entry_thresh, ex, inv_l = _evict_z(
        state_keys, counts, kb, tau, l, salt, round_no)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    delta = jnp.maximum(n_valid - k, 0)
    if select == "auto":
        select = "topk" if jax.default_backend() == "tpu" else "rank"
    if select == "rank":
        # delta-th largest == (n - delta)-th smallest (0-indexed)
        z_sel = kth_smallest(z, jnp.clip(n - delta, 0, n - 1))
    else:
        top = n if max_evict is None else min(int(max_evict), n)
        # reprolint: disable=RPL002 -- select='topk' is the opt-in TPU-native
        # route; the XLA:CPU default is select='kth' via kth_smallest below
        z_top = jax.lax.top_k(z, top)[0]
        z_sel = z_top[jnp.maximum(delta - 1, 0)]
    tau_star = jnp.where(delta > 0, z_sel, tau)
    return _evict_apply(state_keys, counts, kb, seed, tau, l, delta, tau_star,
                        valid, z, entry_thresh, ex, inv_l)


def _evict_to_k_ref(state_keys, counts, kb, seed, tau, k, l, salt, round_no):
    """Reference eviction: full descending sort for tau* (the pre-top_k form,
    kept as the bit-identity oracle and benchmark baseline)."""
    valid, z, entry_thresh, ex, inv_l = _evict_z(
        state_keys, counts, kb, tau, l, salt, round_no)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    delta = jnp.maximum(n_valid - k, 0)
    # reprolint: disable=RPL002 -- verbatim pre-top_k oracle; the full sort IS
    # the reference semantics the fast path is bit-tested against
    z_desc = -jnp.sort(-z)
    tau_star = jnp.where(delta > 0, z_desc[jnp.maximum(delta - 1, 0)], tau)
    return _evict_apply(state_keys, counts, kb, seed, tau, l, delta, tau_star,
                        valid, z, entry_thresh, ex, inv_l)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _run_fixed_k_continuous(keys, weights, l, salt, *, k, chunk):
    n = keys.shape[0]
    n_chunks = n // chunk
    capacity = k + chunk  # merge never overflows: <=k valid + <=chunk new
    keys = keys.reshape(n_chunks, chunk)
    weights = weights.reshape(n_chunks, chunk)
    eids = jnp.arange(n, dtype=jnp.int32).reshape(n_chunks, chunk)

    init = init_table(capacity)

    def body(state: TableState, xs):
        ck, cw, ce = xs
        return fixed_k_step(state, ck, cw, ce, l, salt, k=k), None

    state, _ = jax.lax.scan(body, init, (keys, weights, eids))
    return state


def sample_fixed_k(keys, weights=None, *, k, l, salt=0, chunk=2048) -> SampleResult:
    """1-pass fixed-size continuous SH_l sample (the paper's recommended scheme)."""
    keys, weights = _prep(keys, weights, chunk)
    st = _run_fixed_k_continuous(keys, weights, jnp.float32(l), jnp.uint32(salt), k=k, chunk=chunk)
    return _to_result(st, l=l, kind="continuous", tau=float(jax.device_get(st.tau)))


# ---------------------------------------------------------------------------
# 2-pass sampler (Algorithm 1): exact bottom-k by seed + exact weights
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kind", "k", "chunk"))
def _run_pass1(keys, weights, l, salt, *, kind, k, chunk):
    n = keys.shape[0]
    n_chunks = n // chunk
    keys = keys.reshape(n_chunks, chunk)
    weights = weights.reshape(n_chunks, chunk)
    eids = jnp.arange(n, dtype=jnp.int32).reshape(n_chunks, chunk)
    cap = k + 1  # bottom-(k+1) is mergeable and yields tau exactly

    init_keys = jnp.full((cap,), EMPTY, dtype=jnp.int32)
    init_seeds = jnp.full((cap,), jnp.inf, jnp.float32)

    def body(carry, xs):
        ck, cw, ce = xs
        return pass1_step(carry, ck, cw, ce, l, salt, kind=kind, cap=cap), None

    (skeys, sseeds), _ = jax.lax.scan(body, (init_keys, init_seeds), (keys, weights, eids))
    return skeys, sseeds


@functools.partial(jax.jit, static_argnames=("chunk",))
def _run_pass2(keys, weights, sampled_sorted, *, chunk):
    n = keys.shape[0]
    n_chunks = n // chunk
    keys = keys.reshape(n_chunks, chunk)
    weights = weights.reshape(n_chunks, chunk)
    k = sampled_sorted.shape[0]

    def body(acc, xs):
        ck, cw = xs
        loc = searchsorted(sampled_sorted, ck)
        loc = jnp.clip(loc, 0, k - 1)
        match = (sampled_sorted[loc] == ck) & is_live(ck)
        return acc.at[loc].add(jnp.where(match, cw, 0.0)), None

    # reprolint: disable=RPL004 -- dtype dispatch, not a literal: f64 only when
    # the caller already enabled x64 and handed us f64 weights
    acc, _ = jax.lax.scan(body, jnp.zeros((k,), jnp.float64 if weights.dtype == jnp.float64 else jnp.float32), (keys, weights))
    return acc


def sample_two_pass(keys, weights=None, *, k, l, kind="continuous", salt=0, chunk=2048) -> SampleResult:
    keys, weights = _prep(keys, weights, chunk)
    skeys, sseeds = _run_pass1(keys, weights, jnp.float32(l), jnp.uint32(salt), kind=kind, k=k, chunk=chunk)
    skeys = np.asarray(skeys)
    sseeds = np.asarray(sseeds)
    valid = is_live(skeys)
    order = np.argsort(sseeds[valid])
    kk = skeys[valid][order]
    if len(kk) > k:
        tau = float(sseeds[valid][order][k])
        kk = kk[:k]
    else:
        tau = math.inf
    sampled_sorted = np.sort(kk)
    wts = _run_pass2(keys, weights, jnp.asarray(sampled_sorted), chunk=chunk)
    return SampleResult(
        keys=sampled_sorted, counts=np.asarray(wts, dtype=np.float64), tau=tau,
        l=l, kind=kind, exact_weights=True,
    )


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------


def _prep(keys, weights, chunk):
    # same validation surface as the streaming observe()/reconcile() path:
    # bad dtypes / out-of-int32 ids / the reserved EMPTY id raise instead of
    # silently wrapping into another key's randomness
    keys = normalize_keys(keys)
    n = len(keys)
    if weights is None:
        weights = np.ones(n, dtype=np.float32)
    weights = np.asarray(weights, dtype=np.float32)
    pad = (-n) % chunk
    if pad:
        keys = np.concatenate([keys, np.full(pad, int(EMPTY), dtype=np.int32)])
        weights = np.concatenate([weights, np.zeros(pad, dtype=np.float32)])
    return jnp.asarray(keys), jnp.asarray(weights)


def _to_result(st: TableState, *, l, kind, tau) -> SampleResult:
    keys = np.asarray(st.keys)
    counts = np.asarray(st.counts, dtype=np.float64)
    valid = is_live(keys)
    order = np.argsort(keys[valid])
    return SampleResult(
        keys=keys[valid][order], counts=counts[valid][order], tau=tau, l=l, kind=kind,
    )
