"""Sort-and-segment utilities + first-class query ``Segment`` objects.

Two related meanings of "segment" live here on purpose:

1. **Sorted-run segments** (the original contents): the TPU-native
   replacement for hash tables.  The sequential algorithms probe a dict per
   element; the vectorized samplers instead sort a chunk by key and reduce
   with ``jax.ops.segment_*``.  These helpers are shared by the samplers,
   the GNN message passing and the recsys EmbeddingBag (JAX has no native
   EmbeddingBag/CSR — segment ops ARE the substrate).

2. **Query segments** (the H in Q(f, H), paper §2): first-class,
   *hashable* predicates over key ids.  Every query surface
   (``estimators.estimate``, ``freqfns.exact_statistic``, the batched
   ``stats.query.QueryEngine``) coerces its ``segment`` argument through
   ``as_segment`` so id-lists, Python predicates, boolean masks and hash
   buckets all mean the same thing everywhere — and so the query engine can
   compile a segment ONCE per sketch lane into a device mask and cache it by
   ``Segment`` identity instead of re-running ``np.isin`` per query.

Conventions: padding key is ``EMPTY = int32 max`` so padded slots sort last;
all shapes are static (chunk size / capacity are compile-time constants).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import hashing as H

EMPTY = jnp.int32(2**31 - 1)
_EMPTY_INT = int(EMPTY)


def is_empty(keys):
    """Canonical "is this slot padding?" test for key arrays.

    Works on traced jnp arrays and host numpy arrays alike (numpy stays on
    host — no implicit device round-trip) and is the single point where the
    EMPTY encoding is compared, so the sentinel stays changeable in one
    place. Enforced by reprolint RPL006 on hot-path modules.
    """
    if isinstance(keys, np.ndarray):
        return keys == _EMPTY_INT
    return keys == EMPTY


def is_live(keys):
    """Negation of :func:`is_empty`; same contract."""
    if isinstance(keys, np.ndarray):
        return keys != _EMPTY_INT
    return keys != EMPTY

# salt lane for HashBucket segments (disjoint from the sampler salt lanes in
# core.samplers, which start at 0x01)
SALT_SEGMENT = 0x5E


# ---------------------------------------------------------------------------
# Query segments: the H in Q(f, H)
# ---------------------------------------------------------------------------


class Segment:
    """A set of key ids, evaluable as a boolean mask over any key array.

    Subclasses implement ``mask_np(keys) -> bool[len(keys)]`` and are
    hashable/equatable by *content* (or by held-object identity for opaque
    predicates), so compiled per-lane masks can be cached with the Segment
    itself as the cache key — holding the Segment in the cache keeps any
    captured callable alive, which keeps identity-based keys valid.
    """

    def mask_np(self, keys: np.ndarray) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    def describe(self) -> str:
        return type(self).__name__


class AllKeys(Segment):
    """H = all keys (segment=None everywhere coerces to this)."""

    def mask_np(self, keys):
        return np.ones(len(keys), dtype=bool)

    def __eq__(self, other):
        return type(other) is AllKeys

    def __hash__(self):
        return hash(AllKeys)

    def describe(self):
        return "all"


class IdSet(Segment):
    """Membership in an explicit id set (kept sorted; content-hashed)."""

    def __init__(self, ids):
        self.ids = np.unique(np.asarray(ids).reshape(-1))
        self._digest = hash((len(self.ids), self.ids.tobytes()))

    def mask_np(self, keys):
        # np.isin == the historical estimators._segment_mask id-list semantics
        return np.isin(keys, self.ids)

    def __eq__(self, other):
        return (type(other) is IdSet and self._digest == other._digest
                and np.array_equal(self.ids, other.ids))

    def __hash__(self):
        return self._digest

    def describe(self):
        return f"ids[{len(self.ids)}]"


class Mask(Segment):
    """A precomputed boolean mask aligned with a specific key array.

    This is the historical ``freqfns.exact_statistic`` calling convention;
    the mask length must match the key array it is applied to.
    """

    def __init__(self, mask):
        self.mask = np.asarray(mask, dtype=bool).reshape(-1)
        self._digest = hash((len(self.mask), self.mask.tobytes()))

    def mask_np(self, keys):
        if len(self.mask) != len(keys):
            raise ValueError(
                f"Mask segment of length {len(self.mask)} applied to "
                f"{len(keys)} keys — mask segments are positional; use IdSet/"
                "Predicate/HashBucket for key-id semantics")
        return self.mask

    def __eq__(self, other):
        return (type(other) is Mask and self._digest == other._digest
                and np.array_equal(self.mask, other.mask))

    def __hash__(self):
        return self._digest

    def describe(self):
        return f"mask[{int(self.mask.sum())}/{len(self.mask)}]"


class Predicate(Segment):
    """An arbitrary vectorized predicate over key ids (host-evaluated).

    Equality/hash are by callable identity: two Predicates wrapping the same
    function object compare equal (and hit the same compiled-mask cache);
    distinct lambdas are distinct segments even if textually identical.
    """

    def __init__(self, fn, name: str | None = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "predicate")

    def mask_np(self, keys):
        return np.asarray(self.fn(keys), dtype=bool).reshape(len(keys))

    def __eq__(self, other):
        return type(other) is Predicate and self.fn is other.fn

    def __hash__(self):
        return hash(self.fn)

    def describe(self):
        return self.name


class HashBucket(Segment):
    """H = keys hashing into bucket ``bucket`` of ``n_buckets`` (A/B slices).

    Uses the shared counter-based hashing substrate (core.hashing), so the
    same (n_buckets, bucket, salt) triple selects the same keys on every
    host and backend.
    """

    def __init__(self, n_buckets: int, bucket: int, salt: int = 0):
        if not 0 <= bucket < n_buckets:
            raise ValueError(f"bucket {bucket} not in [0, {n_buckets})")
        self.n_buckets, self.bucket, self.salt = int(n_buckets), int(bucket), int(salt)

    def mask_np(self, keys):
        h = H.hash_combine_np(np.asarray(keys), np.uint32(SALT_SEGMENT),
                              np.uint32(self.salt))
        return (h % np.uint32(self.n_buckets)) == np.uint32(self.bucket)

    def __eq__(self, other):
        return (type(other) is HashBucket
                and (self.n_buckets, self.bucket, self.salt)
                == (other.n_buckets, other.bucket, other.salt))

    def __hash__(self):
        return hash((HashBucket, self.n_buckets, self.bucket, self.salt))

    def describe(self):
        return f"bucket {self.bucket}/{self.n_buckets}"


def as_segment(segment) -> Segment:
    """Coerce every historical ``segment=`` convention to a Segment.

    None -> AllKeys; Segment -> itself; callable -> Predicate; boolean
    array -> positional Mask; any other array-like -> IdSet membership.
    """
    if segment is None:
        return AllKeys()
    if isinstance(segment, Segment):
        return segment
    if callable(segment):
        return Predicate(segment)
    arr = np.asarray(segment)
    if arr.dtype == bool:
        return Mask(arr)
    return IdSet(arr)


def normalize_keys(keys) -> np.ndarray:
    """Validate and convert stream keys to the canonical int32 form.

    Every ingestion surface — the stateful ``observe``/``reconcile`` AND the
    one-shot samplers (``vectorized._prep``) — funnels through this one helper
    so keys can never be *silently* wrapped by an ``np.asarray(keys, np.int32)``
    cast: non-integer dtypes, values outside int32 range, and the reserved
    padding id ``EMPTY`` (int32 max) all raise instead of corrupting the
    per-key randomness.
    """
    arr = np.asarray(keys).reshape(-1)
    if arr.dtype == np.int32:
        out = arr
    else:
        if not np.issubdtype(arr.dtype, np.integer):
            raise TypeError(
                f"stream keys must be integers, got dtype {arr.dtype} — "
                "casting floats/objects would silently truncate key ids")
        if arr.size and (arr.min() < -_EMPTY_INT - 1 or arr.max() > _EMPTY_INT):
            bad = arr[(arr < -_EMPTY_INT - 1) | (arr > _EMPTY_INT)][0]
            raise ValueError(
                f"stream key {bad} outside int32 range — int32 is the key "
                "domain of the sketches; remap ids before ingestion")
        out = arr.astype(np.int32)
    if out.size and out.max() == _EMPTY_INT:
        raise ValueError(
            f"stream key {_EMPTY_INT} is the reserved EMPTY padding id — "
            "remap it before ingestion")
    return out


def sort_by_key(keys, *arrays):
    """Stable-sort ``keys`` ascending; apply the permutation to all arrays."""
    order = jnp.argsort(keys, stable=True)
    return keys[order], tuple(a[order] for a in arrays)


def stable_sort_with_perm(keys):
    """Registered XLA sort dual: ``(keys[perm], perm)`` under the stable
    ascending argsort.  The chunksort Pallas kernel pins bit-identity against
    exactly this function; it is also the fallback route when a backend has
    no compiled sort lowering."""
    perm = jnp.argsort(keys, stable=True)
    return keys[perm], perm


class ChunkOrder(NamedTuple):
    """The shared sort of one stream chunk: computed ONCE per chunk, consumed
    by every per-lane reduction (aggregate, bottom-k summary, merge).

    The key insight behind the single-sort ingest path: the permutation that
    sorts a chunk by key depends only on the keys, never on the per-lane
    payloads, so L lanes can share it.  ``ks = keys[perm]`` is ascending with
    EMPTY (int32 max) last; ``seg`` are its segment ids; ``ukeys`` the unique
    keys compacted to the front (ascending, EMPTY padded) — exactly what
    ``sort_by_key`` + ``segment_ids`` + ``scatter_unique`` produce, shared.

    ``eids``/``ws`` (optional) are the **pre-gathered view**: the chunk's
    element ids and weights already permuted into key order.  Element
    randomness depends only on the (key, eid) *values*, never on stream
    position, so scoring the pre-gathered view emits every per-element score
    already key-sorted — scoring is permutation-covariant,
    ``score(x[perm]) == score(x)[perm]`` bit for bit — and the downstream
    segment reductions need no per-lane gathers at all (the score-in-key-order
    ingest path; DESIGN.md §9).
    """

    ks: jax.Array     # [C] keys sorted ascending (stable; EMPTY last)
    perm: jax.Array   # [C] permutation: ks == keys[perm]
    seg: jax.Array    # [C] segment ids of ks (0..n_seg-1)
    ukeys: jax.Array  # [C] unique keys, ascending, EMPTY padded
    eids: jax.Array | None = None  # [C] element ids in key order (= eids[perm])
    ws: jax.Array | None = None    # [C] weights in key order (= weights[perm])


def chunk_order(keys, eids=None, weights=None, *,
                sort_backend: str | None = None) -> ChunkOrder:
    """Sort a chunk by key once; derive (permutation, segments, uniques).

    Pass ``eids``/``weights`` to also attach the pre-gathered (key-ordered)
    view — three O(C) gathers paid once per chunk, shared by every lane.

    ``sort_backend`` routes the shared key sort: ``'pallas'`` runs the
    block-local bitonic + cross-block merge kernel (kernels/chunksort),
    ``'xla'`` the stable argsort dual above, ``None`` (auto) picks pallas on
    backends with a compiled lowering (TPU/GPU) and XLA elsewhere.  Both
    routes are bit-identical (the kernel sorts (key, index) pairs
    lexicographically, which *is* the stable argsort), so the choice is pure
    perf routing.
    """
    if sort_backend not in (None, "xla", "pallas"):
        raise ValueError(
            f"unknown sort backend {sort_backend!r}: use None (auto), 'xla' "
            "or 'pallas'")
    if sort_backend is None:
        # auto: compiled sort route only where a real lowering exists; on
        # CPU the argsort dual needs no kernel import at all
        sort_backend = ("pallas" if jax.default_backend() in ("tpu", "gpu")
                        else "xla")
    if sort_backend == "pallas":
        # deferred import: kernels.chunksort imports this module for EMPTY
        from ..kernels.chunksort.ops import sort_with_perm

        ks, perm = sort_with_perm(keys, backend="pallas")
    else:
        ks, perm = stable_sort_with_perm(keys)
    seg, first = segment_ids(ks)
    # gather-form unique compaction: each segment's first element, compacted
    # to the front — bit-identical to ``scatter_unique(ks, seg, ...)`` (same
    # keys land on the same slots) without paying an XLA:CPU scatter
    (ukeys,) = compact_valid(first, ks, fills=(EMPTY,))
    return ChunkOrder(
        ks=ks, perm=perm, seg=seg, ukeys=ukeys,
        eids=None if eids is None else eids[perm],
        ws=None if weights is None else weights[perm],
    )


def merge_sorted_runs(a, b):
    """Positions of two sorted runs in their stable merged order.

    ``a`` and ``b`` must each be sorted ascending.  Returns ``(pos_a, pos_b)``
    — a permutation of ``0..len(a)+len(b)-1`` such that scattering ``a`` to
    ``pos_a`` and ``b`` to ``pos_b`` yields exactly the array a stable sort of
    ``concatenate([a, b])`` would produce (ties: all of ``a``'s entries before
    ``b``'s, internal order preserved).  Cost is two ``searchsorted`` passes —
    O((|a|+|b|) log) comparisons with tiny constants — instead of a full
    O(N log N) sort, which is the point: the sampler table is already sorted,
    so merging a C-sized chunk aggregate into it never re-sorts the table.
    """
    na, nb = a.shape[0], b.shape[0]
    pos_a = jnp.arange(na) + searchsorted(b, a, side="left")
    pos_b = jnp.arange(nb) + searchsorted(a, b, side="right")
    return pos_a, pos_b


def merge_sorted_runs_gather(a, b, out_len: int | None = None):
    """Gather-form of ``merge_sorted_runs``: per merged slot, which run and
    which index feeds it.

    Returns ``(from_b, ia, ib)`` with merged[p] = b[ib[p]] if from_b[p] else
    a[ia[p]] — the exact inverse of the scatter positions above, recovered
    with one extra ``searchsorted`` over the (strictly increasing) insertion
    positions of ``b``.  The point: applying a merge to many payload columns
    costs one cheap gather per column, where the scatter form pays a scatter
    per column — and XLA CPU executes gathers ~50x faster than scatters.

    ``out_len`` truncates the merged view to its first ``out_len`` positions
    (callers that immediately slice the merge — the fixed-capacity table and
    summary folds — skip building rank information for slots they drop).

    The inverse rank map (``nb_before``: how many b-slots land at or before
    each merged position) is a unit-scatter + cumsum rather than a second
    ``searchsorted``: the insertion positions are strictly increasing and
    unique, so marking them and prefix-summing yields exactly the same
    integers — and XLA:CPU runs the scatter+cumsum ~4x faster than a binary
    search whose queries are the full iota.

    These are XLA:CPU timings.  On the TPU, where XLA lowers element-wise
    dynamic indexing slowly, the table merge takes the sort form instead
    (``vectorized._merge_runs_by_sort``).
    """
    na, nb = a.shape[0], b.shape[0]
    m = na + nb if out_len is None else min(out_len, na + nb)
    pos_b = jnp.arange(nb) + searchsorted(a, b, side="right")
    # out-of-window positions pile onto the sacrificial slot m (sliced off);
    # clipped positions stay non-decreasing, so the scatter-add is sorted
    ind = jnp.zeros((m + 1,), jnp.int32).at[
        jnp.minimum(pos_b, m)].add(1, indices_are_sorted=True)[:m]
    nb_before = jnp.cumsum(ind)  # == count of pos_b <= p, bit for bit
    ib = jnp.clip(nb_before - 1, 0, nb - 1)
    from_b = ind > 0
    ia = jnp.clip(jnp.arange(m) - nb_before, 0, na - 1)
    return from_b, ia, ib


def searchsorted(a, v, side: str = "left"):
    """``jnp.searchsorted`` pinned to ``method='scan_unrolled'``.

    Identical indices to the default ``'scan'`` lowering — the method only
    picks the loop form — but the unrolled binary search avoids XLA:CPU's
    per-iteration while-loop thunk overhead (~20% on the rank passes that
    dominate the sorted-runs merges).  All hot-path rank computations go
    through here.  That is an XLA:CPU timing; on the TPU the table merge
    searches nothing (it takes the sort form,
    ``vectorized._merge_runs_by_sort``).
    """
    return jnp.searchsorted(a, v, side=side, method="scan_unrolled")


def kth_smallest(x, r):
    """Exact r-th smallest value (0-indexed; ``r`` may be traced) of a float32
    array — without sorting.

    XLA:CPU lowers a full f32 sort at ~250ns/element, which made order-
    statistic thresholds (the eviction tau*, the bottom-cap seed threshold)
    the single hottest primitive of the ingest step.  A threshold does not
    need a sort: map f32 to uint32 by the standard monotone total-order
    bijection (negatives bit-flipped, non-negatives sign-bit set) and build
    the r-th smallest key bit by bit — 32 branchless rounds of
    compare-and-count, each a vectorized reduction.  ~15x faster than the
    sort at 8k elements and exact: the returned bits are the element's own
    bits (ties share bits; no NaNs expected — -0.0/+0.0 straddles are the
    only bit ambiguity, and every caller compares, never hashes, the result).
    """
    u = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))
    res = jnp.uint32(0)
    for bit in range(31, -1, -1):
        cand = res | (jnp.uint32(1) << bit)
        cnt = jnp.sum((key < cand).astype(jnp.int32))
        res = jnp.where(cnt <= r, cand, res)
    back = jnp.where(res >> 31 == 1, res ^ jnp.uint32(0x80000000), ~res)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def segment_ids(sorted_keys):
    """Segment ids (0..n_seg-1) for a sorted key array; padding gets its own
    trailing segment(s)."""
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sorted_keys[1:] != sorted_keys[:-1]]
    )
    return jnp.cumsum(first) - 1, first


def scatter_unique(sorted_keys, seg, fill, values=None):
    """Place per-segment values at positions 0..n_seg-1 of a fixed-size array.

    Returns (unique_keys, value_array or None).  Slots past n_seg keep
    ``EMPTY`` / ``fill``.
    """
    n = sorted_keys.shape[0]
    ukeys = jnp.full((n,), EMPTY, dtype=sorted_keys.dtype).at[seg].set(sorted_keys)
    if values is None:
        return ukeys, None
    vals = jnp.full((n,), fill, dtype=values.dtype).at[seg].set(values)
    return ukeys, vals


def compact_valid(valid, *arrays, fills):
    """Move entries with valid=True to the front (stable), padding the rest.

    The source map (p-th output slot <- index of the (p+1)-th valid entry) is
    one unit int scatter: valid entry ``i`` owns output slot ``cs[i]-1``, and
    those slots are unique, so ``src.at[cs-1].set(i)`` builds the map
    directly — bit-identical to the historical ``searchsorted(cs, iota)``
    form (both compute the same stable ranks) but ~2x faster on XLA:CPU,
    where iota-query binary searches lower poorly.  Payload columns then pay
    one cheap gather each.  Order-preserving: compacting an ascending array
    yields an ascending array, which is what maintains the sorted-table
    invariant of core.vectorized.  The costs quoted are XLA:CPU's; on the
    TPU the table merge compacts by a sort instead
    (``vectorized._merge_runs_by_sort``), while the eviction still comes
    here.
    """
    n = valid.shape[0]
    cs = jnp.cumsum(valid)
    # invalid entries target the sacrificial slot n (sliced off), keeping
    # every target in-bounds — valid targets are unique by construction
    src = jnp.zeros((n + 1,), cs.dtype).at[
        jnp.where(valid, cs - 1, n)].set(jnp.arange(n))[:n]
    keep = jnp.arange(n) < cs[-1]
    out = []
    for a, fill in zip(arrays, fills):
        out.append(jnp.where(keep, a[src], jnp.asarray(fill, dtype=a.dtype)))
    return tuple(out)


def bottom_k_by(score, k, *arrays, fills):
    """Keep the k entries with smallest score; pad the rest.

    Returns (scores_k, arrays_k...).  Uses top_k on negated scores (TPU native).
    """
    neg = -score
    _, idx = jax.lax.top_k(neg, k)
    outs = [score[idx]]
    for a, fill in zip(arrays, fills):
        outs.append(a[idx])
    # entries with +inf score are padding
    validk = jnp.isfinite(outs[0])
    outs = [outs[0]] + [
        jnp.where(validk, a, jnp.asarray(fill, dtype=a.dtype))
        for a, fill in zip(outs[1:], fills)
    ]
    return tuple(outs)
