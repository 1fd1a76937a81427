"""Distributed stream sampling: the multi-pod story of the paper (§2, §3.1).

Mergeability is the paper's key systems property: bottom-k summaries of two
streams merge losslessly into the bottom-k summary of the union.  We map it
onto the mesh:

* every device runs the chunked sampler (core.vectorized) over its *stream
  shard* inside ``shard_map``, with shard-hashed element ids
  (``vectorized.shard_eids``) so randomness never aliases across shards;
* states merge with ``jax.lax`` collectives:
    - `all_gather` merge: one hop, O(P * k) state per device — right for
      small k, final extraction, and non-power-of-two axes;
    - butterfly merge via `ppermute`: log2(P) hops of bottom-k merges,
      O(k) live state — right for large k on power-of-two axes (other sizes
      fall back to all_gather automatically);
* pass 2 (exact weights of sampled keys) is a per-shard segment-sum followed
  by a `psum` — exactly the paper's 2-pass distributed scheme;
* ``make_distributed_two_pass_multi`` runs the whole l-grid in one program:
  chunks are scored once through the fused multi-l capscore kernel
  (kernels/capscore) and every lane reuses the element hashes.

Two cross-host merge families (contracts in DESIGN.md §5.2, regression
tests in tests/test_merge_bias.py):

* ``merge_bottomk`` / ``merge_bottomk_multi`` — lossless summary merges,
  exact for any element split (the service's exact mode);
* ``merge_fixed_k`` / ``merge_fixed_k_multi`` — 1-pass sketch merges,
  unbiased for key-partitioned shards, ~10% bias for element splits.

All functions are pure and shard_map-compatible; they are exercised on real
multi-device meshes in tests (subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count={3,6,8}) and in the
dry-run at 512 devices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .segments import (
    EMPTY,
    compact_valid,
    is_live,
    scatter_unique,
    searchsorted,
    segment_ids,
    sort_by_key,
)
from . import vectorized as VZ


# ---------------------------------------------------------------------------
# Mergeable bottom-k summaries
# ---------------------------------------------------------------------------


def merge_bottomk(keys_a, seeds_a, keys_b, seeds_b, k: int):
    """Merge two bottom-k (key, seed) summaries: min-seed per key, bottom-k.

    Lossless for bottom-k of the union (paper §3.1).
    """
    return VZ.merge_bottomk_summary(keys_a, seeds_a, keys_b, seeds_b, k)


def _lanewise_merge_bottomk(keys_a, seeds_a, keys_b, seeds_b, cap: int):
    """vmap of merge_bottomk over stacked lanes — the one definition shared
    by merge_bottomk_multi and both collective multi-lane mergers."""
    return jax.vmap(
        lambda ka, sa, kb, sb: merge_bottomk(ka, sa, kb, sb, cap)
    )(keys_a, seeds_a, keys_b, seeds_b)


@functools.partial(jax.jit, static_argnames=("cap",))
def merge_bottomk_multi(keys_a, seeds_a, keys_b, seeds_b, *, cap):
    """Lane-wise lossless min-merge of stacked bottom-cap summaries [L, cap] —
    the exact-mode multi-host path of stats.service.StreamStatsService."""
    return _lanewise_merge_bottomk(keys_a, seeds_a, keys_b, seeds_b, cap)


def tree_merge_bottomk(keys, seeds, k: int, axis_name: str):
    """Butterfly (recursive-halving) bottom-k merge across a mesh axis.

    log2(P) ppermute hops, each exchanging O(k) state: collective bytes
    O(k log P) per device versus O(k P) for the all_gather merge.

    The butterfly permutation ``i ^ stage`` is only a valid pairing when the
    axis size is a power of two; other sizes fall back to the one-hop
    all_gather merge (same result, O(k P) bytes).
    """
    size = jax.lax.axis_size(axis_name)
    if size & (size - 1):
        return allgather_merge_bottomk(keys, seeds, k, axis_name)
    stage = 1
    while stage < size:
        perm = [(i, i ^ stage) for i in range(size)]
        other_keys = jax.lax.ppermute(keys, axis_name, perm)
        other_seeds = jax.lax.ppermute(seeds, axis_name, perm)
        keys, seeds = merge_bottomk(keys, seeds, other_keys, other_seeds, k)
        stage *= 2
    return keys, seeds


def allgather_merge_bottomk(keys, seeds, k: int, axis_name: str):
    """One-hop merge: all_gather all summaries then local bottom-k."""
    all_keys = jax.lax.all_gather(keys, axis_name).reshape(-1)
    all_seeds = jax.lax.all_gather(seeds, axis_name).reshape(-1)
    # combine duplicates + bottom-k
    return merge_bottomk(
        all_keys, all_seeds,
        jnp.full((1,), EMPTY, all_keys.dtype), jnp.full((1,), jnp.inf, all_seeds.dtype),
        k,
    )


def tree_merge_bottomk_multi(keys, seeds, cap: int, axis_name: str):
    """Butterfly merge of stacked per-lane summaries ([L, cap] per device):
    each hop exchanges the whole stack once, then merges lane-wise locally.
    Non-power-of-two axes fall back to the all_gather merge."""
    size = jax.lax.axis_size(axis_name)
    if size & (size - 1):
        return allgather_merge_bottomk_multi(keys, seeds, cap, axis_name)
    stage = 1
    while stage < size:
        perm = [(i, i ^ stage) for i in range(size)]
        other_keys = jax.lax.ppermute(keys, axis_name, perm)
        other_seeds = jax.lax.ppermute(seeds, axis_name, perm)
        keys, seeds = _lanewise_merge_bottomk(keys, seeds, other_keys, other_seeds, cap)
        stage *= 2
    return keys, seeds


def allgather_merge_bottomk_multi(keys, seeds, cap: int, axis_name: str):
    """One-hop merge of stacked per-lane summaries [L, cap]."""
    L = keys.shape[0]
    all_keys = jnp.moveaxis(jax.lax.all_gather(keys, axis_name), 0, 1).reshape(L, -1)
    all_seeds = jnp.moveaxis(jax.lax.all_gather(seeds, axis_name), 0, 1).reshape(L, -1)
    empty_k = jnp.full((L, 1), EMPTY, keys.dtype)
    empty_s = jnp.full((L, 1), jnp.inf, seeds.dtype)
    return _lanewise_merge_bottomk(all_keys, all_seeds, empty_k, empty_s, cap)


# ---------------------------------------------------------------------------
# Mergeable fixed-k continuous states (1-pass sketches across hosts)
# ---------------------------------------------------------------------------


# reprolint: disable=RPL003 -- cross-host merge: both inputs may alias live
# resident states the caller keeps serving from (service.absorb merges into
# self.state); donating would invalidate them
@functools.partial(jax.jit, static_argnames=("k",))
def merge_fixed_k(table_a, table_b, l, salt, *, k):
    """Merge two per-host fixed-k continuous sampler states (core.vectorized
    ``TableState``) under a shared threshold.

    Procedure: union the tables; combine duplicate keys (counts add, KeyBase
    and seed min, plus one expected entry clip ``1/max(1/l, tau)`` per extra
    host — a key that entered on m hosts paid m entry-time clips while the
    continuous estimator corrects for exactly one); adopt the *lower*
    threshold; run one batched eviction round (§5.2 machinery) back down to
    <= k keys.  The result is a valid fixed-k state with ``table_a``'s
    capacity, so it can keep ingesting or merge again — pairwise folds give
    multi-host trees, the same topology as the bottom-k merges above.

    Accuracy contract (measured in tests/test_incremental.py): with
    **key-partitioned** shards (each key lives on one host — the natural
    sharding for user-keyed streams) merged estimates are unbiased within
    noise, like a single-stream run.  With arbitrary element-level splits,
    keys straddling hosts make the 1-pass merge inherently approximate
    (per-host entry events condition on per-host thresholds; cross-shard
    mass of unsampled keys is unrecoverable) — expect up to ~10% bias at
    k=512.  Use the 2-pass path (lossless bottom-k merge + exact pass-2
    weights) when cross-host exactness is required.
    """
    cap = table_a.keys.shape[0]
    tau = jnp.minimum(table_a.tau, table_b.tau)
    keys2 = jnp.concatenate([table_a.keys, table_b.keys])
    counts2 = jnp.concatenate([table_a.counts, table_b.counts])
    kb2 = jnp.concatenate([table_a.kb, table_b.kb])
    seed2 = jnp.concatenate([table_a.seed, table_b.seed])

    ks, (cn, kb, sd) = sort_by_key(keys2, counts2, kb2, seed2)
    seg, _ = segment_ids(ks)
    N = ks.shape[0]
    live = is_live(ks)
    cnt = jax.ops.segment_sum(jnp.where(live, cn, 0.0), seg, num_segments=N)
    dup = jax.ops.segment_sum(jnp.where(live, 1.0, 0.0), seg, num_segments=N)
    kbm = jax.ops.segment_min(jnp.where(live, kb, jnp.inf), seg, num_segments=N)
    sdm = jax.ops.segment_min(jnp.where(live, sd, jnp.inf), seg, num_segments=N)
    uk, _ = scatter_unique(ks, seg, 0.0)

    # duplicate-entry clip correction (m hosts -> m-1 extra clips)
    rate = jnp.maximum(1.0 / l, tau)
    cnt = cnt + jnp.maximum(dup - 1.0, 0.0) / rate
    uk_live = is_live(uk)
    cnt = jnp.where(uk_live, cnt, 0.0)
    kbm = jnp.where(uk_live, kbm, jnp.inf)
    sdm = jnp.where(uk_live, sdm, jnp.inf)

    # eviction randomness is hashed on the round counter: the merged state
    # stores this same round as its step so NO later per-chunk eviction can
    # reuse it (max(a,b)+1 would collide with a future round, replaying the
    # same ux/rx draws and correlating evictions)
    round_no = table_a.step + table_b.step + 1
    keys_e, counts_e, kb_e, seed_e, tau_e = VZ._evict_to_k(
        uk, cnt, kbm, sdm, tau, k, l, salt, round_no)

    # compact the <= k survivors back into table_a's capacity
    keys_c, counts_c, kb_c, seed_c = compact_valid(
        is_live(keys_e), keys_e, counts_e, kb_e, seed_e,
        fills=(EMPTY, 0.0, jnp.float32(jnp.inf), jnp.float32(jnp.inf)),
    )
    return VZ.TableState(
        keys=keys_c[:cap], counts=counts_c[:cap], kb=kb_c[:cap],
        seed=seed_c[:cap],
        tau=tau_e,
        step=round_no,
        overflow=table_a.overflow + table_b.overflow,
    )


def merge_fixed_k_states(tables, l, salt, *, k):
    """Fold a sequence of per-host fixed-k states into one (pairwise tree)."""
    tables = list(tables)
    if not tables:
        raise ValueError("no states to merge")
    while len(tables) > 1:
        nxt = [
            merge_fixed_k(tables[i], tables[i + 1], l, salt, k=k)
            if i + 1 < len(tables) else tables[i]
            for i in range(0, len(tables), 2)
        ]
        tables = nxt
    return tables[0]


# reprolint: disable=RPL003 -- cross-host merge, inputs alias live states
# (see merge_fixed_k)
@functools.partial(jax.jit, static_argnames=("k",))
def merge_fixed_k_multi(table_a, table_b, ls, salt, *, k):
    """Lane-wise merge of two stacked multi-l states (leading axis |ls|) —
    the multi-host path of stats.service.StreamStatsService."""
    return jax.vmap(
        lambda ta, tb, l: merge_fixed_k(ta, tb, l, salt, k=k),
        in_axes=(0, 0, 0),
    )(table_a, table_b, ls)


def merge_fixed_k_multi_states(tables, ls, salt, *, k, fold="left"):
    """Fold any subset of stacked multi-l states into one.

    The partial-merge surface of the sharded ingestion tier
    (stats.shardtier): the coordinator folds the *surviving* shards'
    states for degraded-mode queries — with key-partitioned shards every
    subset fold is itself an unbiased sketch of the covered key space.
    A single-element sequence folds to itself (no merge dispatch).

    ``fold="left"`` (default) is bit-compatible with a chain of pairwise
    merges — the fixed-k merge heuristic is order-sensitive, so the fold
    shape IS the answer's identity (MultiSampler.absorb_many relies on
    this to stay bit-equal to repeated ``absorb``); ``fold="tree"`` halves
    the critical path for genuinely parallel (mesh) folds at the cost of
    that compatibility."""
    tables = list(tables)
    if not tables:
        raise ValueError("no states to merge")
    if fold == "left":
        acc = tables[0]
        for t in tables[1:]:
            acc = merge_fixed_k_multi(acc, t, ls, salt, k=k)
        return acc
    if fold != "tree":
        raise ValueError(f"unknown fold {fold!r}")
    while len(tables) > 1:
        tables = [
            merge_fixed_k_multi(tables[i], tables[i + 1], ls, salt, k=k)
            if i + 1 < len(tables) else tables[i]
            for i in range(0, len(tables), 2)
        ]
    return tables[0]


def merge_bottomk_multi_states(summaries, *, cap):
    """Fold stacked per-lane bottom-cap summaries ``[(keys, seeds), ...]``
    into one pair — the exact-mode half of the tier's partial merge.
    Min-merge is associative and commutative, so (unlike the fixed-k fold
    above) the fold shape cannot change a bit of the result; the left fold
    keeps the dispatch sequence aligned with the table fold."""
    summaries = list(summaries)
    if not summaries:
        raise ValueError("no summaries to merge")
    ka, sa = summaries[0]
    for kb, sb in summaries[1:]:
        ka, sa = merge_bottomk_multi(ka, sa, kb, sb, cap=cap)
    return ka, sa


# ---------------------------------------------------------------------------
# Distributed 2-pass sampling (shard_map bodies)
# ---------------------------------------------------------------------------


def pass1_shard(keys_shard, weights_shard, *, kind, l, salt, k, chunk, axis_name, merge="tree"):
    """Per-device pass 1 over the local stream shard + cross-device merge.

    Element ids are disambiguated by hashing the shard index into the id
    (``vectorized.shard_eids``), so ids from different shards never alias —
    the previous ``shard_no * n`` arithmetic overflowed int32 once P*n > 2^31,
    silently correlating element randomness across shards.
    """
    shard_no = jax.lax.axis_index(axis_name)
    n = keys_shard.shape[0]
    n_chunks = n // chunk
    kshape = keys_shard.reshape(n_chunks, chunk)
    wshape = weights_shard.reshape(n_chunks, chunk)
    eids = VZ.shard_eids(shard_no, jnp.arange(n, dtype=jnp.int32)).reshape(n_chunks, chunk)

    cap = k + 1

    def body(carry, xs):
        skeys, sseeds = carry
        ck, cw, ce = xs
        uk, mins = VZ.chunk_bottomk_summary(ck, ce, cw, l, salt, kind=kind)
        return merge_bottomk(skeys, sseeds, uk, mins, cap), None

    init = (jnp.full((cap,), EMPTY, jnp.int32), jnp.full((cap,), jnp.inf, jnp.float32))
    # mark the carry as varying over the mesh axis (its value depends on the
    # shard's data from step 1 on)
    init = jax.lax.pcast(init, (axis_name,), to="varying")
    (skeys, sseeds), _ = jax.lax.scan(body, init, (kshape, wshape, eids))
    if merge == "tree":
        return tree_merge_bottomk(skeys, sseeds, cap, axis_name)
    return allgather_merge_bottomk(skeys, sseeds, cap, axis_name)


def pass2_shard(keys_shard, weights_shard, sampled_sorted, *, axis_name):
    """Per-device exact-weight accumulation + psum (paper pass II)."""
    kk = sampled_sorted.shape[0]
    loc = searchsorted(sampled_sorted, keys_shard)
    loc = jnp.clip(loc, 0, kk - 1)
    match = (sampled_sorted[loc] == keys_shard) & is_live(keys_shard)
    local = jnp.zeros((kk,), jnp.float32).at[loc].add(jnp.where(match, weights_shard, 0.0))
    return jax.lax.psum(local, axis_name)


def make_distributed_two_pass(mesh, *, kind, l, salt, k, chunk, axis_name="data", merge="tree"):
    """Build a jitted shard_map program computing the distributed 2-pass sample.

    Returns fn(keys [P*n], weights [P*n]) -> (sampled_keys [k+1], seeds [k+1],
    weights [k+1]) replicated.
    """
    def program(keys, weights):
        def shard_body(kshard, wshard):
            skeys, sseeds = pass1_shard(
                kshard.reshape(-1), wshard.reshape(-1),
                kind=kind, l=l, salt=salt, k=k, chunk=chunk,
                axis_name=axis_name, merge=merge,
            )
            # reprolint: disable=RPL002 -- sorts the [k+1] sampled summary once
            # per two-pass program, not per chunk; k+1 << stream length
            order = jnp.argsort(skeys)
            sorted_keys = skeys[order]
            w = pass2_shard(kshard.reshape(-1), wshard.reshape(-1), sorted_keys, axis_name=axis_name)
            return sorted_keys[None], sseeds[order][None], w[None]

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        )(keys, weights)

    return jax.jit(program)


# ---------------------------------------------------------------------------
# Multi-l distributed 2-pass: the whole l-grid in one program
# ---------------------------------------------------------------------------


def pass1_shard_multi(keys_shard, weights_shard, *, ls, salt, k, chunk,
                      axis_name, merge="tree"):
    """Per-device pass 1 for every l of a grid + cross-device lane-wise merge.

    Chunks are scored once through the fused multi-l capscore kernel
    (kernels/capscore; Pallas on TPU, lane-exact XLA reference elsewhere):
    the element hashes are computed once and every (l) lane reuses them, so
    the whole grid costs barely more than a single-l pass 1.  Element ids are
    shard-hashed (``vectorized.shard_eids``).  Returns ([L, k+1] keys,
    [L, k+1] seeds), the per-lane bottom-(k+1) summaries of the union.
    """
    from ..kernels.capscore.ops import capscore_multi

    shard_no = jax.lax.axis_index(axis_name)
    n = keys_shard.shape[0]
    n_chunks = n // chunk
    kshape = keys_shard.reshape(n_chunks, chunk)
    wshape = weights_shard.reshape(n_chunks, chunk)
    eids = VZ.shard_eids(shard_no, jnp.arange(n, dtype=jnp.int32)).reshape(n_chunks, chunk)

    ls = jnp.asarray(ls, jnp.float32)
    L = ls.shape[0]
    cap = k + 1
    # element scores don't depend on tau; feed inert thresholds to the kernel
    taus = jnp.full((L,), jnp.inf, jnp.float32)

    def body(carry, xs):
        ck, cw, ce = xs
        score, _, _, _ = capscore_multi(ck, ce, cw, ls, taus, salt)
        return VZ.pass1_step_multi(carry, ck, score, cap=cap), None

    init = (jnp.full((L, cap), EMPTY, jnp.int32),
            jnp.full((L, cap), jnp.inf, jnp.float32))
    init = jax.lax.pcast(init, (axis_name,), to="varying")
    (skeys, sseeds), _ = jax.lax.scan(body, init, (kshape, wshape, eids))
    if merge == "tree":
        return tree_merge_bottomk_multi(skeys, sseeds, cap, axis_name)
    return allgather_merge_bottomk_multi(skeys, sseeds, cap, axis_name)


def pass2_shard_multi(keys_shard, weights_shard, sampled_sorted, *, axis_name):
    """Per-device exact-weight accumulation for every lane + one psum.

    ``sampled_sorted``: [L, kk] per-lane sorted sampled keys (EMPTY-padded,
    EMPTY sorts last).  Returns [L, kk] exact weights, replicated.
    """
    def lane(ss):
        kk = ss.shape[0]
        loc = searchsorted(ss, keys_shard)
        loc = jnp.clip(loc, 0, kk - 1)
        match = (ss[loc] == keys_shard) & is_live(keys_shard)
        return jnp.zeros((kk,), jnp.float32).at[loc].add(
            jnp.where(match, weights_shard, 0.0))

    local = jax.vmap(lane)(sampled_sorted)
    return jax.lax.psum(local, axis_name)


def two_pass_multi_shard(kshard, wshard, *, ls, salt, k, chunk, axis_name,
                         merge="tree"):
    """One device's body of the multi-l distributed 2-pass program: pass 1
    over the local shard, the cross-device summary merge, and pass 2 + psum.
    Returns ([L, k+1] sorted keys, their seeds, their exact weights), equal
    on every device of the axis."""
    skeys, sseeds = pass1_shard_multi(
        kshard, wshard, ls=ls, salt=salt, k=k, chunk=chunk,
        axis_name=axis_name, merge=merge)
    # reprolint: disable=RPL002 -- sorts the [L, k+1] sampled summary
    # once per two-pass program, not per chunk
    order = jnp.argsort(skeys, axis=1)
    sorted_keys = jnp.take_along_axis(skeys, order, axis=1)
    sorted_seeds = jnp.take_along_axis(sseeds, order, axis=1)
    w = pass2_shard_multi(kshard, wshard, sorted_keys, axis_name=axis_name)
    return sorted_keys, sorted_seeds, w


def make_distributed_two_pass_multi(mesh, *, ls, salt, k, chunk,
                                    axis_name="data", merge="tree"):
    """Build a jitted shard_map program computing the exact distributed
    2-pass sample for EVERY l of the grid in one launch.

    Returns fn(keys [P*n], weights [P*n]) -> (sampled_keys [P, L, k+1],
    seeds [P, L, k+1], weights [P, L, k+1]), one identical replica per
    device; per lane, keys are sorted ascending (EMPTY-padded) with their
    seeds and exact pass-2 weights.
    """
    def program(keys, weights):
        def shard_body(kshard, wshard):
            out = two_pass_multi_shard(
                kshard.reshape(-1), wshard.reshape(-1), ls=ls, salt=salt,
                k=k, chunk=chunk, axis_name=axis_name, merge=merge)
            return tuple(a[None] for a in out)

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        )(keys, weights)

    return jax.jit(program)
