"""Incremental sampler state API: the streaming face of core.vectorized.

The chunked samplers were born as ``lax.scan`` loops over a fully
materialized stream.  This module exposes the *same* per-chunk step
functions as an explicit state machine so long-lived services ingest a
stream piece by piece with O(k) resident state and zero recompute:

    state = init_state(l=20.0, k=4096, chunk=2048)
    state = update(state, key_chunk, weight_chunk)      # one jitted dispatch
    ...
    result = finalize(state)                            # SampleResult

Contracts (verified in tests/test_incremental.py):

* **Same function, same bits.**  ``update`` applies exactly the step the
  one-shot scan applies (``vectorized.fixed_tau_step`` / ``fixed_k_step``),
  with element ids continuing from ``state.n_seen``.  Feeding a stream
  through ``update`` in chunk-aligned pieces and finalizing reproduces the
  one-shot sampler on the concatenated stream **element-exactly** (fixed
  threshold) / identically per lane (fixed-k, same chunk boundaries).
* **Donated buffers.**  The update jits donate the incoming state pytree, so
  steady-state ingestion performs no state copies; never reuse a state you
  passed to ``update`` — use its return value.
* **Multi-l in one dispatch.**  ``init_multi_state`` stacks one fixed-k
  continuous sketch per l of a grid (leading axis |ls|); ``update_multi``
  advances *all* of them per batch in a single device dispatch: the fused
  multi-l capscore kernel (kernels/capscore) scores every lane in one
  VMEM-resident pass over the elements, then the merge/evict step runs
  vmapped across lanes.
* **O(k) checkpoints.**  A state is a flat pytree of fixed-size arrays —
  serialize it with ``jax.tree`` utilities or checkpoint.manager; size is
  independent of how many elements were observed.

Unaligned batches (sizes not a multiple of ``chunk``) are the caller's
concern by design — the pure functions stay shape-static for jit.  The
``IncrementalSampler`` / ``MultiSampler`` wrappers below carry the O(chunk)
host-side remainder buffer and do the padding at finalize, mirroring the
one-shot samplers' end-of-stream padding so exactness is preserved.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels.capscore.ops import capscore_agg, capscore_multi
from .samplers import SampleResult
from . import segments as SG
from .segments import EMPTY, chunk_order, normalize_keys  # noqa: F401 (re-export)
from . import vectorized as VZ
from .x64 import x64_scope

_EMPTY_INT = int(EMPTY)

# normalize_keys lives in core.segments now (so the one-shot samplers'
# ``vectorized._prep`` shares it without an import cycle); re-exported here
# because this module was its historical home.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SamplerState:
    """Streaming sampler state: the scan carry, liberated from the scan.

    ``table`` leaves are [capacity] for a single sketch or [L, capacity] for
    a stacked multi-l state; ``l`` is scalar or [L] to match; ``n_seen`` is
    the stream position (it seeds element ids, shared by all lanes).

    ``bk_keys``/``bk_seeds`` (multi-l states only, else None) carry the
    *lossless* per-lane bottom-(k+1) (key, min element score) summary of
    everything observed — the coordinated-randomness handle that makes
    cross-host merges exact (paper §3.1; core.distributed.merge_bottomk_multi
    + the service reconcile pass).
    """

    table: VZ.TableState
    n_seen: jax.Array   # int32 scalar: elements consumed so far
    l: jax.Array        # float32: cap parameter(s)
    salt: jax.Array     # uint32 scalar
    bk_keys: jax.Array | None = None   # [L, k+1] int32 bottom-k summary keys
    bk_seeds: jax.Array | None = None  # [L, k+1] f32 per-key min element score

    def tree_flatten(self):
        return (self.table, self.n_seen, self.l, self.salt,
                self.bk_keys, self.bk_seeds), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.table.keys.shape[-1]


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Static (compile-time) configuration of an incremental sampler.

    ``host_id`` disambiguates element randomness across hosts that ingest
    disjoint shards of one logical stream: ids become
    ``hash(SALT_SHARD, host_id, position)`` (vectorized.shard_eids) instead
    of the raw position, so no two hosts ever share an element's randomness —
    the precondition for both merge modes of stats.service.  ``None`` (the
    default) keeps raw positions, preserving bit-exact equivalence with the
    one-shot samplers.

    ``evict_every`` (fixed-k only) amortizes the batched eviction: the table
    capacity grows to ``k + evict_every * chunk`` and the eviction pass runs
    only every ``evict_every``-th chunk, so steady-state chunks pay merge
    cost alone.  E=1 (default) is bit-compatible with the one-shot samplers;
    E>1 changes the eviction randomness *schedule* — the sample stays a valid
    fixed-k SH_l sample (count law / unbiasedness are Monte-Carlo validated
    in tests/test_ingest_order.py) but is no longer per-run identical to E=1.

    ``backend`` routes the fused score+aggregate stage of the multi-l update
    (kernels.capscore.ops.capscore_agg): None auto-picks per detected
    accelerator (compiled Pallas on TPU/GPU, XLA elsewhere); 'xla' | 'pallas'
    force a path.  The XLA path is bit-identical to the reference pipeline;
    Pallas reassociates the f32 segment sums in-block (see the kernel).

    ``sort_backend`` routes the shared chunk-order key sort
    (segments.chunk_order): 'pallas' selects the block-local bitonic +
    cross-block merge kernel (kernels.chunksort), 'xla' the stable argsort
    dual.  ``None`` (default) follows ``backend``, so a single knob moves
    the whole chunk step; set it separately to mix routes — both sort routes
    are bit-identical, so this is pure perf routing.
    """

    kind: str = "continuous"
    k: int | None = None          # fixed-k mode when set, else fixed-tau
    chunk: int = 2048
    host_id: int | None = None    # element-id namespace for multi-host runs
    evict_every: int = 1          # fixed-k eviction period E (chunks)
    backend: str | None = None    # capscore_agg dispatch: None|'xla'|'pallas'
    sort_backend: str | None = None  # chunk_order sort; None: follow backend

    @property
    def mode(self) -> str:
        return "fixed_k" if self.k is not None else "fixed_tau"

    @property
    def sort_route(self) -> str | None:
        """Effective chunk_order sort backend (sort_backend, else backend)."""
        return self.sort_backend if self.sort_backend is not None else self.backend

    def eids(self, pos):
        """Element ids for one chunk starting at stream position ``pos``."""
        base = pos + jnp.arange(self.chunk, dtype=jnp.int32)
        if self.host_id is None:
            return base
        return VZ.shard_eids(jnp.uint32(self.host_id), base)


def init_state(l, *, k=None, tau=None, kind="continuous", chunk=2048,
               capacity=8192, salt=0, evict_every=1) -> tuple[SamplerState, SamplerSpec]:
    """Fresh O(k)/O(capacity) sampler state + its static spec.

    Fixed-k (``k`` set): capacity is k + evict_every*chunk so the merges of a
    whole eviction period never overflow before the scheduled eviction (only
    ``kind="continuous"`` supports one-pass fixed-k, as in the one-shot
    sampler).  Fixed-tau (``tau`` set): table of ``capacity`` slots, overflow
    counted and raised at finalize.
    """
    if (k is None) == (tau is None):
        raise ValueError("exactly one of k= / tau= must be given")
    if evict_every < 1:
        raise ValueError(f"evict_every must be >= 1, got {evict_every}")
    if k is not None:
        if kind != "continuous":
            raise ValueError("one-pass fixed-k requires kind='continuous'")
        table = VZ.init_table(k + evict_every * chunk)
    else:
        if evict_every != 1:
            raise ValueError("evict_every applies to fixed-k samplers only")
        table = VZ.init_table(capacity, tau)
    state = SamplerState(
        table=table,
        n_seen=jnp.int32(0),
        l=jnp.float32(l),
        salt=jnp.asarray(salt, jnp.uint32),
    )
    return state, SamplerSpec(kind=kind, k=k, chunk=chunk, evict_every=evict_every)


def _scheduled_evict(table, spec: SamplerSpec, evict_fn):
    """Run ``evict_fn`` on the merged table at the spec's eviction cadence.

    E=1 calls it unconditionally (bit-compatible fast path, no cond); E>1
    evicts only when the chunk counter hits a multiple of E — the lazy
    partition-based schedule.  ``table.step`` may be scalar or [L] (all lanes
    advance in lockstep, so lane 0 decides)."""
    if spec.evict_every == 1:
        return evict_fn(table)
    step = table.step if table.step.ndim == 0 else table.step[0]
    return jax.lax.cond(step % spec.evict_every == 0, evict_fn,
                        lambda t: t, table)


def _update_impl(state: SamplerState, keys, weights, spec: SamplerSpec) -> SamplerState:
    chunk = spec.chunk
    n = keys.shape[0]
    if n % chunk:
        raise ValueError(f"update batch ({n}) must be a multiple of chunk ({chunk})")
    kc = keys.reshape(n // chunk, chunk)
    wc = weights.reshape(n // chunk, chunk)
    max_evict = spec.evict_every * chunk

    def body(carry, xs):
        table, pos = carry
        ck, cw = xs
        eids = spec.eids(pos)
        if spec.mode == "fixed_k":
            # pre-gathered view: score in key order, reduce in the same pass
            order = chunk_order(ck, eids, cw, sort_backend=spec.sort_route)
            agg = VZ.aggregate_continuous(ck, cw, eids, table.tau, state.l,
                                          state.salt, order)
            table = _scheduled_evict(
                VZ.fixed_k_merge(table, agg), spec,
                lambda t: VZ.evict_table(t, k=spec.k, l=state.l,
                                         salt=state.salt, max_evict=max_evict))
        else:
            table = VZ.fixed_tau_step(table, ck, cw, eids, state.l, state.salt,
                                      kind=spec.kind)
        return (table, pos + chunk), None

    (table, pos), _ = jax.lax.scan(body, (state.table, state.n_seen), (kc, wc))
    return SamplerState(table, pos, state.l, state.salt)


_update_donated = functools.partial(jax.jit, static_argnames=("spec",),
                                    donate_argnums=(0,))(_update_impl)
# reprolint: disable=RPL003 -- the flush path (lazy finalize) must keep the
# input state alive and usable after the call; donation would invalidate it
_update_fresh = functools.partial(jax.jit, static_argnames=("spec",))(_update_impl)


def update(state: SamplerState, keys, weights, spec: SamplerSpec, *,
           donate: bool = True) -> SamplerState:
    """Advance the sampler over a chunk-aligned batch in one jitted dispatch.

    With ``donate=True`` (default) the input state's buffers are donated to
    the output — do not touch ``state`` afterwards.  ``donate=False`` leaves
    the input intact (the lazy-finalize flush path).
    """
    fn = _update_donated if donate else _update_fresh
    return fn(state, jnp.asarray(keys), jnp.asarray(weights), spec)


# reprolint: disable=RPL003 -- non-destructive projection: finalize must leave
# the resident table intact so the sampler keeps ingesting after extraction
@functools.partial(jax.jit, static_argnames=("spec",))
def _final_evict(table, l, salt, spec: SamplerSpec):
    """Project a lazily-evicted table down to <= k for extraction.

    With ``evict_every > 1`` the resident table may hold up to
    ``k + E*chunk`` keys between scheduled evictions; finalize runs one
    (non-persisted) eviction round at the current step so the extracted
    sample is a valid fixed-k sample.  Deterministic in the state, so
    repeated finalize calls agree; no-op whenever the table is <= k."""
    return VZ.evict_table(table, k=spec.k, l=l, salt=salt,
                          max_evict=spec.evict_every * spec.chunk)


# reprolint: disable=RPL003 -- non-destructive projection (see _final_evict)
@functools.partial(jax.jit, static_argnames=("spec",))
def _final_evict_multi(table, ls, salt, spec: SamplerSpec):
    return jax.vmap(
        lambda t, l: VZ.evict_table(t, k=spec.k, l=l, salt=salt,
                                    max_evict=spec.evict_every * spec.chunk)
    )(table, ls)


def finalize(state: SamplerState, spec: SamplerSpec) -> SampleResult:
    """Extract the SampleResult; the state remains usable for more updates."""
    st = state.table
    overflow = int(jax.device_get(st.overflow))
    if overflow > 0:
        raise RuntimeError(
            f"fixed-tau capacity overflow ({overflow}); raise capacity")
    if spec.mode == "fixed_k" and spec.evict_every > 1:
        st = _final_evict(st, state.l, state.salt, spec)
    l_host, tau_host = jax.device_get((state.l, st.tau))
    return VZ._to_result(st, l=float(l_host), kind=spec.kind, tau=float(tau_host))


# ---------------------------------------------------------------------------
# Stacked multi-l state: every sketch of an l-grid advances per dispatch
# ---------------------------------------------------------------------------


def init_multi_state(ls, *, k, chunk=2048, salt=0, host_id=None,
                     evict_every=1, backend=None,
                     sort_backend=None) -> tuple[SamplerState, SamplerSpec]:
    """One fixed-k continuous sketch per l, stacked on a leading axis, plus a
    lossless per-lane bottom-(k+1) summary for exact cross-host merging.

    ``evict_every=E`` opts into amortized eviction: capacity k + E*chunk,
    eviction every E chunks (see SamplerSpec; E=1 is bit-compatible with
    the one-shot samplers).  ``backend`` routes the fused score+aggregate
    stage and ``sort_backend`` the shared chunk-order sort (see
    SamplerSpec)."""
    if evict_every < 1:
        raise ValueError(f"evict_every must be >= 1, got {evict_every}")
    ls = np.asarray(ls, np.float32)
    L = len(ls)
    capacity = k + evict_every * chunk
    table = VZ.TableState(
        keys=jnp.full((L, capacity), EMPTY, dtype=jnp.int32),
        counts=jnp.zeros((L, capacity), jnp.float32),
        kb=jnp.full((L, capacity), jnp.inf, jnp.float32),
        seed=jnp.full((L, capacity), jnp.inf, jnp.float32),
        tau=jnp.full((L,), jnp.inf, jnp.float32),
        step=jnp.zeros((L,), jnp.int32),
        overflow=jnp.zeros((L,), jnp.int32),
    )
    state = SamplerState(
        table=table,
        n_seen=jnp.int32(0),
        l=jnp.asarray(ls),
        salt=jnp.asarray(salt, jnp.uint32),
        bk_keys=jnp.full((L, k + 1), EMPTY, dtype=jnp.int32),
        bk_seeds=jnp.full((L, k + 1), jnp.inf, jnp.float32),
    )
    return state, SamplerSpec(kind="continuous", k=k, chunk=chunk,
                              host_id=host_id, evict_every=evict_every,
                              backend=backend, sort_backend=sort_backend)


def _multi_chunk_step(table, bk_keys, bk_seeds, pos, ck, cw, l, salt,
                      spec: SamplerSpec):
    """One chunk through the fused multi-l step (summaries carried KEY-sorted).

    The shared body of ``_update_multi_impl``'s scan and the per-tenant vmap
    of ``_update_bank_impl``:

    1. **Permute once**: the chunk is sorted by key exactly once
       (``chunk_order``), WITH the pre-gathered (eids, weights) view — the
       only gathers of the whole step.
    2. **Score in key order, reduce in the same pass**: ``capscore_agg``
       scores every l lane on the pre-gathered view (element randomness
       hangs off (key, eid) values, so scoring is permutation-covariant) and
       segment-reduces the scores into the per-unique-key ChunkAgg columns
       [L, C] directly — the [L, N] score/delta/entry/kb intermediates never
       exist as arrays between stages, and the lane-independent ``w_total``
       is computed once instead of L times.
    3. The per-lane sorted-runs table merges consume the already-key-sorted
       aggregate columns; eviction runs on the spec's cadence with a
       backend-fastest threshold selection.
    4. The aggregate's ``min_score`` column IS the pass-1 chunk summary
       (element scores are tau-independent), so the lossless bottom-(k+1)
       summaries advance with no re-scoring and no reorder — on a KEY-sorted
       carry (``pass1_fold_keysorted``: searchsorted/gather/value-sort, no
       argsort, no TopK, no segment scatters), converted to/from the
       seed-sorted state layout once per batch at the scan boundary.
    """
    cap_bk = bk_keys.shape[-1]
    max_evict = spec.evict_every * spec.chunk
    with obs.stage("chunk_sort"):
        eids = spec.eids(pos)
        # the ONE chunk sort, with the pre-gathered view for ordered scoring
        order = chunk_order(ck, eids, cw, sort_backend=spec.sort_route)
    with obs.stage("score"):
        # fused: score every l lane AND reduce to per-key columns in one pass
        w_total, entered, contrib, kb_min, min_score = capscore_agg(
            order.ks, order.eids, order.ws, order.seg, l, table.tau,
            salt, backend=spec.backend)

    def lane_merge(tab, en, ct, kbm, ms):
        # l is already baked into the per-lane aggregate columns; the
        # merge itself is l-independent (w_total/ukeys shared by closure)
        agg = VZ.ChunkAgg(ukeys=order.ukeys, w_total=w_total, entered=en,
                          contrib=ct, kb=kbm, min_score=ms)
        return VZ.fixed_k_merge(tab, agg)

    with obs.stage("merge"):
        table = jax.vmap(lane_merge)(table, entered, contrib, kb_min,
                                     min_score)
    with obs.stage("evict"):
        table = _scheduled_evict(
            table, spec,
            lambda t: jax.vmap(
                lambda tab, ll: VZ.evict_table(tab, k=spec.k, l=ll,
                                               salt=salt, max_evict=max_evict)
            )(t, l))
    with obs.stage("fold"):
        # min_score doubles as the (already key-ordered) pass-1 chunk
        # summary; the key-sorted carry folds it in sort-free
        bk_keys, bk_seeds = jax.vmap(
            lambda sk, ss, mn: VZ.pass1_fold_keysorted(sk, ss, order.ukeys,
                                                       mn, cap_bk)
        )(bk_keys, bk_seeds, min_score)
    return table, bk_keys, bk_seeds, pos + spec.chunk


def _update_multi_impl(state: SamplerState, keys, weights, spec: SamplerSpec) -> SamplerState:
    """The permute-once / score-ordered / reduce-fused multi-l batch update:
    a scan of ``_multi_chunk_step`` with the bottom-(k+1) summaries converted
    to/from the key-sorted carry layout once per batch at the scan boundary.

    Bit-identical per lane to the pre-restructure path
    (``_update_multi_reference_impl``) at evict_every=1 — tables, taus, AND
    summaries (tests/test_ingest_order.py).
    """
    chunk = spec.chunk
    n = keys.shape[0]
    if n % chunk:
        raise ValueError(f"update batch ({n}) must be a multiple of chunk ({chunk})")
    kc = keys.reshape(n // chunk, chunk)
    wc = weights.reshape(n // chunk, chunk)

    cap_bk = state.bk_keys.shape[1]
    with obs.stage("to_keysorted"):
        bkk0, bks0 = jax.vmap(VZ.summary_to_keysorted)(state.bk_keys,
                                                       state.bk_seeds)

    def body(carry, xs):
        table, bk_keys, bk_seeds, pos = carry
        ck, cw = xs
        table, bk_keys, bk_seeds, pos = _multi_chunk_step(
            table, bk_keys, bk_seeds, pos, ck, cw, state.l, state.salt, spec)
        return (table, bk_keys, bk_seeds, pos), None

    (table, bkk, bks, pos), _ = jax.lax.scan(
        body, (state.table, bkk0, bks0, state.n_seen), (kc, wc))
    with obs.stage("from_keysorted"):
        bk_keys, bk_seeds = jax.vmap(
            lambda kk, ss: VZ.summary_from_keysorted(kk, ss, cap_bk))(bkk, bks)
    return SamplerState(table, pos, state.l, state.salt, bk_keys, bk_seeds)


def _update_multi_reference_impl(state: SamplerState, keys, weights,
                                 spec: SamplerSpec) -> SamplerState:
    """The pre-PR multi-l chunk step, verbatim: every lane re-sorts the chunk
    inside its aggregate, re-sorts the whole table in its merge, and
    full-sorts the eviction race; the summary advance sorts the chunk once
    more.  L+1 chunk sorts + L table sorts per chunk.  Kept as the
    bit-identity oracle (tests/test_ingest_order.py) and the baseline of
    benchmarks/sampler_throughput.py — supports evict_every=1 only."""
    if spec.evict_every != 1:
        raise ValueError("reference path supports evict_every=1 only")
    chunk = spec.chunk
    n = keys.shape[0]
    if n % chunk:
        raise ValueError(f"update batch ({n}) must be a multiple of chunk ({chunk})")
    kc = keys.reshape(n // chunk, chunk)
    wc = weights.reshape(n // chunk, chunk)

    def lane_step(table, ck, cw, score, delta, entry, kb, l):
        return VZ.fixed_k_step_scored_ref(table, ck, cw, score, delta, entry, kb,
                                          k=spec.k, l=l, salt=state.salt)

    vstep = jax.vmap(lane_step, in_axes=(0, None, None, 0, 0, 0, 0, 0))

    cap_bk = state.bk_keys.shape[1]

    def body(carry, xs):
        table, bk_keys, bk_seeds, pos = carry
        ck, cw = xs
        eids = spec.eids(pos)
        # spec.backend keeps the oracle's scoring on the same kernel route as
        # the fused path per bench leg; capscore_multi is elementwise, so the
        # routes are bit-identical and the oracle's answers never move
        score, delta, entry, kb = capscore_multi(ck, eids, cw, state.l, table.tau,
                                                 state.salt, backend=spec.backend)
        table = vstep(table, ck, cw, score, delta, entry, kb, state.l)
        bk_keys, bk_seeds = VZ.pass1_step_multi(
            (bk_keys, bk_seeds), ck, score, cap=cap_bk)
        return (table, bk_keys, bk_seeds, pos + chunk), None

    (table, bk_keys, bk_seeds, pos), _ = jax.lax.scan(
        body, (state.table, state.bk_keys, state.bk_seeds, state.n_seen), (kc, wc))
    return SamplerState(table, pos, state.l, state.salt, bk_keys, bk_seeds)


_update_multi_donated = functools.partial(jax.jit, static_argnames=("spec",),
                                          donate_argnums=(0,))(_update_multi_impl)
# reprolint: disable=RPL003 -- flush path: input state must survive the call
_update_multi_fresh = functools.partial(jax.jit, static_argnames=("spec",))(_update_multi_impl)
_update_multi_ref_donated = functools.partial(
    jax.jit, static_argnames=("spec",), donate_argnums=(0,))(_update_multi_reference_impl)
# reprolint: disable=RPL003 -- flush path: input state must survive the call
_update_multi_ref_fresh = functools.partial(
    jax.jit, static_argnames=("spec",))(_update_multi_reference_impl)


def update_multi(state: SamplerState, keys, weights, spec: SamplerSpec, *,
                 donate: bool = True, reference: bool = False) -> SamplerState:
    """Advance every l-lane sketch over a chunk-aligned batch: one dispatch.

    ``reference=True`` routes through the pre-single-sort step (bit-identical
    results at evict_every=1, strictly slower) — benchmarking/testing only.
    """
    args = (state, jnp.asarray(keys), jnp.asarray(weights), spec)
    if reference:
        fn = _update_multi_ref_donated if donate else _update_multi_ref_fresh
        return fn(*args)
    if donate:
        return obs.dispatch("update_multi", _update_multi_donated, *args)
    return _update_multi_fresh(*args)


def finalize_multi(state: SamplerState, spec: SamplerSpec,
                   ls=None) -> dict[float, SampleResult]:
    """Per-lane SampleResults, keyed by l (host-side extraction).

    ``ls`` supplies the dict keys (the caller's original, full-precision l
    values); defaults to the f32 lane values stored in the state.  Pass the
    configured grid so lookups like ``results[3.3]`` don't miss on f32
    rounding.
    """
    table = state.table
    if spec.evict_every > 1:
        table = _final_evict_multi(table, state.l, state.salt, spec)
    tables = jax.device_get(table)
    if ls is None:
        ls = np.asarray(state.l)
    out = {}
    for j, l in enumerate(ls):
        st = jax.tree.map(lambda a: a[j], tables)
        out[float(l)] = VZ._to_result(st, l=float(l), kind=spec.kind,
                                      tau=float(st.tau))
    return out


# ---------------------------------------------------------------------------
# Stacked tenant banks: N resident sampler instances (tenant x l-grid) in one
# pytree, all advanced by a single vmapped/jitted dispatch per ingest tick —
# the multi-tenant analogue of the multi-l lane stacking above.
# ---------------------------------------------------------------------------


def init_bank_state(ls, *, n_tenants, k, chunk=2048, salts=0, host_id=None,
                    evict_every=1, backend=None,
                    sort_backend=None) -> tuple[SamplerState, SamplerSpec]:
    """A stacked bank of ``n_tenants`` independent multi-l sampler instances.

    Leaves gain a leading tenant axis: table leaves are [T, L, capacity],
    summaries [T, L, k+1], ``n_seen`` [T] (every tenant is its own stream
    with its own element-id positions), ``salt`` [T] (``salts`` may be one
    int shared by all tenants or a per-tenant sequence — per-tenant salts
    decorrelate the tenants' key randomness, shared salts keep each tenant
    bit-identical to a standalone sampler built with that salt).  ``l`` stays
    [L]: the grid is shared bank-wide (static shapes are what make the one
    stacked dispatch possible).
    """
    if evict_every < 1:
        raise ValueError(f"evict_every must be >= 1, got {evict_every}")
    if n_tenants < 1:
        raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
    ls = np.asarray(ls, np.float32)
    T, L = int(n_tenants), len(ls)
    salts_arr = np.broadcast_to(np.asarray(salts, np.uint32), (T,)).copy()
    capacity = k + evict_every * chunk
    table = VZ.TableState(
        keys=jnp.full((T, L, capacity), EMPTY, dtype=jnp.int32),
        counts=jnp.zeros((T, L, capacity), jnp.float32),
        kb=jnp.full((T, L, capacity), jnp.inf, jnp.float32),
        seed=jnp.full((T, L, capacity), jnp.inf, jnp.float32),
        tau=jnp.full((T, L), jnp.inf, jnp.float32),
        step=jnp.zeros((T, L), jnp.int32),
        overflow=jnp.zeros((T, L), jnp.int32),
    )
    state = SamplerState(
        table=table,
        n_seen=jnp.zeros((T,), jnp.int32),
        l=jnp.asarray(ls),
        salt=jnp.asarray(salts_arr),
        bk_keys=jnp.full((T, L, k + 1), EMPTY, dtype=jnp.int32),
        bk_seeds=jnp.full((T, L, k + 1), jnp.inf, jnp.float32),
    )
    return state, SamplerSpec(kind="continuous", k=k, chunk=chunk,
                              host_id=host_id, evict_every=evict_every,
                              backend=backend, sort_backend=sort_backend)


def _mask_tenants(active, new, old):
    """Per-leaf select: tenants with ``active[t]`` take the updated leaf row,
    the rest keep their previous state bit-for-bit (their dispatch lane ran
    on an EMPTY padding chunk whose results are discarded here)."""
    sel = lambda n, o: jnp.where(
        active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(sel, new, old)


def _update_bank_impl(state: SamplerState, keys, weights, active,
                      spec: SamplerSpec) -> SamplerState:
    """One bank tick: ONE chunk per tenant, every tenant's L lanes advanced by
    a single vmapped dispatch of the fused multi-l chunk step.

    ``keys``/``weights`` are [T, chunk] (EMPTY/0 rows for inactive tenants),
    ``active`` is a [T] bool mask.  Inactive tenants' lanes still flow through
    the vmapped compute (static shapes) but their state — table, summaries
    AND stream position — passes through unchanged, so a tenant's trajectory
    depends only on ITS chunk sequence: each tenant stays bit-identical to a
    standalone ``MultiSampler`` fed the same chunks (property-tested in
    tests/test_serving.py).
    """
    cap_bk = state.bk_keys.shape[-1]
    with obs.stage("to_keysorted"):
        bkk0, bks0 = jax.vmap(jax.vmap(VZ.summary_to_keysorted))(
            state.bk_keys, state.bk_seeds)

    def tenant_step(table, bkk, bks, pos, ck, cw, salt):
        return _multi_chunk_step(table, bkk, bks, pos, ck, cw, state.l, salt,
                                 spec)

    table, bkk, bks, pos = jax.vmap(tenant_step)(
        state.table, bkk0, bks0, state.n_seen, keys, weights, state.salt)
    with obs.stage("from_keysorted"):
        bk_keys, bk_seeds = jax.vmap(jax.vmap(
            lambda kk, ss: VZ.summary_from_keysorted(kk, ss, cap_bk)))(bkk,
                                                                        bks)

    with obs.stage("mask_tenants"):
        table = _mask_tenants(active, table, state.table)
        bk_keys = _mask_tenants(active, bk_keys, state.bk_keys)
        bk_seeds = _mask_tenants(active, bk_seeds, state.bk_seeds)
        pos = jnp.where(active, pos, state.n_seen)
    return SamplerState(table, pos, state.l, state.salt, bk_keys, bk_seeds)


_update_bank_donated = functools.partial(jax.jit, static_argnames=("spec",),
                                         donate_argnums=(0,))(_update_bank_impl)
# reprolint: disable=RPL003 -- flush path: input state must survive the call
_update_bank_fresh = functools.partial(jax.jit, static_argnames=("spec",))(_update_bank_impl)


def update_bank(state: SamplerState, keys, weights, active, spec: SamplerSpec,
                *, donate: bool = True) -> SamplerState:
    """Advance every active tenant's l-grid by one chunk: one device dispatch
    for the whole bank.  Same donation contract as ``update``/``update_multi``.
    """
    args = (state, jnp.asarray(keys), jnp.asarray(weights),
            jnp.asarray(active), spec)
    if donate:
        return obs.dispatch("update_bank", _update_bank_donated, *args)
    return _update_bank_fresh(*args)


# reprolint: disable=RPL003 -- non-destructive projection (see _final_evict)
@functools.partial(jax.jit, static_argnames=("spec",))
def _final_evict_bank(table, ls, salts, spec: SamplerSpec):
    return jax.vmap(lambda t, s: jax.vmap(
        lambda tab, l: VZ.evict_table(tab, k=spec.k, l=l, salt=s,
                                      max_evict=spec.evict_every * spec.chunk)
    )(t, ls))(table, salts)


# ---------------------------------------------------------------------------
# Jitted multi-lane pass II: exact-weight accumulation over stacked bottom-k
# ---------------------------------------------------------------------------


def init_pass2(lane_keys: list[np.ndarray], cap: int | None = None):
    """Device-resident pass-II accumulator over per-lane sorted sample keys.

    ``lane_keys``: one *sorted* int32 key array per lane (each <= k long, no
    EMPTY).  Returns (stacked_keys [L, cap] jnp int32 EMPTY-padded,
    acc [L, cap] jnp float64 zeros).  Run every shard of the stream through
    ``pass2_accumulate``; slice ``acc[j, :len(lane_keys[j])]`` at the end.
    """
    L = len(lane_keys)
    cap = max(1, cap if cap is not None else max((len(k) for k in lane_keys),
                                                 default=1))
    keys = np.full((L, cap), _EMPTY_INT, np.int32)
    for j, kk in enumerate(lane_keys):
        keys[j, : len(kk)] = kk
    with x64_scope():
        return jnp.asarray(keys), jnp.zeros((L, cap), jnp.float64)


@functools.partial(jax.jit, donate_argnums=(1,))
def _pass2_accum_impl(skeys, acc, keys, w):
    def lane(sk, a):
        loc = jnp.clip(SG.searchsorted(sk, keys), 0, sk.shape[0] - 1)
        match = sk[loc] == keys
        return a.at[loc].add(jnp.where(match, w, 0.0))

    return jax.vmap(lane)(skeys, acc)


def pass2_accumulate(skeys, acc, keys, weights=None, *, pad_to: int = 256):
    """Advance every lane's exact-weight accumulator by one stream batch in a
    single jitted dispatch (the device form of the paper's pass II).

    Replaces the historical per-lane host loop of ``np.searchsorted`` +
    ``np.add.at``: all lanes share one device dispatch, the scatter-add is
    bit-identical to ``np.add.at`` on CPU, and the donated accumulator makes
    steady-state reconciliation copy-free.  Batches are padded to power-of-
    two buckets (>= ``pad_to``) with EMPTY keys / zero weights so arbitrary
    batch sizes reuse a handful of compiled shapes.
    """
    keys = normalize_keys(keys)
    n = len(keys)
    w = (np.ones(n, np.float64) if weights is None
         else np.asarray(weights, np.float64).reshape(-1))
    if len(w) != n:
        raise ValueError(f"weights length {len(w)} != keys length {n}")
    m = max(pad_to, 1 << max(0, (n - 1).bit_length()))
    if m != n:
        keys = np.concatenate([keys, np.full(m - n, _EMPTY_INT, np.int32)])
        w = np.concatenate([w, np.zeros(m - n, np.float64)])
    with x64_scope():
        return _pass2_accum_impl(skeys, acc, jnp.asarray(keys), jnp.asarray(w))


# ---------------------------------------------------------------------------
# Host-side wrappers: remainder buffering for unaligned batches
# ---------------------------------------------------------------------------


class _RemainderBuffer:
    """O(chunk) staging area between arbitrary observe() batches and the
    chunk-aligned jitted update."""

    def __init__(self, chunk: int):
        self.chunk = chunk
        self.keys = np.zeros(0, np.int32)
        self.weights = np.zeros(0, np.float32)

    def add(self, keys, weights):
        """Append; return the chunk-aligned prefix ready for dispatch.

        ``keys`` must already be normalized (``normalize_keys``) — both
        stateful samplers do this in ``observe``.
        """
        keys = np.concatenate([self.keys, np.asarray(keys, np.int32).reshape(-1)])
        if weights is None:
            weights = np.ones(len(keys) - len(self.weights), np.float32)
        weights = np.concatenate(
            [self.weights, np.asarray(weights, np.float32).reshape(-1)])
        m = (len(keys) // self.chunk) * self.chunk
        self.keys, self.weights = keys[m:], weights[m:]
        return (keys[:m], weights[:m]) if m else (None, None)

    def flush_padded(self):
        """The trailing partial chunk, EMPTY/0-padded to one full chunk —
        exactly the padding the one-shot samplers apply at end-of-stream."""
        if not len(self.keys):
            return None, None
        pad = self.chunk - len(self.keys)
        keys = np.concatenate([self.keys, np.full(pad, int(EMPTY), np.int32)])
        weights = np.concatenate([self.weights, np.zeros(pad, np.float32)])
        return keys, weights

    def state_dict(self) -> dict:
        """Fixed-shape payload ([chunk] + a length scalar) so checkpoints
        restore into a fresh buffer regardless of current fill level."""
        pad = self.chunk - len(self.keys)
        return {
            "rem_keys": np.concatenate([self.keys, np.zeros(pad, np.int32)]),
            "rem_weights": np.concatenate([self.weights, np.zeros(pad, np.float32)]),
            "rem_len": np.int32(len(self.keys)),
        }

    def load_state_dict(self, d: dict) -> None:
        m = int(d["rem_len"])
        self.keys = np.asarray(d["rem_keys"], np.int32)[:m]
        self.weights = np.asarray(d["rem_weights"], np.float32)[:m]

    @property
    def nbytes(self) -> int:
        return self.keys.nbytes + self.weights.nbytes


class IncrementalSampler:
    """Single-sketch streaming sampler with arbitrary batch sizes.

    Thin stateful shell over the pure API: buffers the sub-chunk remainder on
    host, dispatches chunk-aligned prefixes through the donated update, and
    pads only at (non-destructive) finalize.
    """

    def __init__(self, l, *, k=None, tau=None, kind="continuous", chunk=2048,
                 capacity=8192, salt=0, host_id=None, evict_every=1):
        self.state, self.spec = init_state(
            l, k=k, tau=tau, kind=kind, chunk=chunk, capacity=capacity, salt=salt,
            evict_every=evict_every)
        if host_id is not None:
            self.spec = dataclasses.replace(self.spec, host_id=host_id)
        self._rem = _RemainderBuffer(chunk)

    def observe(self, keys, weights=None) -> None:
        bk, bw = self._rem.add(normalize_keys(keys), weights)
        if bk is not None:
            self.state = update(self.state, bk, bw, self.spec)

    def flushed_state(self) -> SamplerState:
        """State with the (padded) sub-chunk remainder folded in — what
        finalize sees; the live state is left untouched."""
        state = self.state
        fk, fw = self._rem.flush_padded()
        if fk is not None:
            state = update(state, fk, fw, self.spec, donate=False)
        return state

    def finalize(self) -> SampleResult:
        """Current sample over everything observed; ingestion may continue."""
        return finalize(self.flushed_state(), self.spec)

    @property
    def n_observed(self) -> int:
        return int(self.state.n_seen) + len(self._rem.keys)


class MultiSampler:
    """l-grid streaming sampler: all lanes advance in one dispatch/batch.

    Besides the fixed-k sketches, every lane carries the lossless
    bottom-(k+1) (key, seed) summary of the observed stream — O(k) extra
    state that makes cross-host merges exact (see stats.service).  Multi-host
    deployments must give each host a distinct ``host_id`` so element
    randomness never aliases across shards.
    """

    def __init__(self, ls, *, k, chunk=2048, salt=0, host_id=None,
                 evict_every=1, backend=None, sort_backend=None):
        self.ls = tuple(float(l) for l in ls)  # full-precision query keys
        self.state, self.spec = init_multi_state(
            ls, k=k, chunk=chunk, salt=salt, host_id=host_id,
            evict_every=evict_every, backend=backend,
            sort_backend=sort_backend)
        self._rem = _RemainderBuffer(chunk)
        self._n_real = 0  # real (non-padding) elements, incl. merged-in hosts

    def observe(self, keys, weights=None) -> None:
        keys = normalize_keys(keys)
        self._n_real += len(keys)
        obs.count("ingest.elements", len(keys))
        bk, bw = self._rem.add(keys, weights)
        if bk is not None:
            n_chunks = len(bk) // self.spec.chunk
            obs.count("ingest.chunks", n_chunks)
            if VZ.merge_form() == "sort":
                obs.count("merge.sort_route", n_chunks * len(self.ls))
            self.state = update_multi(self.state, bk, bw, self.spec)

    def flushed_state(self) -> SamplerState:
        """State with the (padded) sub-chunk remainder folded in — what
        finalize sees; the live state is left untouched.  Use this when
        handing the table to merge_fixed_k so trailing elements count."""
        state = self.state
        fk, fw = self._rem.flush_padded()
        if fk is not None:
            state = update_multi(state, fk, fw, self.spec, donate=False)
        return state

    def absorb(self, other: "MultiSampler", *, k, merge_summaries: bool) -> None:
        """Fold another host's sampler into this one (both flushed first).

        The fixed-k tables merge through the 1-pass heuristic
        (distributed.merge_fixed_k_multi); with ``merge_summaries`` the
        lossless bottom-(k+1) summaries min-merge too (exact mode).  Both
        remainders are flushed *in their own host's element-id namespace* —
        never re-scored under the absorbing host's ids, which would draw
        fresh randomness for already-scored elements and bias the summaries.
        """
        self.absorb_many([other], k=k, merge_summaries=merge_summaries)

    def absorb_many(self, others, *, k, merge_summaries: bool) -> None:
        """Fold any number of other hosts' samplers into this one at once —
        bit-identical to calling ``absorb`` on each in sequence (the fixed-k
        fold is a left fold; see distributed.merge_fixed_k_multi_states).
        This is the partial-merge surface the shard-tier coordinator uses to
        fold a subset of surviving shards in one shot."""
        from . import distributed as DZ

        others = list(others)
        if not others:
            return
        states = [self.flushed_state()] + [o.flushed_state() for o in others]
        mine = states[0]
        table = DZ.merge_fixed_k_multi_states(
            [s.table for s in states], mine.l, mine.salt, k=k)
        if merge_summaries:
            bk_keys, bk_seeds = DZ.merge_bottomk_multi_states(
                [(s.bk_keys, s.bk_seeds) for s in states],
                cap=mine.bk_keys.shape[1])
        else:
            bk_keys, bk_seeds = mine.bk_keys, mine.bk_seeds
        n_seen = mine.n_seen
        for s in states[1:]:
            n_seen = n_seen + s.n_seen
        self.state = SamplerState(
            table=table,
            n_seen=n_seen,
            l=mine.l, salt=mine.salt,
            bk_keys=bk_keys, bk_seeds=bk_seeds,
        )
        # remainders are inside the merged state now
        self._n_real += sum(o._n_real for o in others)
        self._rem = _RemainderBuffer(self.spec.chunk)

    def finalize(self) -> dict[float, SampleResult]:
        return finalize_multi(self.flushed_state(), self.spec, ls=self.ls)

    def bottomk_summaries(self) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of the flushed per-lane bottom-(k+1) summaries:
        ([L, k+1] keys, [L, k+1] seeds)."""
        st = self.flushed_state()
        return np.asarray(st.bk_keys), np.asarray(st.bk_seeds)

    @property
    def n_observed(self) -> int:
        return self._n_real

    # -- serialization (O(k * |ls| + chunk), independent of stream length) --

    def state_dict(self) -> dict:
        st = jax.device_get(self.state)
        t = st.table
        d = {
            "keys": t.keys, "counts": t.counts, "kb": t.kb, "seed": t.seed,
            "tau": t.tau, "step": t.step, "overflow": t.overflow,
            "bk_keys": st.bk_keys, "bk_seeds": st.bk_seeds,
            "n_seen": np.int32(st.n_seen),
            "n_real": np.int64(self._n_real),
            "ls": np.asarray(st.l),
            "salt": np.uint32(st.salt),
        }
        d.update(self._rem.state_dict())
        return d

    def load_state_dict(self, d: dict) -> None:
        # re-canonicalize the table layout: blobs written before the
        # single-sort ingest path stored eviction holes in place, while the
        # sorted-runs merge requires ascending keys with EMPTY compacted last
        # (a stable per-lane key sort is a no-op on current-format blobs)
        blob_keys = np.asarray(d["keys"], np.int32)
        if blob_keys.shape[-1] != self.state.capacity:
            # capacity is k + evict_every*chunk: a blob written under a
            # different evict_every would silently truncate merges (E too
            # small) or overflow the top_k eviction window (E too large)
            raise ValueError(
                f"state blob table capacity {blob_keys.shape[-1]} != configured "
                f"capacity {self.state.capacity} (k + evict_every*chunk) — "
                "restore with the same (k, chunk, evict_every) the blob was "
                "written with")
        ord_ = np.argsort(blob_keys, axis=1, kind="stable")
        tab = lambda name, dt: jnp.asarray(
            np.take_along_axis(np.asarray(d[name], dt), ord_, axis=1))
        table = VZ.TableState(
            keys=tab("keys", np.int32), counts=tab("counts", np.float32),
            kb=tab("kb", np.float32), seed=tab("seed", np.float32),
            tau=jnp.asarray(d["tau"]),
            step=jnp.asarray(d["step"]), overflow=jnp.asarray(d["overflow"]),
        )
        # blobs written before the summary buffers existed load with fresh
        # (empty) summaries — the caller must treat them as invalid for
        # exact merging (stats.service keys this off the same absence)
        L, cap_bk = table.keys.shape[0], (self.spec.k or 0) + 1
        bk_keys = (jnp.asarray(d["bk_keys"], jnp.int32) if "bk_keys" in d
                   else jnp.full((L, cap_bk), EMPTY, jnp.int32))
        bk_seeds = (jnp.asarray(d["bk_seeds"], jnp.float32) if "bk_seeds" in d
                    else jnp.full((L, cap_bk), jnp.inf, jnp.float32))
        self.state = SamplerState(
            table=table,
            n_seen=jnp.asarray(d["n_seen"], jnp.int32),
            l=jnp.asarray(d["ls"], jnp.float32),
            salt=jnp.asarray(d["salt"], jnp.uint32),
            bk_keys=bk_keys, bk_seeds=bk_seeds,
        )
        self._rem.load_state_dict(d)
        self._n_real = int(d["n_real"]) if "n_real" in d else (
            int(self.state.n_seen) + len(self._rem.keys))

    @property
    def resident_bytes(self) -> int:
        """Device-resident sketch bytes + host remainder bytes."""
        leaves = jax.tree.leaves(self.state)
        return sum(int(np.asarray(x).nbytes) for x in leaves) + self._rem.nbytes


class _PendingQueue:
    """Per-tenant ingest staging: an O(backlog) list of arrays with O(1)
    appends; ``take``/``peek`` concatenate lazily.  Unlike _RemainderBuffer
    this may hold many chunks — the bank drains one chunk per tick."""

    def __init__(self):
        self._keys: list[np.ndarray] = []
        self._weights: list[np.ndarray] = []
        self.size = 0

    def push(self, keys: np.ndarray, weights) -> None:
        """``keys`` must already be normalized (int32, validated)."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        if weights is None:
            weights = np.ones(len(keys), np.float32)
        weights = np.asarray(weights, np.float32).reshape(-1)
        if len(weights) != len(keys):
            raise ValueError(
                f"weights length {len(weights)} != keys length {len(keys)}")
        if len(keys):
            self._keys.append(keys)
            self._weights.append(weights)
            self.size += len(keys)

    def _compact(self):
        if len(self._keys) > 1:
            self._keys = [np.concatenate(self._keys)]
            self._weights = [np.concatenate(self._weights)]

    def take(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Pop exactly the oldest ``n`` elements (requires size >= n)."""
        if n > self.size:
            raise ValueError(f"take({n}) from queue of {self.size}")
        self._compact()
        k, w = self._keys[0], self._weights[0]
        self._keys = [k[n:]] if len(k) > n else []
        self._weights = [w[n:]] if len(w) > n else []
        self.size -= n
        return k[:n], w[:n]

    def peek_all(self) -> tuple[np.ndarray, np.ndarray]:
        """Everything queued, without popping."""
        self._compact()
        if not self._keys:
            return np.zeros(0, np.int32), np.zeros(0, np.float32)
        return self._keys[0], self._weights[0]

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self._keys) + sum(
            a.nbytes for a in self._weights)


class TenantBank:
    """N resident multi-l sampler instances advanced as ONE stacked pytree.

    The multi-tenant analogue of ``MultiSampler``: ``observe(tenant, ...)``
    stages elements in per-tenant host queues; each ``tick()`` drains one
    chunk from EVERY tenant with a full chunk buffered and advances all of
    their l-grids in a single vmapped/jitted device dispatch with donated
    buffers.  Sub-chunk remainders stay queued (the per-tenant analogue of
    MultiSampler's remainder buffer) and are folded in — padded, without
    consuming real stream positions — only at finalize/state_dict time.

    Per-tenant bit-identity contract (tests/test_serving.py): tenant ``t`` of
    a bank fed some chunk sequence finalizes bit-identically (tables, taus,
    bottom-(k+1) summaries, query answers) to a standalone ``MultiSampler``
    constructed with ``salt=salts[t]`` and fed the same sequence — the bank
    is purely a dispatch-batching layout, not a statistical change.

    Checkpointing: ``state_dict`` is one flat dict of [T, ...]-stacked
    fixed-size arrays (saves through checkpoint.manager like any pytree);
    ``tenant_state_dict(t)`` slices out one tenant in the exact
    ``MultiSampler.state_dict`` format, and ``load_tenant_state_dict(t, d)``
    splices one back in — the join/leave handoff surface (see
    checkpoint.manager.restore_slice for restoring a single tenant without
    an example bank).
    """

    def __init__(self, ls, *, n_tenants, k, chunk=2048, salts=0, host_id=None,
                 evict_every=1, backend=None, sort_backend=None):
        self.ls = tuple(float(l) for l in ls)
        self.n_tenants = int(n_tenants)
        with obs.span("bank.build"):
            self.state, self.spec = init_bank_state(
                ls, n_tenants=n_tenants, k=k, chunk=chunk, salts=salts,
                host_id=host_id, evict_every=evict_every, backend=backend,
                sort_backend=sort_backend)
        self._queues = [_PendingQueue() for _ in range(self.n_tenants)]
        self._n_real = np.zeros(self.n_tenants, np.int64)

    # -- ingestion ---------------------------------------------------------

    def observe(self, tenant: int, keys, weights=None) -> None:
        """Stage a batch of tenant ``tenant``'s stream (host arrays ok); the
        device state advances at the next ``tick``."""
        keys = normalize_keys(keys)
        self._n_real[tenant] += len(keys)
        self._queues[tenant].push(keys, weights)

    def backlog_chunks(self) -> np.ndarray:
        """Full chunks currently buffered, per tenant."""
        return np.asarray([q.size // self.spec.chunk for q in self._queues],
                          np.int64)

    def tick(self) -> int:
        """One stacked dispatch: every tenant with >= 1 full chunk buffered
        advances by exactly one chunk (inherently fair — no tenant can take
        more than one chunk per tick).  Returns the number of active tenants
        (0 = nothing to do, no dispatch issued).  The dispatch is enqueued
        asynchronously — this never blocks on device compute."""
        chunk = self.spec.chunk
        active = np.asarray([q.size >= chunk for q in self._queues])
        if not active.any():
            return 0
        n_active = int(active.sum())
        with obs.span("bank.tick"):
            K = np.full((self.n_tenants, chunk), _EMPTY_INT, np.int32)
            W = np.zeros((self.n_tenants, chunk), np.float32)
            for t in np.nonzero(active)[0]:
                K[t], W[t] = self._queues[t].take(chunk)
            self.state = update_bank(self.state, K, W, active, self.spec)
        # every tenant's lane runs in the vmapped tick; the active carry
        # a real chunk (their ratio is the tick's useful share)
        obs.count("bank.lanes_run", self.n_tenants)
        obs.count("bank.lanes_real", n_active)
        if VZ.merge_form() == "sort":
            obs.count("merge.sort_route", self.n_tenants * len(self.ls))
        return n_active

    def drain(self) -> int:
        """Tick until no tenant holds a full chunk; returns ticks issued."""
        ticks = 0
        while self.tick():
            ticks += 1
        return ticks

    # -- extraction --------------------------------------------------------

    def flushed_state(self) -> SamplerState:
        """Bank state with every queued element folded in: full chunks are
        drained for real, then each non-empty sub-chunk remainder is EMPTY/0
        padded to one chunk and applied WITHOUT donating (live state and
        queues untouched by the padding pass) — exactly the padding a
        standalone MultiSampler applies at finalize."""
        self.drain()
        chunk = self.spec.chunk
        active = np.asarray([q.size > 0 for q in self._queues])
        if not active.any():
            return self.state
        K = np.full((self.n_tenants, chunk), _EMPTY_INT, np.int32)
        W = np.zeros((self.n_tenants, chunk), np.float32)
        for t in np.nonzero(active)[0]:
            kk, ww = self._queues[t].peek_all()
            K[t, : len(kk)], W[t, : len(ww)] = kk, ww
        return update_bank(self.state, K, W, active, self.spec, donate=False)

    def finalize_all(self) -> list[dict[float, SampleResult]]:
        """Every tenant's per-lane SampleResults in ONE device extraction
        (vmapped final eviction + a single device_get of the stacked table),
        indexed ``out[tenant][l]``."""
        st = self.flushed_state()
        table = st.table
        if self.spec.evict_every > 1:
            table = _final_evict_bank(table, st.l, st.salt, self.spec)
        tables = jax.device_get(table)
        out = []
        for t in range(self.n_tenants):
            per = {}
            for j, l in enumerate(self.ls):
                tab = jax.tree.map(lambda a: a[t, j], tables)
                per[l] = VZ._to_result(tab, l=l, kind=self.spec.kind,
                                       tau=float(tab.tau))
            out.append(per)
        return out

    def finalize_some(self, tenants) -> dict[int, dict[float, SampleResult]]:
        """A SUBSET of tenants' per-lane SampleResults, extracting (and
        host-materializing) only those rows of the bank — the serving-tier
        fast path when a query batch touches few of many tenants (the whole
        bank still flushes; only the device→host copy and the per-lane
        result construction are restricted)."""
        st = self.flushed_state()
        idx = np.asarray(sorted({int(t) for t in tenants}), np.int64)
        table = jax.tree.map(lambda a: a[idx], st.table)
        if self.spec.evict_every > 1:
            table = _final_evict_bank(table, st.l, st.salt[idx], self.spec)
        tables = jax.device_get(table)
        out: dict[int, dict[float, SampleResult]] = {}
        for i, t in enumerate(idx.tolist()):
            per = {}
            for j, l in enumerate(self.ls):
                tab = jax.tree.map(lambda a: a[i, j], tables)
                per[l] = VZ._to_result(tab, l=l, kind=self.spec.kind,
                                       tau=float(tab.tau))
            out[t] = per
        return out

    def finalize(self, tenant: int) -> dict[float, SampleResult]:
        """One tenant's per-lane SampleResults (subset extraction; use
        ``finalize_all`` when you need every tenant)."""
        return self.finalize_some([tenant])[tenant]

    def n_observed(self, tenant: int) -> int:
        return int(self._n_real[tenant])

    # -- serialization (O(T * k * |ls| + T * chunk)) -------------------------

    def _remainders(self) -> dict:
        """Fixed-shape per-tenant remainder payload (full chunks drained
        first so every queue fits one [chunk] row)."""
        self.drain()
        chunk = self.spec.chunk
        rk = np.zeros((self.n_tenants, chunk), np.int32)
        rw = np.zeros((self.n_tenants, chunk), np.float32)
        rl = np.zeros(self.n_tenants, np.int32)
        for t, q in enumerate(self._queues):
            kk, ww = q.peek_all()
            rk[t, : len(kk)], rw[t, : len(ww)] = kk, ww
            rl[t] = len(kk)
        return {"rem_keys": rk, "rem_weights": rw, "rem_len": rl}

    def state_dict(self) -> dict:
        """Flat dict of [T, ...]-stacked arrays, leaf-for-leaf parallel to
        ``MultiSampler.state_dict`` (same key names, one extra leading tenant
        axis on per-tenant leaves) so ``checkpoint.manager.restore_slice``
        can restore any single tenant against a MultiSampler-shaped example.
        Drains queued full chunks first (they belong in the checkpoint)."""
        rem = self._remainders()  # drains full chunks INTO the state first
        st = jax.device_get(self.state)
        t = st.table
        d = {
            "keys": t.keys, "counts": t.counts, "kb": t.kb, "seed": t.seed,
            "tau": t.tau, "step": t.step, "overflow": t.overflow,
            "bk_keys": st.bk_keys, "bk_seeds": st.bk_seeds,
            "n_seen": np.asarray(st.n_seen, np.int32),
            "n_real": self._n_real.copy(),
            "ls": np.asarray(st.l),
            "salt": np.asarray(st.salt, np.uint32),
        }
        d.update(rem)
        return d

    def tenant_state_dict(self, tenant: int) -> dict:
        """One tenant, in the exact ``MultiSampler.state_dict`` format —
        loads into a standalone ``MultiSampler``/``StreamStatsService`` (the
        leave/handoff path) bit-for-bit."""
        d = self.state_dict()
        shared = {"ls"}
        return {k: (v if k in shared else v[tenant]) for k, v in d.items()}

    def load_tenant_state_dict(self, tenant: int, d: dict) -> None:
        """Splice a ``MultiSampler``-format blob into one bank row (the join
        path).  Validated by round-tripping through a scratch MultiSampler
        loader (same capacity/layout canonicalization)."""
        probe = MultiSampler(self.ls, k=self.spec.k, chunk=self.spec.chunk,
                             evict_every=self.spec.evict_every)
        probe.load_state_dict(d)
        ps = jax.device_get(probe.state)
        at = lambda arr, new: jnp.asarray(np.asarray(arr)).at[tenant].set(new)
        table = VZ.TableState(
            keys=at(self.state.table.keys, ps.table.keys),
            counts=at(self.state.table.counts, ps.table.counts),
            kb=at(self.state.table.kb, ps.table.kb),
            seed=at(self.state.table.seed, ps.table.seed),
            tau=at(self.state.table.tau, ps.table.tau),
            step=at(self.state.table.step, ps.table.step),
            overflow=at(self.state.table.overflow, ps.table.overflow),
        )
        self.state = SamplerState(
            table=table,
            n_seen=at(self.state.n_seen, ps.n_seen),
            l=self.state.l,
            salt=at(self.state.salt, ps.salt),
            bk_keys=at(self.state.bk_keys, ps.bk_keys),
            bk_seeds=at(self.state.bk_seeds, ps.bk_seeds),
        )
        self._queues[tenant] = _PendingQueue()
        self._queues[tenant].push(
            np.asarray(d["rem_keys"], np.int32)[: int(d["rem_len"])],
            np.asarray(d["rem_weights"], np.float32)[: int(d["rem_len"])])
        self._n_real[tenant] = int(d["n_real"]) if "n_real" in d else 0

    def load_state_dict(self, d: dict) -> None:
        T = self.n_tenants
        if np.asarray(d["keys"]).shape[0] != T:
            raise ValueError(
                f"bank blob has {np.asarray(d['keys']).shape[0]} tenants, "
                f"bank configured with {T}")
        if np.asarray(d["keys"]).shape[-1] != self.state.capacity:
            raise ValueError(
                f"state blob table capacity {np.asarray(d['keys']).shape[-1]} "
                f"!= configured capacity {self.state.capacity} "
                "(k + evict_every*chunk) — restore with the same "
                "(k, chunk, evict_every) the blob was written with")
        # same per-lane layout re-canonicalization as MultiSampler: stable
        # key sort per (tenant, lane) row is a no-op on current-format blobs
        blob_keys = np.asarray(d["keys"], np.int32)
        ord_ = np.argsort(blob_keys, axis=-1, kind="stable")
        tab = lambda name, dt: jnp.asarray(
            np.take_along_axis(np.asarray(d[name], dt), ord_, axis=-1))
        table = VZ.TableState(
            keys=tab("keys", np.int32), counts=tab("counts", np.float32),
            kb=tab("kb", np.float32), seed=tab("seed", np.float32),
            tau=jnp.asarray(d["tau"]),
            step=jnp.asarray(d["step"]), overflow=jnp.asarray(d["overflow"]),
        )
        self.state = SamplerState(
            table=table,
            n_seen=jnp.asarray(d["n_seen"], jnp.int32),
            l=jnp.asarray(d["ls"], jnp.float32),
            salt=jnp.asarray(d["salt"], jnp.uint32),
            bk_keys=jnp.asarray(d["bk_keys"], jnp.int32),
            bk_seeds=jnp.asarray(d["bk_seeds"], jnp.float32),
        )
        self._queues = [_PendingQueue() for _ in range(T)]
        rl = np.asarray(d["rem_len"], np.int32)
        for t in range(T):
            self._queues[t].push(
                np.asarray(d["rem_keys"], np.int32)[t, : rl[t]],
                np.asarray(d["rem_weights"], np.float32)[t, : rl[t]])
        self._n_real = np.asarray(d["n_real"], np.int64).copy()

    @property
    def resident_bytes(self) -> int:
        leaves = jax.tree.leaves(self.state)
        return sum(int(np.asarray(x).nbytes) for x in leaves) + sum(
            q.nbytes for q in self._queues)
