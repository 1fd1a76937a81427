"""StreamStatsService: frequency-cap statistics as a first-class framework
feature (the paper's ad-campaign application, generalized) — a true
incremental service with an **exact multi-host mode**.

Attach a service to any input pipeline; it maintains one fixed-k continuous
SH_l sketch per configured l over the stream of keys flowing through
training/serving and answers

    service.query_cap(T, segment)  ~=  Q(cap_T, segment)
    service.query_batch([(fn, segment), ...])   # many (T x segment) cells,
                                                # ONE jitted device dispatch

Queries ride the batched query plane (stats/query.py, DESIGN.md §7): the
whole batch is answered in one jitted dispatch over the stacked lane
arrays — bit-identical to looping the scalar estimators — with per-query
variance/CI diagnostics; segment masks and estimator coefficient tables
are compiled once per sketch and cached device-resident.
``launch.stats_serve`` wraps this in a request-batching server loop.

**State is O(k * |ls|), independent of stream length.**  ``observe()``
advances every sketch of the l-grid in a single jitted device dispatch with
donated state buffers (core.incremental.MultiSampler): each chunk is
permuted into key order once, then the fused multi-l ``capscore_agg``
kernel scores all lanes AND segment-reduces them to per-key aggregate
columns in the same pass (the per-element [L, chunk] scores never
materialize; DESIGN.md §9), then the sorted-runs merge/evict step runs
vmapped across lanes.  Nothing is buffered
except the sub-chunk remainder (< chunk elements) awaiting alignment;
queries finalize the resident sketches lazily (cached until the next
``observe``) — no replay, no recompute.

Uses: ad-campaign reach forecasting (recsys archs: keys = (user, campaign)
pairs, answer = number of qualifying impressions under a per-user cap T);
token-frequency statistics for LM data mixing; degree statistics for GNN
samplers; expert-load statistics for MoE routing diagnostics.

Multi-host contract (DESIGN.md §5):

* Give every host a distinct ``StatsConfig.host_id`` (same k/ls/chunk/salt).
  Key randomness (KeyBase hashes) is shared through the salt — that is the
  coordination that makes merges meaningful — while element randomness is
  host-disambiguated so shards never alias.
* ``merge(other)`` (mode="exact", the default) min-merges each lane's
  *lossless* bottom-(k+1) (key, seed) summary — exact for ANY split of
  elements across hosts, including keys straddling hosts (paper §3.1) — and
  also folds the 1-pass fixed-k sketches so approximate queries keep working.
* ``reconcile(keys, weights)`` is the paper's pass II: re-scan each host's
  shard (stream it through in any batch sizes; or use
  core.distributed.pass2_shard_multi on a mesh) to accumulate the exact
  weights of the sampled keys.  Once every shard has been reconciled,
  queries flow through the 2-pass inverse-probability estimators
  (``exact_weights=True``) with **zero merge bias**.
* ``merge(other, mode="approx")`` skips the summaries: cheapest, unbiased
  for key-partitioned shards, but carries up to ~10% bias at k=512 when
  keys straddle hosts (measured in tests/test_merge_bias.py).  Exact mode
  exists precisely to kill that bias.

The service state is a pytree: ``state_dict()`` is a flat dict of fixed-size
arrays (sketches + summaries + remainder) that checkpoints through
checkpoint.manager (``save_checkpoint`` / ``restore_checkpoint`` below) and
resumes bit-for-bit mid-stream.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from pathlib import Path
from typing import Sequence

import numpy as np

from .. import obs
from ..checkpoint import manager as ckpt_manager
from ..core import freqfns, incremental
from ..core.samplers import SampleResult
from ..core.segments import EMPTY
from .query import BatchResult, PendingBatch, Query, QueryEngine

# the paper's guidance (preceding §6.1): a geometric l-grid with ratio
# sqrt(2)^2 = 2 keeps every T within sqrt(2) of a lane in log space
_L_GRID_FACTOR = 0.5 * math.log(2.0)  # log(sqrt(2))


def _nearest_lane(ls, T: float) -> tuple[float, float]:
    """(nearest-in-log lane l, log-space distance) for a cap parameter T."""
    ls = np.asarray(ls, dtype=np.float64)
    dist = np.abs(np.log(ls) - math.log(max(T, 1e-9)))
    j = int(np.argmin(dist))
    return float(ls[j]), float(dist[j])


def _grid_warning(T: float, l: float, dist: float) -> str:
    return (
        f"cap T={T:g} is {math.exp(dist):.2f}x away from the "
        f"nearest configured lane l={l:g} — beyond the paper's "
        "sqrt(2) log-space factor, so the estimate's CV degrades with "
        "the disparity max(T/l, l/T) (Thm 5.4).  Densify StatsConfig.ls "
        "toward a geometric grid of ratio <= 2 over the queried T range "
        "(and extend its ends if T falls outside).  "
        "(warning shown once per service)")


@dataclasses.dataclass
class StatsConfig:
    k: int = 4096                      # sample size per sketch
    ls: Sequence[float] = (1.0, 16.0, 256.0, 4096.0)  # geometric l-grid (§6)
    chunk: int = 2048
    salt: int = 0x5EED
    host_id: int | None = None         # REQUIRED (distinct) for exact merges
    # eviction amortization period E (DESIGN.md §8): capacity grows to
    # k + E*chunk and the sketches evict every E chunks.  E=1 (default) is
    # bit-compatible with the one-shot samplers; E>1 trades that per-run
    # identity (NOT correctness — the count law and unbiasedness hold, see
    # tests/test_ingest_order.py) for skipping eviction work on E-1 of every
    # E chunks.  The lossless bottom-(k+1) summaries and the exact two-pass
    # mode are unaffected by E.
    evict_every: int = 1
    # backend of the fused score+aggregate ingest stage (capscore_agg):
    # None auto-picks per accelerator (compiled Pallas on TPU, XLA
    # elsewhere); 'xla' | 'pallas' force a path.  Does not gate merging —
    # the XLA path is bit-identical everywhere, Pallas only reassociates
    # in-block f32 sums.
    ingest_backend: str | None = None


@dataclasses.dataclass
class _LaneSample:
    """Frozen pass-1 outcome of one l lane (the pass-2 exact-weight
    accumulators live stacked on device, see ``reconcile``)."""

    l: float
    keys: np.ndarray       # sorted sampled keys (<= k)
    tau: float             # (k+1)-smallest seed, inf if everything sampled


class StreamStatsService:
    """Incremental multi-l sketch service over the jitted chunked samplers.

    For each l in the grid we keep a fixed-k continuous SH_l sketch plus the
    lossless bottom-(k+1) summary that powers the exact distributed mode.  A
    cap_T query is answered from the sketch with l closest to T in log-space
    (the paper's recommendation preceding §6.1: pick l within sqrt(2) of T).
    """

    def __init__(self, config: StatsConfig):
        self.config = config
        with obs.span("service.build"):
            self._sampler = incremental.MultiSampler(
                tuple(float(l) for l in config.ls), k=config.k,
                chunk=config.chunk, salt=config.salt, host_id=config.host_id,
                evict_every=config.evict_every,
                backend=config.ingest_backend,
            )
        self._results: dict[float, SampleResult] | None = None
        self._engines: dict[bool, QueryEngine] = {}  # query plane, per path
        self._lanes: list[_LaneSample] | None = None  # frozen pass-1 samples
        self._recon_keys = None  # [L, kmax] device sorted sample keys
        self._recon_acc = None   # [L, kmax] device f64 exact-weight accs
        self._recon_n = 0  # elements re-scanned by the current reconcile
        self._recon_discarded = False  # a begun reconcile was invalidated
        self._exact_ok = True  # summaries valid (invalidated by approx merge)
        self._l_grid_warned = False  # pick_l out-of-grid warning (once)
        self._pick_l_cache: dict[float, float] = {}
        # every host whose stream this service has absorbed (exact mode must
        # never merge two streams sharing an element-id namespace)
        self._host_ids: set[int] = (
            set() if config.host_id is None else {config.host_id})

    # -- ingestion ---------------------------------------------------------

    def observe(self, keys, weights=None) -> None:
        """Feed a batch of stream elements (host arrays ok).

        One jitted dispatch advances all |ls| sketches; only the sub-chunk
        remainder stays on host until the next batch aligns it.  Keys are
        validated through the same ``incremental.normalize_keys`` helper as
        ``reconcile`` — never silently wrapped to int32.
        """
        with obs.span("service.observe"):
            self._sampler.observe(keys, weights)
        self._results = None
        self._engines.clear()
        self._invalidate_reconcile()

    def _invalidate_reconcile(self) -> None:
        """New elements / merges change the pass-1 sample: any accumulated
        pass-II weights refer to a stale sample and must be discarded."""
        if self._lanes is not None:
            self._lanes = None
            self._recon_keys = self._recon_acc = None
            self._recon_discarded = True
            self._engines.pop(True, None)

    @property
    def n_observed(self) -> int:
        return self._sampler.n_observed

    # -- sketch materialization --------------------------------------------

    def _materialize(self) -> dict[float, SampleResult]:
        if self._results is None:
            self._results = self._sampler.finalize()
        return self._results

    def sketches(self) -> dict[float, SampleResult]:
        return self._materialize()

    # -- queries -------------------------------------------------------------

    _L_GRID_FACTOR = _L_GRID_FACTOR  # see module level (shared with the bank)

    def pick_l(self, T: float) -> float:
        cached = self._pick_l_cache.get(T)
        if cached is not None:
            return cached
        l, dist = _nearest_lane(self.config.ls, T)
        if dist > self._L_GRID_FACTOR + 1e-9 and not self._l_grid_warned:
            self._l_grid_warned = True
            warnings.warn(_grid_warning(T, l, dist), RuntimeWarning,
                          stacklevel=2)
        self._pick_l_cache[T] = l
        return l

    @property
    def _reconcile_complete(self) -> bool:
        """Every observed element has been streamed back through reconcile
        (each shard exactly once re-scans the whole logical stream)."""
        return self._lanes is not None and self._recon_n >= self.n_observed

    def _use_exact(self, exact: bool | None) -> bool:
        # auto mode only trusts the exact path once pass II covered the whole
        # stream — a half-reconciled accumulator would silently report
        # partial sums (or 0/0 = nan for zero-weight keys)
        use_exact = exact if exact is not None else self._reconcile_complete
        if use_exact and not self._reconcile_complete:
            raise ValueError(
                f"exact query before reconcile completed: {self._recon_n} "
                f"of {self.n_observed} observed elements re-scanned — "
                "stream every shard through reconcile() first")
        return use_exact

    def _engine(self, exact: bool | None) -> QueryEngine:
        """The batched query plane over the current sketches (lazily built,
        cached until the underlying sample changes)."""
        use_exact = self._use_exact(exact)
        engine = self._engines.get(use_exact)
        if engine is None:
            sketches = (self.exact_sketches() if use_exact
                        else self._materialize())
            engine = self._engines[use_exact] = QueryEngine(sketches)
        return engine

    def _resolve_lane(self, q: Query) -> Query:
        if q.l is not None:
            return q
        kind = q.fn.kind
        if kind in ("cap", "threshold"):
            l = self.pick_l(q.fn.param)
        elif kind == "distinct":
            l = self.pick_l(1.0)
        else:  # total / moment / log1p / custom: weight-proportional regime
            l = max(self.config.ls)
        return Query(q.fn, q.segment, l)

    def query_batch(self, queries, *, exact: bool | None = None) -> BatchResult:
        """Answer a whole batch of (FreqFn, segment[, lane]) queries in one
        jitted device dispatch over the stacked lane arrays.

        Each element of ``queries`` is a ``stats.query.Query`` or an
        ``(fn, segment)`` / ``(fn, segment, l)`` tuple; unresolved lanes are
        picked per statistic exactly like the scalar wrappers (``cap_T`` /
        ``threshold_T`` -> nearest-in-log lane, ``distinct`` -> pick_l(1),
        everything else -> max l).  Answers are bit-identical to looping
        ``estimators.estimate`` over the same sketches, and arrive with
        per-query variance/CI diagnostics (see stats.query).
        """
        qs = [q if isinstance(q, Query) else Query(*q) for q in queries]
        engine = self._engine(exact)
        return engine.query_batch([self._resolve_lane(q) for q in qs])

    def query_cap(self, T: float, segment=None, *, exact: bool | None = None) -> float:
        """Estimate Q(cap_T, segment).

        ``exact=None`` (default) uses the reconciled 2-pass estimates when a
        reconcile pass has run, else the resident 1-pass sketches; force one
        path with True/False.  Thin wrapper over ``query_batch`` (one-query
        batch), bit-compatible with the scalar estimator path.
        """
        r = self.query_batch([Query(freqfns.cap(T), segment)], exact=exact)
        return float(r.estimates[0])

    def query_distinct(self, segment=None, *, exact: bool | None = None) -> float:
        r = self.query_batch([Query(freqfns.distinct(), segment)], exact=exact)
        return float(r.estimates[0])

    def query_total(self, segment=None, *, exact: bool | None = None) -> float:
        r = self.query_batch([Query(freqfns.total(), segment)], exact=exact)
        return float(r.estimates[0])

    def campaign_forecast(self, cap_per_user: float, segment=None, *,
                          exact: bool | None = None) -> float:
        """The paper's motivating query: qualifying impressions under a
        per-user frequency cap, for the user segment H."""
        return self.query_cap(cap_per_user, segment, exact=exact)

    # -- hot-key extraction (embedding-sharding integration) -----------------

    def hot_keys(self, top: int) -> np.ndarray:
        """Keys with the largest sampled counts — candidates for replicated
        'hot' embedding-table placement.  Uses the largest-l sketch (closest
        to pps-by-frequency)."""
        res = self._materialize()[max(self.config.ls)]
        order = np.argsort(-res.counts)
        return res.keys[order[:top]]

    # -- multi-host merge ----------------------------------------------------

    def merge(self, other: "StreamStatsService", mode: str = "exact") -> None:
        """Absorb another host's state.  Both services must share
        (k, ls, chunk, salt).

        mode="exact": additionally min-merge the lossless per-lane
        bottom-(k+1) summaries (paper §3.1 mergeability) — requires the two
        hosts to carry **distinct** ``host_id``s, otherwise their element
        randomness aliases and the merged summary is silently biased.  Run
        ``reconcile`` over every shard afterwards to unlock exact queries.

        mode="approx": 1-pass ``merge_fixed_k`` only — cheap, unbiased for
        key-partitioned shards, ~10% bias for arbitrary element splits;
        exact queries become unavailable.
        """
        self.merge_many([other], mode=mode)

    def merge_many(self, others, mode: str = "exact") -> None:
        """Absorb ANY number of other hosts' states in one pairwise-tree
        fold (same validation as ``merge``, applied across the whole group;
        a single ``other`` is exactly ``merge``).  The shard-tier
        coordinator uses this to fold the surviving shards of a degraded
        tier — or all shards of a healthy one — into a scratch service.
        An empty sequence is a no-op."""
        others = list(others)
        if not others:
            return
        for other in others:
            if (tuple(other.config.ls) != tuple(self.config.ls)
                    or other.config.k != self.config.k
                    or other.config.salt != self.config.salt
                    or other.config.chunk != self.config.chunk
                    or other.config.evict_every != self.config.evict_every):
                # salt especially: kb/seed/tau from different hash functions
                # would union into a silently biased sketch; evict_every
                # because the lane-wise table merge requires equal capacities
                raise ValueError(
                    "merge requires identical (k, ls, chunk, salt, evict_every) configs")
        if mode not in ("exact", "approx"):
            raise ValueError(f"unknown merge mode {mode!r}")
        if mode == "exact":
            if self.config.host_id is None or any(
                    o.config.host_id is None for o in others):
                raise ValueError(
                    "exact merge requires a host_id on both services: shared "
                    "element-id namespaces alias randomness across shards")
            ids = set(self._host_ids)
            for other in others:
                overlap = ids & other._host_ids
                if overlap:
                    # not just pairwise: hosts absorbed earlier count too (two
                    # absorbed shards sharing an id namespace are just as
                    # biased)
                    raise ValueError(
                        "exact merge requires distinct host_ids across ALL "
                        f"absorbed hosts; {sorted(overlap)} appear on both sides")
                ids |= other._host_ids
            if not (self._exact_ok and all(o._exact_ok for o in others)):
                raise ValueError(
                    "exact merge unavailable: a prior mode='approx' merge "
                    "invalidated the lossless summaries")
        self._sampler.absorb_many([o._sampler for o in others],
                                  k=self.config.k,
                                  merge_summaries=(mode == "exact"))
        for other in others:
            self._host_ids |= other._host_ids
        if mode == "approx":
            self._exact_ok = False
        self._results = None
        self._engines.clear()
        self._invalidate_reconcile()

    # -- exact second pass (paper pass II) -----------------------------------

    def begin_reconcile(self) -> None:
        """Freeze the pass-1 sample (per-lane bottom-k keys + threshold) and
        reset the exact-weight accumulators.  Called implicitly by the first
        ``reconcile``; must be called EXPLICITLY to restart after an
        ``observe``/``merge`` discarded a begun reconcile."""
        if not self._exact_ok:
            raise ValueError(
                "exact pass unavailable after a mode='approx' merge")
        self._recon_discarded = False
        self._recon_n = 0
        self._engines.pop(True, None)
        bk_keys, bk_seeds = self._sampler.bottomk_summaries()
        k = self.config.k
        self._lanes = []
        for j, l in enumerate(self.config.ls):
            keys_j, seeds_j = bk_keys[j], bk_seeds[j]
            valid = keys_j != int(EMPTY)
            kk, ss = keys_j[valid], seeds_j[valid]
            order = np.argsort(ss)
            if len(kk) > k:
                tau = float(ss[order[k]])
                kk = kk[order[:k]]
            else:
                tau = math.inf
            kk = np.sort(kk)
            self._lanes.append(_LaneSample(l=float(l), keys=kk, tau=tau))
        # stacked device accumulators: every lane advances per reconcile
        # batch in one jitted dispatch (core.incremental.pass2_accumulate)
        self._recon_keys, self._recon_acc = incremental.init_pass2(
            [lane.keys for lane in self._lanes])

    def reconcile(self, keys, weights=None) -> None:
        """Accumulate exact weights of the sampled keys from a batch of the
        original stream (pass II).  Stream EVERY shard's elements through
        this (any batch sizes, any order) before exact queries; weights of
        un-reconciled elements are simply missing from the estimates.

        All |ls| lanes advance in a single jitted device dispatch over the
        stacked bottom-k keys, with the accumulator buffers donated between
        batches.  Keys are validated (dtype / int32 range / reserved EMPTY)
        by the same helper as ``observe`` — never silently wrapped.  On a
        mesh, core.distributed.pass2_shard_multi + psum is the equivalent
        collective form."""
        if self._lanes is None:
            if self._recon_discarded:
                # an observe()/merge() changed the pass-1 sample after a
                # reconcile began: silently re-beginning would drop the
                # weights accumulated so far and report partial sums as exact
                raise ValueError(
                    "reconcile was invalidated by observe()/merge(): the "
                    "accumulated pass-II weights were discarded — call "
                    "begin_reconcile() and re-stream EVERY shard")
            self.begin_reconcile()
        keys = incremental.normalize_keys(keys)
        self._recon_acc = incremental.pass2_accumulate(
            self._recon_keys, self._recon_acc, keys, weights)
        self._recon_n += len(keys)
        self._engines.pop(True, None)

    def exact_sketches(self) -> dict[float, SampleResult]:
        """Per-lane 2-pass SampleResults (exact weights) from the reconciled
        accumulators.  Available only once pass II covered the whole stream
        — partial accumulators stamped ``exact_weights=True`` would be the
        silent-wrong-answer path this API exists to kill."""
        if not self._reconcile_complete:
            raise ValueError(
                f"no complete exact sample: {self._recon_n} of "
                f"{self.n_observed} observed elements re-scanned — run "
                "reconcile(keys, weights) over every shard of the stream")
        acc = np.asarray(self._recon_acc, dtype=np.float64)
        return {
            lane.l: SampleResult(
                keys=lane.keys, counts=acc[j, : len(lane.keys)].copy(),
                tau=lane.tau, l=lane.l, kind="continuous", exact_weights=True)
            for j, lane in enumerate(self._lanes)
        }

    # -- checkpointing --------------------------------------------------------

    def state_dict(self) -> dict:
        """O(k * |ls| + chunk) pytree of fixed-size arrays — the size is
        independent of how many elements were observed.  Includes the
        lossless bottom-(k+1) summary buffers and their validity flag.

        Checkpoint per host, before merging: the set of absorbed host_ids is
        deliberately not serialized (variable length), so a restored service
        only knows its own configured host_id."""
        d = self._sampler.state_dict()
        d["exact_ok"] = np.bool_(self._exact_ok)
        return d

    def load_state_dict(self, d: dict) -> None:
        self._sampler.load_state_dict(d)
        # pre-summary blobs load with empty summaries: exact mode stays off
        self._exact_ok = ("bk_keys" in d) and bool(d.get("exact_ok", True))
        self._results = None
        self._engines.clear()
        self._lanes = None
        self._recon_keys = self._recon_acc = None
        self._recon_n = 0
        self._recon_discarded = False
        self._host_ids = (set() if self.config.host_id is None
                          else {self.config.host_id})

    @property
    def resident_bytes(self) -> int:
        """Bytes held by the sketches + summaries + remainder (the whole
        service state)."""
        return self._sampler.resident_bytes

    def save_checkpoint(self, ckpt_dir: str | Path, step: int) -> Path:
        """Write the service state through checkpoint.manager (atomic commit,
        retention); composes with a training state living in the same dir."""
        return ckpt_manager.save(ckpt_dir, step, self.state_dict())

    def restore_checkpoint(self, ckpt_dir: str | Path, step: int | None = None) -> int:
        """Load the latest (or a specific) committed step; returns the step."""
        if step is None:
            step = ckpt_manager.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no committed checkpoint under {ckpt_dir}")
        tree = ckpt_manager.restore(ckpt_dir, step, self.state_dict())
        self.load_state_dict(tree)
        return step


# ---------------------------------------------------------------------------
# Multi-tenant serving plane: one stacked bank, one coalesced query engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TenantQuery:
    """One (tenant, statistic, segment[, lane]) request against a bank."""

    tenant: int
    fn: freqfns.FreqFn
    segment: object = None
    l: float | None = None


class MultiTenantStats:
    """N independent per-tenant stat services served from ONE device plane.

    The serving-tier face of ``core.incremental.TenantBank`` (DESIGN.md §10):
    every tenant keeps its own l-grid of fixed-k sketches, but all
    ``n_tenants * |ls|`` sketches live as one stacked pytree — a single
    vmapped/jitted dispatch per ``tick`` advances every tenant with a full
    chunk buffered, and a single ``QueryEngine`` over ``(tenant, l)`` lane
    keys answers a query batch that mixes tenants in ONE device dispatch.

    Per-tenant answers are bit-identical to running ``n_tenants`` standalone
    ``StreamStatsService`` instances over the same streams (property-tested
    in tests/test_serving.py) — the bank changes the dispatch count, not one
    bit of any tenant's sample or estimate.

    Snapshot semantics: queries are answered from the engine built at the
    last ``refresh()`` — the materialized sketches as of that point.  The
    continuous-batching scheduler (stats.scheduler) controls the refresh
    cadence explicitly (``auto_refresh=False``) so ingest dispatch for tick
    t+1 can overlap query evaluation against the tick-t snapshot; direct
    callers get refresh-on-demand by default.
    """

    def __init__(self, config: StatsConfig, *, n_tenants: int,
                 tenant_salts=None):
        self.config = config
        self.n_tenants = int(n_tenants)
        salts = config.salt if tenant_salts is None else tenant_salts
        self._bank = incremental.TenantBank(
            config.ls, n_tenants=n_tenants, k=config.k, chunk=config.chunk,
            salts=salts, host_id=config.host_id,
            evict_every=config.evict_every, backend=config.ingest_backend)
        self._engine: QueryEngine | None = None
        self._engine_tenants: set[int] | None = None  # None = all tenants
        self._stale = True
        self._l_grid_warned = False
        self._pick_l_cache: dict[float, float] = {}

    # -- ingestion ---------------------------------------------------------

    def observe(self, tenant: int, keys, weights=None) -> None:
        """Stage stream elements for one tenant (advanced at the next tick)."""
        self._bank.observe(tenant, keys, weights)
        self._stale = True

    def tick(self) -> int:
        """One stacked ingest dispatch (every tenant with a full buffered
        chunk advances by one chunk); returns the active-tenant count."""
        n = self._bank.tick()
        if n:
            self._stale = True
        return n

    def drain(self) -> int:
        n = self._bank.drain()
        if n:
            self._stale = True
        return n

    def backlog_chunks(self) -> np.ndarray:
        return self._bank.backlog_chunks()

    def n_observed(self, tenant: int) -> int:
        return self._bank.n_observed(tenant)

    # -- query plane -------------------------------------------------------

    @property
    def stale(self) -> bool:
        """True when elements were observed/ticked since the last refresh."""
        return self._stale or self._engine is None

    @property
    def has_engine(self) -> bool:
        return self._engine is not None

    def refresh(self, tenants=None) -> QueryEngine:
        """(Re)build the coalesced query snapshot: ONE device extraction,
        one engine over the (tenant, l) lanes.  This is the only query-plane
        point that synchronizes with in-flight ingest dispatches — the
        scheduler calls it at a controlled cadence.

        ``tenants`` restricts the snapshot to a subset (the scheduler passes
        the tenants of the admitted query batch): only those rows are copied
        off device and materialized as engine lanes — the dominant refresh
        cost when a batch touches few of many tenants.  Queries for a tenant
        outside the subset trigger an automatic widening refresh (their
        lanes then reflect the state at THAT point — per-tenant snapshot
        ages can differ under a partial-refresh policy)."""
        if tenants is None:
            sketches = {(t, float(l)): res
                        for t, per in enumerate(self._bank.finalize_all())
                        for l, res in per.items()}
            self._engine_tenants = None
        else:
            sub = self._bank.finalize_some(tenants)
            sketches = {(t, float(l)): res
                        for t, per in sub.items() for l, res in per.items()}
            self._engine_tenants = set(sub)
        self._engine = QueryEngine(sketches)
        self._stale = False
        return self._engine

    def _ensure_engine(self, auto_refresh: bool, needed: set[int]) -> QueryEngine:
        if self._engine is None or (auto_refresh and self._stale):
            return self.refresh()
        covered = self._engine_tenants
        if covered is not None and not needed <= covered:
            return self.refresh(tenants=covered | needed)
        return self._engine

    def pick_l(self, T: float) -> float:
        cached = self._pick_l_cache.get(T)
        if cached is not None:
            return cached
        l, dist = _nearest_lane(self.config.ls, T)
        if dist > _L_GRID_FACTOR + 1e-9 and not self._l_grid_warned:
            self._l_grid_warned = True
            warnings.warn(_grid_warning(T, l, dist), RuntimeWarning,
                          stacklevel=2)
        self._pick_l_cache[T] = l
        return l

    def _resolve(self, q: TenantQuery) -> Query:
        if not (0 <= q.tenant < self.n_tenants):
            raise ValueError(
                f"tenant {q.tenant} out of range [0, {self.n_tenants})")
        l = q.l
        if l is None:
            kind = q.fn.kind
            if kind in ("cap", "threshold"):
                l = self.pick_l(q.fn.param)
            elif kind == "distinct":
                l = self.pick_l(1.0)
            else:  # total / moment / log1p / custom: weight-proportional
                l = max(self.config.ls)
        return Query(q.fn, q.segment, (int(q.tenant), float(l)))

    def resolve_queries(self, requests) -> list[Query]:
        """Normalize (tenant, fn, segment[, l]) tuples / TenantQuery objects
        into engine-addressed Query objects (lane key = (tenant, l))."""
        qs = [r if isinstance(r, TenantQuery) else TenantQuery(*r)
              for r in requests]
        return [self._resolve(q) for q in qs]

    def query_batch(self, requests, *, auto_refresh: bool = True) -> BatchResult:
        """Answer a batch mixing tenants in one jitted device dispatch.

        Each request is a ``TenantQuery`` or a ``(tenant, fn, segment[, l])``
        tuple.  Answers (and diagnostics) are bit-identical to querying each
        tenant's standalone service."""
        return self.query_batch_async(
            requests, auto_refresh=auto_refresh).result()

    def query_batch_async(self, requests, *,
                          auto_refresh: bool = True) -> PendingBatch:
        """Enqueue the batch's device dispatch without blocking (see
        QueryEngine.query_batch_async) — the scheduler's overlap hook."""
        qs = self.resolve_queries(requests)
        engine = self._ensure_engine(auto_refresh, {q.l[0] for q in qs})
        return engine.query_batch_async(qs)

    def query_cap(self, tenant: int, T: float, segment=None) -> float:
        r = self.query_batch([TenantQuery(tenant, freqfns.cap(T), segment)])
        return float(r.estimates[0])

    def query_distinct(self, tenant: int, segment=None) -> float:
        r = self.query_batch(
            [TenantQuery(tenant, freqfns.distinct(), segment)])
        return float(r.estimates[0])

    def query_total(self, tenant: int, segment=None) -> float:
        r = self.query_batch([TenantQuery(tenant, freqfns.total(), segment)])
        return float(r.estimates[0])

    # -- checkpointing -----------------------------------------------------

    def state_dict(self) -> dict:
        """[T, ...]-stacked flat dict (see TenantBank.state_dict); slices
        per tenant through ``tenant_state_dict`` / manager.restore_slice."""
        return self._bank.state_dict()

    def load_state_dict(self, d: dict) -> None:
        self._bank.load_state_dict(d)
        self._engine = None
        self._stale = True

    def tenant_state_dict(self, tenant: int) -> dict:
        """One tenant in ``StreamStatsService``-loadable form (handoff)."""
        return self._bank.tenant_state_dict(tenant)

    def load_tenant_state_dict(self, tenant: int, d: dict) -> None:
        """Splice one tenant's blob into the bank (join/handoff)."""
        self._bank.load_tenant_state_dict(tenant, d)
        self._engine = None
        self._stale = True

    @property
    def resident_bytes(self) -> int:
        return self._bank.resident_bytes

    def save_checkpoint(self, ckpt_dir: str | Path, step: int) -> Path:
        return ckpt_manager.save(ckpt_dir, step, self.state_dict())

    def restore_checkpoint(self, ckpt_dir: str | Path,
                           step: int | None = None) -> int:
        if step is None:
            step = ckpt_manager.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {ckpt_dir}")
        tree = ckpt_manager.restore(ckpt_dir, step, self.state_dict())
        self.load_state_dict(tree)
        return step
