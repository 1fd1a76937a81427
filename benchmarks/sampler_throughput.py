"""Sampler throughput: sequential oracle vs TPU-native chunked vs kernel path.

The paper's own evaluation skips runtime ("similar to widely applied distinct
counting algorithms"); for a framework the element-rate IS the product, so we
measure it: elements/second for the oracle (Algorithm 5), the vectorized
fixed-k sampler at several chunk sizes, the capscore elementwise stage alone,
and — the headline — the multi-lane ``update_multi`` ingest across its three
generations:

* ``reference``: the pre-single-sort path (PR 4's oracle, verbatim in src);
* ``sorted``: the single-sort path exactly as it shipped before the fused
  restructure — frozen HERE (legacy primitive forms included) so the
  trajectory point stays measurable after src moved on;
* ``fused``: the current permute-once / score-ordered / reduce-fused path.

Per-stage timings are **jitted** closures timed by **min-of-rounds**
(matching query_throughput.py) — the previous single-shot wall times mostly
measured eager dispatch overhead and machine noise, which is how a ~0.2ms
fused score+aggregate stage was once booked at 17ms.

    PYTHONPATH=src python -m benchmarks.sampler_throughput \
        [--smoke] [--json PATH] [--backend {auto,cpu,gpu,tpu,interpret}] \
        [--check-stamps COMMITTED.json]

``--backend`` pins the kernel routes for the whole run (the CI matrix axis):
``auto`` keeps per-platform dispatch, ``cpu`` forces the XLA routes,
``interpret`` forces the Pallas routes in interpret mode on a CPU host
(tile configs exercised, nothing compiled), ``gpu``/``tpu`` force the
compiled Pallas routes; each SKIPS with a reason when the host platform
does not match (exit 0 — a skipped leg is not a failed leg).

``--json`` emits a machine-readable record (schema_version 4: stamped with
the backend axis and a per-kernel ``{name, backend, compiled, tile_config}``
list — replacing v3's single global ``capscore_interpret`` flag — plus the
reprolint version/retrace budgets the timings were taken under).
``--smoke`` additionally acts as the CI perf-regression gate: the job FAILS
if the fused path measures slower than the reference oracle (per leg, both
paths scored through the leg's kernel route).  ``--check-stamps`` compares
the emitted kernel stamps against a committed record (both normalized
through the v3/v4 reader) and fails on drift.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import incremental as I
from repro.core import samplers as S
from repro.core import vectorized as V
from repro.core.segments import (
    EMPTY, ChunkOrder, chunk_order, scatter_unique, segment_ids,
)
from repro.kernels.capscore.capscore import default_interpret
from repro.kernels.capscore.ops import capscore, capscore_agg, capscore_multi
from repro.kernels.capscore.tiling import resolve_backend, tile_config
from repro.kernels.chunksort import sort_with_perm as chunksort_with_perm

SCHEMA_VERSION = 4

#: kernel entry points stamped into schema-v4 records
KERNEL_NAMES = ("capscore", "capscore_multi", "capscore_agg", "chunksort")

BACKEND_AXES = ("auto", "cpu", "gpu", "tpu", "interpret")


def resolve_backend_axis(axis: str):
    """Map a --backend axis value onto (kernel_backend, skip_reason).

    ``kernel_backend`` is the dispatch route handed to SamplerSpec.backend /
    the kernel ops: None (auto), 'xla', or 'pallas'.  A non-None
    ``skip_reason`` means this leg cannot run on the current host (compiled
    legs on a CPU runner) and the caller should exit 0 without timing.

    The interpret leg runs the Pallas routes on a CPU host, where interpret
    mode is the platform's default, so every Pallas route runs the real tile
    configs through the interpreter.
    """
    plat = jax.default_backend()
    if axis == "auto":
        return None, None
    if axis == "cpu":
        if plat != "cpu":
            return None, f"cpu (XLA-route) leg requested on a {plat} host"
        return "xla", None
    if axis == "interpret":
        if plat != "cpu":
            return None, (f"interpret leg runs on a cpu host only (found "
                          f"{plat!r}: Pallas compiles there)")
        return "pallas", None
    if axis in ("gpu", "tpu"):
        if plat != axis:
            return None, (f"{axis} leg needs a {axis} host to compile its "
                          f"Pallas route (found {plat!r})")
        return "pallas", None
    raise ValueError(f"unknown --backend axis {axis!r}: use one of {BACKEND_AXES}")


def kernel_stamps(kernel_backend: str | None = None):
    """Schema-v4 per-kernel stamps: dispatch route, compiled?, tile config.

    Deterministic given (host platform, backend axis) — the
    CI interpret leg diffs these against the committed snapshot."""
    route = resolve_backend(kernel_backend)
    interp = bool(default_interpret())
    out = []
    for name in KERNEL_NAMES:
        if route == "pallas":
            cfg = tile_config(name)
            out.append({"name": name, "backend": "pallas",
                        "compiled": bool(cfg.compiled and not interp),
                        "tile_config": cfg.describe()})
        else:
            out.append({"name": name, "backend": "xla", "compiled": False,
                        "tile_config": None})
    return out


def kernel_stamps_from_record(record: dict):
    """Normalize a BENCH_ingest record's kernel stamps across schemas.

    v4 records carry the per-kernel list verbatim; v3 records carried one
    global ``capscore_interpret`` flag and predate the chunksort kernel, so
    they normalize to the equivalent per-kernel entries (no tile configs).
    Keeping this reader v3-capable is what lets benchmarks/run.py and
    --check-stamps consume historical records unchanged."""
    if int(record.get("schema_version", 0)) >= 4:
        return record["kernels"]
    interp = bool(record.get("capscore_interpret", True))
    plat = record.get("backend", "cpu")
    route = "pallas" if plat == "tpu" else "xla"
    compiled = route == "pallas" and not interp
    return [{"name": n, "backend": route, "compiled": compiled,
             "tile_config": None}
            for n in ("capscore", "capscore_multi", "capscore_agg")]


def reprolint_stamp():
    """Compile-count context for the perf numbers (DESIGN.md §11.3): the
    reprolint version and the committed retrace budgets these timings were
    taken under. Best-effort — absent files just leave the stamp empty."""
    root = Path(__file__).resolve().parents[1]
    stamp: dict = {}
    try:
        m = re.search(r'__version__\s*=\s*"([^"]+)"',
                      (root / "tools/reprolint/__init__.py").read_text())
        if m:
            stamp["reprolint_version"] = m.group(1)
        stamp["retrace_budgets"] = json.loads(
            (root / "tools/reprolint/reprolint_traces.json").read_text()
        )["budgets"]
    except (OSError, KeyError, ValueError):
        pass
    return stamp


def bench(fn, *args, reps=3, **kw):
    """Min-of-rounds timing: the machine-capability number on shared boxes
    (a single-shot wall time is dominated by whoever else runs that second).
    """
    out = fn(*args, **kw)  # warm/compile
    jax.tree.map(lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.tree.map(lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, out)
        best = min(best, time.perf_counter() - t0)
    return best


def _zipf(n, n_keys=50000, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.zipf(1.3, size=n) % n_keys).astype(np.int64)


# ---------------------------------------------------------------------------
# The pre-fuse single-sort ingest step, FROZEN (the PR's "before" point).
#
# src keeps only the pre-single-sort reference as a living oracle; the
# single-sort generation is reconstructed here verbatim — including the
# primitive forms it ran on (scatter-form unique keys, iota-query
# searchsorted compaction, full-width run interleave, top_k eviction
# threshold), all of which the fused restructure replaced — so ``sorted_eps``
# keeps measuring the same computation across PRs.
# ---------------------------------------------------------------------------

_INF = jnp.float32(jnp.inf)


def _legacy_chunk_order(keys):
    perm = jnp.argsort(keys, stable=True)
    ks = keys[perm]
    seg, _ = segment_ids(ks)
    ukeys, _ = scatter_unique(ks, seg, 0.0)
    return ChunkOrder(ks=ks, perm=perm, seg=seg, ukeys=ukeys)


def _legacy_compact_valid(valid, *arrays, fills):
    n = valid.shape[0]
    cs = jnp.cumsum(valid)
    src = jnp.clip(jnp.searchsorted(cs, jnp.arange(1, n + 1), side="left"),
                   0, n - 1)
    keep = jnp.arange(n) < cs[-1]
    return tuple(jnp.where(keep, a[src], jnp.asarray(fill, dtype=a.dtype))
                 for a, fill in zip(arrays, fills))


def _legacy_merge_sorted_runs_gather(a, b):
    na, nb = a.shape[0], b.shape[0]
    pos_b = jnp.arange(nb) + jnp.searchsorted(a, b, side="right")
    p = jnp.arange(na + nb)
    nb_before = jnp.searchsorted(pos_b, p, side="right")
    ib = jnp.clip(nb_before - 1, 0, nb - 1)
    from_b = (nb_before > 0) & (pos_b[ib] == p)
    ia = jnp.clip(p - nb_before, 0, na - 1)
    return from_b, ia, ib


def _legacy_merge_table_sorted(state, agg):
    cap = state.keys.shape[0]
    C = agg.ukeys.shape[0]
    a_keys, b_keys = state.keys, agg.ukeys
    a_live = a_keys != EMPTY
    b_live = b_keys != EMPTY
    loc_ab = jnp.clip(jnp.searchsorted(b_keys, a_keys), 0, C - 1)
    hit_a = (b_keys[loc_ab] == a_keys) & a_live
    counts_a = state.counts + jnp.where(hit_a, agg.w_total[loc_ab], 0.0)
    kb_a = jnp.minimum(state.kb, jnp.where(hit_a, agg.kb[loc_ab], _INF))
    sd_a = jnp.minimum(state.seed, jnp.where(hit_a, agg.min_score[loc_ab], _INF))
    loc_ba = jnp.clip(jnp.searchsorted(a_keys, b_keys), 0, cap - 1)
    in_table = a_keys[loc_ba] == b_keys
    new = b_live & ~in_table & agg.entered
    newk, newcnt, newkb, newsd = _legacy_compact_valid(
        new, b_keys, agg.contrib, agg.kb, agg.min_score,
        fills=(EMPTY, 0.0, _INF, _INF))
    from_b, ia, ib = _legacy_merge_sorted_runs_gather(a_keys, newk)
    pick = lambda av, bv: jnp.where(from_b, bv[ib], av[ia])
    return (pick(a_keys, newk)[:cap], pick(counts_a, newcnt)[:cap],
            pick(kb_a, newkb)[:cap], pick(sd_a, newsd)[:cap])


def _legacy_evict_table(table, *, k, l, salt, max_evict):
    valid, z, entry_thresh, ex, inv_l = V._evict_z(
        table.keys, table.counts, table.kb, table.tau, l, salt, table.step)
    n = table.keys.shape[0]
    delta = jnp.maximum(jnp.sum(valid.astype(jnp.int32)) - k, 0)
    z_top = jax.lax.top_k(z, min(int(max_evict), n))[0]
    tau_star = jnp.where(delta > 0, z_top[jnp.maximum(delta - 1, 0)], table.tau)
    keys_e, counts_e, kb_e, seed_e, tau_e = V._evict_apply(
        table.keys, table.counts, table.kb, table.seed, table.tau, l, delta,
        tau_star, valid, z, entry_thresh, ex, inv_l)
    keys_c, counts_c, kb_c, seed_c = _legacy_compact_valid(
        keys_e != EMPTY, keys_e, counts_e, kb_e, seed_e,
        fills=(EMPTY, 0.0, _INF, _INF))
    return V.TableState(keys_c, counts_c, kb_c, seed_c, tau_e, table.step,
                        table.overflow)


def _update_multi_sorted_impl(state, keys, weights, spec):
    """The single-sort multi-l batch update, as shipped pre-fuse."""
    chunk = spec.chunk
    kc = keys.reshape(-1, chunk)
    wc = weights.reshape(-1, chunk)
    cap_bk = state.bk_keys.shape[1]

    def body(carry, xs):
        table, bk_keys, bk_seeds, pos = carry
        ck, cw = xs
        eids = spec.eids(pos)
        score, delta, entry, kb = capscore_multi(ck, eids, cw, state.l,
                                                 table.tau, state.salt)
        order = _legacy_chunk_order(ck)

        def lane_merge(tab, sc, dl, en, kb_l):
            agg = V.aggregate_continuous_scored(ck, cw, sc, dl, en, kb_l, order)
            keys_c, counts_c, kb_c, seed_c = _legacy_merge_table_sorted(tab, agg)
            return V.TableState(keys_c, counts_c, kb_c, seed_c, tab.tau,
                                tab.step + 1, tab.overflow)

        table = jax.vmap(lane_merge)(table, score, delta, entry, kb)
        table = jax.vmap(
            lambda tab, l: _legacy_evict_table(tab, k=spec.k, l=l,
                                               salt=state.salt, max_evict=chunk)
        )(table, state.l)
        bk_keys, bk_seeds = V.pass1_step_multi(
            (bk_keys, bk_seeds), ck, score, cap=cap_bk, order=order)
        return (table, bk_keys, bk_seeds, pos + chunk), None

    (table, bkk, bks, pos), _ = jax.lax.scan(
        body, (state.table, state.bk_keys, state.bk_seeds, state.n_seen),
        (kc, wc))
    return I.SamplerState(table, pos, state.l, state.salt, bkk, bks)


# reprolint: disable=RPL003 -- bench harness: min-of-rounds timing re-feeds
# the same input state every round, so its buffers must stay alive
_update_multi_sorted = functools.partial(
    jax.jit, static_argnames=("spec",))(_update_multi_sorted_impl)


# ---------------------------------------------------------------------------
# Multi-lane ingest: fused vs pre-fuse single-sort vs pre-single-sort
# ---------------------------------------------------------------------------


def _stage_timings(L, k, chunk, reps=5, backend=None):
    """Min-of-rounds timings of each JITTED pipeline stage, fused vs legacy.

    Every stage is compiled before timing; what remains is the device compute
    the scan body actually pays.  The share of the chunk budget spent on
    score+aggregate is reported against one full fused chunk step.
    ``backend`` pins every kernel route (score, aggregate, chunk sort) to one
    leg of the CI matrix; None keeps per-platform dispatch.
    """
    ls = jnp.asarray(np.geomspace(1.0, 2.0 ** (L - 1), L), jnp.float32)
    ck = jnp.asarray(_zipf(chunk, seed=3)[:chunk], jnp.int32)
    cw = jnp.ones(chunk, jnp.float32)
    eids = jnp.arange(chunk, dtype=jnp.int32)
    salt = jnp.uint32(1)

    # a warmed, representative state: ingest a few chunks so tau is finite
    state, spec = I.init_multi_state(np.asarray(ls), k=k, chunk=chunk, salt=1,
                                     backend=backend)
    warm = _zipf(chunk * 4, seed=5).astype(np.int32)
    state = I.update_multi(state, warm, np.ones(len(warm), np.float32), spec,
                           donate=False)
    table = state.table
    cap_bk = state.bk_keys.shape[1]

    j_order = jax.jit(lambda c, e, w: chunk_order(c, e, w,
                                                  sort_backend=backend))
    order = j_order(ck, eids, cw)
    j_sort = jax.jit(lambda c: chunksort_with_perm(c, backend=backend))
    j_sort(ck)
    j_score = jax.jit(lambda: capscore_multi(ck, eids, cw, ls, table.tau, salt,
                                             backend=backend))
    score = j_score()[0]
    j_fused = jax.jit(lambda: capscore_agg(order.ks, order.eids, order.ws,
                                           order.seg, ls, table.tau, salt,
                                           backend=backend))
    cols = j_fused()

    def agg_shared():
        s, d, e, kb = capscore_multi(ck, eids, cw, ls, table.tau, salt,
                                     backend=backend)
        return jax.vmap(
            lambda s_, d_, e_, b_: V.aggregate_continuous_scored(
                ck, cw, s_, d_, e_, b_, order)
        )(s, d, e, kb)

    j_agg_shared = jax.jit(agg_shared)

    def lane_aggs():
        w_total, entered, contrib, kb_min, min_score = cols
        return jax.vmap(lambda en, ct, kbm, ms: V.ChunkAgg(
            ukeys=order.ukeys, w_total=w_total, entered=en, contrib=ct,
            kb=kbm, min_score=ms))(entered, contrib, kb_min, min_score)

    aggs = jax.jit(lane_aggs)()

    j_merge = jax.jit(lambda t, a: jax.vmap(V.fixed_k_merge)(t, a))
    merged = j_merge(table, aggs)
    j_evict_rank = jax.jit(lambda t: jax.vmap(
        lambda tt, l: V.evict_table(tt, k=k, l=l, salt=salt, max_evict=chunk,
                                    select="rank"))(t, ls))
    j_evict_topk = jax.jit(lambda t: jax.vmap(
        lambda tt, l: V.evict_table(tt, k=k, l=l, salt=salt, max_evict=chunk,
                                    select="topk"))(t, ls))

    bkk, bks = jax.vmap(V.summary_to_keysorted)(state.bk_keys, state.bk_seeds)
    j_pass1_fold = jax.jit(lambda b1, b2: jax.vmap(
        lambda sk, ss, mn: V.pass1_fold_keysorted(sk, ss, order.ukeys, mn, cap_bk)
    )(b1, b2, cols[4]))
    j_pass1_legacy = jax.jit(lambda b1, b2: V.pass1_step_multi(
        (b1, b2), ck, score, cap=cap_bk, order=order))

    # one whole fused chunk step — the budget the shares are measured against
    j_chunk = functools.partial(I.update_multi, donate=False)

    stages = {
        "order(1 sort + pre-gather)": lambda: j_order(ck, eids, cw),
        "sort-only[chunk-order route]": lambda: j_sort(ck),
        "score+aggregate[fused capscore_agg]": j_fused,
        "score+aggregate[legacy: score, gather x4L]": j_agg_shared,
        "merge[sorted-runs, L lanes]": lambda: j_merge(table, aggs),
        "evict[rank-select]": lambda: j_evict_rank(merged),
        "evict[legacy top_k]": lambda: j_evict_topk(merged),
        "pass1[key-sorted fold]": lambda: j_pass1_fold(bkk, bks),
        "pass1[legacy seed-sorted merge]": lambda: j_pass1_legacy(state.bk_keys, state.bk_seeds),
        "full chunk step[fused]": lambda: j_chunk(state, ck, cw, spec),
    }
    out = {name: bench(fn, reps=reps) * 1e3 for name, fn in stages.items()}
    chunk_ms = out["full chunk step[fused]"]
    out["score_agg_share_of_chunk"] = (
        out["score+aggregate[fused capscore_agg]"] / chunk_ms if chunk_ms else 0.0)
    return out


def multi_lane_ingest(L=8, k=4096, chunk=4096, n_chunks=4, reps=3, stage_reps=5,
                      backend=None):
    """Elements/s of the three ingest generations, min-of-rounds interleaved.

    ``backend`` pins both live paths (reference oracle and fused) to one
    kernel route so the perf gate compares like-for-like; the frozen
    pre-fuse ``sorted`` path keeps its shipped auto dispatch.
    """
    ls = np.geomspace(1.0, 2.0 ** (L - 1), L)
    n = n_chunks * chunk
    keys = _zipf(n, seed=11).astype(np.int32)
    w = np.ones(n, np.float32)

    state, spec = I.init_multi_state(ls, k=k, chunk=chunk, salt=2,
                                     backend=backend)
    # warm tau so steady-state (evicting) chunks are what gets timed
    state = I.update_multi(state, keys, w, spec, donate=False)
    kj, wj = jnp.asarray(keys), jnp.asarray(w)

    paths = {
        "reference": lambda: I.update_multi(state, keys, w, spec, donate=False,
                                            reference=True),
        "sorted": lambda: _update_multi_sorted(state, kj, wj, spec),
        "fused": lambda: I.update_multi(state, keys, w, spec, donate=False),
    }
    for fn in paths.values():  # compile before any timing
        fn()
    best = {name: float("inf") for name in paths}
    for _ in range(reps):  # interleave rounds so machine noise hits all paths
        for name, fn in paths.items():
            t0 = time.perf_counter()
            out = fn()
            jax.tree.map(lambda x: x.block_until_ready(), jax.tree.leaves(out))
            best[name] = min(best[name], time.perf_counter() - t0)

    stages = _stage_timings(L, k, chunk, reps=stage_reps, backend=backend)
    return {
        "L": L, "k": k, "chunk": chunk, "n": n,
        "reference_eps": n / best["reference"],
        "sorted_eps": n / best["sorted"],
        "fused_eps": n / best["fused"],
        "speedup_vs_reference": best["reference"] / best["fused"],
        "speedup_vs_sorted": best["sorted"] / best["fused"],
        "score_agg_share": stages["score_agg_share_of_chunk"],
        "stages_ms": stages,
    }


def print_ingest(res):
    print(f"\n-- multi-lane ingest (L={res['L']}, k={res['k']}, "
          f"chunk={res['chunk']}, n={res['n']}):")
    print(f"{'path':42s} {'elements/s':>14s}")
    print(f"{'update_multi[reference: pre-single-sort]':42s} {res['reference_eps']:14.0f}")
    print(f"{'update_multi[sorted: pre-fuse, frozen]':42s} {res['sorted_eps']:14.0f}")
    print(f"{'update_multi[fused score-in-key-order]':42s} {res['fused_eps']:14.0f}")
    print(f"speedup vs reference: {res['speedup_vs_reference']:.2f}x   "
          f"vs pre-fuse sorted: {res['speedup_vs_sorted']:.2f}x")
    print(f"\n{'per-stage (jitted, min-of-rounds)':42s} {'ms':>10s}")
    for name, ms in res["stages_ms"].items():
        if name == "score_agg_share_of_chunk":
            print(f"{'score+aggregate share of chunk step':42s} {ms:10.1%}")
        else:
            print(f"{name:42s} {ms:10.3f}")


def main(n=200_000, k=256, l=20.0, ingest_kw=None, json_path=None,
         perf_gate=False, backend_axis="auto", kernel_backend=None):
    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.3, size=n) % 50000).astype(np.int64)
    rows = []

    t = bench(lambda: S.alg5_fixed_k_continuous(keys[:20000], None, k, l=l, salt=1), reps=1)
    rows.append(("alg5_sequential_oracle", 20000 / t, t * 1e6 / 20000))

    for chunk in (1024, 4096, 16384):
        t = bench(V.sample_fixed_k, keys, None, k=k, l=l, salt=1, chunk=chunk)
        rows.append((f"vectorized_fixed_k_chunk{chunk}", n / t, t * 1e6 / n))

    t = bench(V.sample_two_pass, keys, None, k=k, l=l, salt=1, chunk=4096)
    rows.append(("vectorized_two_pass", n / t, t * 1e6 / n))

    m = min(131072, n)
    kk = jnp.asarray(keys[:m], jnp.int32)
    ee = jnp.arange(m, dtype=jnp.int32)
    ww = jnp.ones(m, jnp.float32)
    j_cap = jax.jit(lambda: capscore(kk, ee, ww, l, 0.01, 3, backend="xla"))
    t = bench(j_cap)
    rows.append(("capscore_stage_xla", m / t, t * 1e6 / m))

    print(f"{'path':36s} {'elements/s':>14s} {'us/element':>12s}")
    for name, eps, us in rows:
        print(f"{name:36s} {eps:14.0f} {us:12.4f}")

    ingest = multi_lane_ingest(backend=kernel_backend, **(ingest_kw or {}))
    print_ingest(ingest)

    if json_path:
        record = {
            "bench": "sampler_throughput",
            "schema_version": SCHEMA_VERSION,
            "backend": jax.default_backend(),
            "backend_axis": backend_axis,
            "kernels": kernel_stamps(kernel_backend),
            "reprolint": reprolint_stamp(),
            "single_lane": {name: {"elements_per_s": eps} for name, eps, _ in rows},
            "multi_lane_ingest": {
                k_: v for k_, v in ingest.items() if k_ != "stages_ms"
            },
            "multi_lane_stages_ms": ingest["stages_ms"],
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(f"\n[sampler_throughput] wrote {json_path}")

    if perf_gate and ingest["speedup_vs_reference"] < 1.0:
        print(f"\nPERF REGRESSION: fused ingest measured "
              f"{ingest['speedup_vs_reference']:.2f}x the reference oracle "
              f"(must be >= 1.0x)", file=sys.stderr)
        sys.exit(1)
    return rows, ingest


def check_stamps(committed_path, kernel_backend):
    """Diff the committed record's kernel stamps against this host's.

    Both sides go through the v3/v4 reader so historical records still load;
    a mismatch (route drift, tile-config drift, stale snapshot) exits 1."""
    with open(committed_path) as f:
        committed = kernel_stamps_from_record(json.load(f))
    emitted = kernel_stamps(kernel_backend)
    if committed != emitted:
        print(f"\nKERNEL STAMP DRIFT vs {committed_path}:", file=sys.stderr)
        print(f"  committed: {json.dumps(committed)}", file=sys.stderr)
        print(f"  emitted:   {json.dumps(emitted)}", file=sys.stderr)
        print("  regenerate the snapshot with: python -m "
              "benchmarks.sampler_throughput --smoke --backend interpret",
              file=sys.stderr)
        sys.exit(1)
    print(f"[sampler_throughput] kernel stamps match {committed_path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small L/k/chunk, emits JSON, enforces "
                         "the fused>=reference perf gate)")
    ap.add_argument("--json", default="BENCH_ingest.json",
                    help="machine-readable output path")
    ap.add_argument("--backend", default="auto", choices=BACKEND_AXES,
                    help="kernel-route leg: auto dispatch, forced xla (cpu), "
                         "forced Pallas interpret, or compiled gpu/tpu "
                         "(skips with a reason off-platform)")
    ap.add_argument("--check-stamps", default=None, metavar="PATH",
                    help="after the run, fail if PATH's kernel stamps differ "
                         "from this leg's")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    kernel_backend, skip = resolve_backend_axis(args.backend)
    if skip is not None:
        print(f"[sampler_throughput] SKIP --backend {args.backend}: {skip}")
        sys.exit(0)
    common = dict(json_path=args.json, backend_axis=args.backend,
                  kernel_backend=kernel_backend)
    if args.smoke:
        main(n=50_000, k=128,
             ingest_kw=dict(L=4, k=512, chunk=1024, n_chunks=2, reps=3,
                            stage_reps=2),
             perf_gate=True, **common)
    else:
        main(n=2_000_000 if args.full else 200_000,
             ingest_kw=dict(L=8, k=4096, chunk=4096), **common)
    if args.check_stamps:
        check_stamps(args.check_stamps, kernel_backend)
