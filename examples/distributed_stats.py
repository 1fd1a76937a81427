"""Distributed 2-pass sampling across an 8-device mesh: each device samples
its stream shard, states merge via log-depth ppermute butterflies (the
paper's mergeability, §3.1, as jax.lax collectives).  The multi-l program
answers every cap_T of a query grid from ONE launch — chunks are scored once
through the fused multi-l capscore kernel and all lanes reuse the hashes.

    PYTHONPATH=src python examples/distributed_stats.py

``--chaos SEED`` instead replays a seeded fault schedule against the
fault-tolerant sharded ingestion tier (stats/shardtier.py): crashes,
stalls, slow calls, and lost replies fire at scheduled call sites while
the tier ingests the same stream as a fault-free oracle; the run GATES on
the recovered tier's exact answers being bit-identical to the oracle's
(exit 1 on any divergence).  This is the CI chaos leg — a failing seed's
schedule JSON is printed so it can be committed verbatim as a regression.

    PYTHONPATH=src python examples/distributed_stats.py --chaos 11

``--soak SEED`` runs the out-of-process tier (stats/procshard.py): 4 REAL
worker subprocesses behind the supervisor, a seeded chaos schedule realized
physically (SIGKILL / socket partitions / stalls) while a million-element
keyed stream ingests WAL-first, the background exact-merge cadence
refreshing snapshots throughout.  The run polls the flexlb-style status
plane (``ShardTier.status()``) on a fixed cadence into a JSON event log
(``--soak-out``) and GATES on post-soak exact answers being bit-identical
to a fault-free in-process oracle over the same stream (exit 1 otherwise,
printing the committable failing schedule).  ``--soak-time-box`` stops
ingesting new batches past the budget — verification still runs over
whatever was ingested, so a time-boxed CI leg gates the same contract.

    PYTHONPATH=src python examples/distributed_stats.py --soak 7 \
        --soak-elements 1000000 --soak-out soak_events.json
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def run_mesh_demo():
    import jax
    import numpy as np

    from repro.core import continuous as C
    from repro.core import distributed as DD
    from repro.core import freqfns as F
    from repro.core.segments import EMPTY
    from repro.launch.mesh import make_mesh

    EMPTY_ = int(EMPTY)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    rng = np.random.default_rng(0)
    n = len(jax.devices()) * 65536
    keys = (rng.zipf(1.3, size=n) % 100_000).astype(np.int32)
    weights = np.ones(n, np.float32)

    k = 256
    ls = (1.0, 8.0, 64.0)
    fn = DD.make_distributed_two_pass_multi(mesh, ls=ls, salt=3, k=k,
                                            chunk=4096, merge="tree")
    mkeys, mseeds, mw = (np.asarray(a)[0] for a in fn(keys, weights))

    ukeys, cnts = np.unique(keys, return_counts=True)
    for j, (l, T) in enumerate(zip(ls, (1.0, 8.0, 64.0))):
        valid = mkeys[j] != EMPTY_
        order = np.argsort(mseeds[j][valid])
        tau = mseeds[j][valid][order[k]] if valid.sum() > k else np.inf
        sample_w = mw[j][valid][order[:k]]
        est = float(np.sum(np.minimum(sample_w, T)
                           / C.inclusion_prob(sample_w, tau, l)))
        truth = F.exact_statistic(F.cap(T), cnts)
        print(f"cap_{T:<4g} (lane l={l:<4g}) distributed estimate "
              f"{est:12.0f}  truth {truth:12.0f}  "
              f"err {abs(est-truth)/truth:6.2%}")
    print(f"[example] {len(jax.devices())} devices, {n} elements, k={k}, "
          f"|ls|={len(ls)} lanes in one launch, state per device = "
          f"O(k * |ls|)")


def run_chaos_replay(seed, n_shards=3, n_batches=10, batch=300):
    """Seeded chaos replay over the sharded tier, gated on bit-identity.

    Deterministic end to end: the stream comes from the library's
    counter-based hashing, the fault schedule is a pure function of the
    seed, and backoff runs on the injector's virtual clock — a failing
    seed replays identically anywhere.
    """
    import tempfile

    import numpy as np

    from repro.core import freqfns, hashing
    from repro.launch.faults import FaultInjector, FaultSchedule
    from repro.stats.query import Query
    from repro.stats.service import StatsConfig
    from repro.stats.shardtier import ExactUnavailable, ShardTier, TierConfig

    cfg = StatsConfig(k=128, ls=(1.0, 8.0), chunk=64)
    tier_cfg = TierConfig(n_shards=n_shards, checkpoint_every=4,
                          retain_wal=True, auto_recover=True)
    schedule = FaultSchedule.generate(seed, n_shards=n_shards, n_events=12)
    queries = [Query(freqfns.distinct()), Query(freqfns.cap(8.0))]

    eids = np.arange(n_batches * batch, dtype=np.int64)
    keys = ((hashing.hash_combine_np(eids, np.int64(seed)) % np.uint32(500))
            .astype(np.int64) + 1).reshape(n_batches, batch)

    with tempfile.TemporaryDirectory() as d:
        oracle = ShardTier(cfg, TierConfig(**vars(tier_cfg)), d + "/oracle")
        injector = FaultInjector(schedule)
        tier = ShardTier(cfg, TierConfig(**vars(tier_cfg)), d + "/tier",
                         faults=injector)
        for b in keys:
            oracle.ingest(b)
            tier.ingest(b)

        # drain the (finite) schedule with health rounds, then demand exact
        got = None
        for _ in range(20):
            try:
                got = tier.query_batch(queries, mode="exact")
                break
            except ExactUnavailable:
                for _ in range(10):
                    if all(st == "up" for st in tier.check_health().values()):
                        break
        if got is None:
            print(f"[chaos] seed {seed}: tier never reached exact mode; "
                  f"membership={tier.membership()}", file=sys.stderr)
            print(schedule.to_json(), file=sys.stderr)
            return 1
        want = oracle.query_batch(queries, mode="exact")
        if not np.array_equal(got.estimates, want.estimates):
            print(f"[chaos] seed {seed}: BIT-IDENTITY VIOLATED — recovered "
                  f"tier answers {got.estimates} vs fault-free oracle "
                  f"{want.estimates}.  Regression schedule:",
                  file=sys.stderr)
            print(schedule.to_json(), file=sys.stderr)
            return 1
        n_down = sum(1 for _, _, ev, _ in tier.events if ev == "down")
        print(f"[chaos] seed {seed}: {len(injector.fired)} faults fired "
              f"({n_down} shard-down episodes) across {n_shards} shards / "
              f"{n_batches * batch} elements; exact answers bit-identical "
              f"to the fault-free oracle: {got.estimates}")
    return 0


def run_soak(seed, *, n_shards=4, elements=1_000_000, batch=8192,
             time_box_s=None, out_path=None, n_events=24,
             merge_every_n_batches=24, status_every=8):
    """Seeded multi-process soak over the out-of-process tier, gated on
    post-soak exact bit-identity against a fault-free in-process oracle.

    Everything is derived from ``seed``: the keyed stream (counter-based
    hashing), the chaos schedule (PROC_KINDS — crashes are real SIGKILLs,
    partitions sever real sockets), and therefore the entire run.  The
    status plane is sampled every ``status_every`` batches into a JSON
    event log consumable by dashboards (and uploaded by the CI soak job).
    """
    import tempfile

    import numpy as np

    from repro.core import freqfns, hashing
    from repro.launch.faults import (PROC_KINDS, FaultInjector,
                                     FaultSchedule, WallClock)
    from repro.stats.procshard import ProcShardTier, SupervisorConfig
    from repro.stats.query import Query
    from repro.stats.service import StatsConfig
    from repro.stats.shardtier import ShardTier, TierConfig

    cfg = StatsConfig(k=128, ls=(1.0, 8.0), chunk=1024)
    tier_cfg = TierConfig(n_shards=n_shards, checkpoint_every=8,
                          retain_wal=True, auto_recover=True,
                          backoff_base_s=0.02, call_deadline_s=10.0,
                          merge_every_n_batches=merge_every_n_batches)
    n_batches = (elements + batch - 1) // batch
    # spread events across the whole run: call_no up to ~the apply count a
    # single shard sees, tiny latencies (wall clock — stalls really sleep)
    schedule = FaultSchedule.generate(
        seed, n_shards=n_shards, n_events=n_events, kinds=PROC_KINDS,
        max_call_no=max(8, n_batches // 2), max_latency_s=0.05)
    queries = [Query(freqfns.distinct()), Query(freqfns.cap(8.0)),
               Query(freqfns.total())]

    t0 = time.monotonic()
    log_obj = {
        "schema": 1, "seed": seed, "n_shards": n_shards,
        "elements_requested": elements, "batch": batch,
        "merge_every_n_batches": merge_every_n_batches,
        "schedule": json.loads(schedule.to_json()),
        "status_samples": [], "result": None,
    }

    def stream_batch(i):
        eids = np.arange(i * batch, (i + 1) * batch, dtype=np.int64)
        keys = ((hashing.hash_combine_np(eids, np.int64(seed))
                 % np.uint32(1_000_000)).astype(np.int64) + 1)
        return keys

    def finish(rc, detail, got=None, tier=None):
        log_obj["result"] = {
            "ok": rc == 0, "detail": detail,
            "elapsed_s": round(time.monotonic() - t0, 3),
            "estimates": None if got is None else
                         [float(x) for x in got.estimates],
        }
        if tier is not None:
            log_obj["final_status"] = tier.status(events_tail=256)
        if out_path:
            with open(out_path, "w") as f:
                json.dump(log_obj, f, indent=2)
            print(f"[soak] event log -> {out_path}")
        if rc != 0:
            print(f"[soak] seed {seed}: FAILED — {detail}.  "
                  "Committable regression schedule:", file=sys.stderr)
            print(schedule.to_json(), file=sys.stderr)
        return rc

    with tempfile.TemporaryDirectory() as d:
        injector = FaultInjector(schedule, clock=WallClock())
        tier = ProcShardTier(cfg, tier_cfg, d + "/tier", faults=injector,
                             supervisor=SupervisorConfig(
                                 max_restarts=max(8, n_events),
                                 restart_backoff_s=0.05))
        ingested = []
        try:
            for i in range(n_batches):
                if time_box_s is not None and time.monotonic() - t0 > time_box_s:
                    print(f"[soak] time box {time_box_s}s hit after {i} "
                          f"batches ({i * batch} elements); verifying what "
                          "was ingested")
                    break
                b = stream_batch(i)
                tier.ingest(b)
                ingested.append(b)
                if i % status_every == 0:
                    st = tier.status()
                    st["batch_no"] = i
                    st["elapsed_s"] = round(time.monotonic() - t0, 3)
                    log_obj["status_samples"].append(st)
                if i % 4 == 3:
                    tier.check_health()

            # post-soak: converge membership, then demand exact
            for _ in range(30):
                if all(s == "up" for s in tier.slots):
                    break
                tier.check_health()
            if not all(s == "up" for s in tier.slots):
                return finish(1, f"membership never converged: "
                                 f"{tier.membership()}", tier=tier)
            got = tier.query_batch(queries, mode="exact")
            fired = [f"{e.site}:{e.kind}" for e in injector.fired]
            n_down = sum(1 for _, _, ev, _ in tier.events if ev == "down")
            st = tier.status()
        finally:
            tier.close()

        oracle = ShardTier(
            cfg, TierConfig(n_shards=n_shards, checkpoint_every=8,
                            retain_wal=True, fsync=False), d + "/oracle")
        for b in ingested:
            oracle.ingest(b)
        want = oracle.query_batch(queries, mode="exact")
        if not np.array_equal(got.estimates, want.estimates):
            return finish(
                1, f"POST-SOAK BIT-IDENTITY VIOLATED: {got.estimates} vs "
                   f"oracle {want.estimates}", got=got)
        detail = (f"{len(ingested) * batch} elements over {n_shards} worker "
                  f"processes; {len(fired)} faults realized ({n_down} "
                  f"shard-down episodes, {st['merges']['done']} exact "
                  f"merges, {st['merges']['skipped']} skipped); exact "
                  "answers bit-identical to the fault-free oracle")
        log_obj["fired"] = fired
        log_obj["final_status"] = st
        print(f"[soak] seed {seed}: {detail}: {got.estimates}")
        return finish(0, detail, got=got)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chaos", type=int, metavar="SEED", default=None,
                    nargs="+",
                    help="replay seeded fault schedule(s) against the "
                         "sharded tier; exits 1 unless the recovered exact "
                         "answers are bit-identical to a fault-free oracle")
    ap.add_argument("--soak", type=int, metavar="SEED", default=None,
                    help="multi-process soak: real subprocess workers, "
                         "physical chaos, status-plane event log, gated on "
                         "post-soak exact bit-identity")
    ap.add_argument("--soak-elements", type=int, default=1_000_000)
    ap.add_argument("--soak-shards", type=int, default=4)
    ap.add_argument("--soak-time-box", type=float, default=None,
                    metavar="SECONDS",
                    help="stop ingesting past this budget; verification "
                         "still gates over what was ingested")
    ap.add_argument("--soak-out", default=None, metavar="PATH",
                    help="write the status-plane event log JSON here")
    args = ap.parse_args()
    if args.soak is not None:
        sys.exit(run_soak(args.soak, n_shards=args.soak_shards,
                          elements=args.soak_elements,
                          time_box_s=args.soak_time_box,
                          out_path=args.soak_out))
    if args.chaos is not None:
        rc = 0
        for seed in args.chaos:
            rc |= run_chaos_replay(seed)
        sys.exit(rc)
    run_mesh_demo()


if __name__ == "__main__":
    main()
