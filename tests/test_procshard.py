"""Out-of-process shard tier (stats.procshard): REAL subprocess workers.

These tests are the repo's only ones that spawn worker subprocesses (each
pays an interpreter+jax import, ~10-20s), so they are few and each one
covers several contract points at once:

* ``test_sigkill_mid_ingest_recovery_bit_identity`` — the headline
  acceptance criterion: SIGKILL a real worker mid-stream, let the
  supervisor restart+recover it, and pin the exact two-pass answers
  ``np.array_equal`` to a fault-free in-process oracle over the same
  stream.  Also exercises the restart budget (a second kill exhausts
  ``max_restarts=1`` and the tier degrades instead of hanging) and the
  process-mode status plane (pid/restart facts).

* ``test_chaos_schedule_realized_against_processes`` — a seeded
  PROC_KINDS schedule (crash/stall/slow/lost_reply/partition) realized
  physically: kills are SIGKILLs, partitions sever the actual socket (the
  worker reconnects with state intact).  Post-chaos, after health rounds
  converge, exact answers are bit-identical to the oracle.
"""
import numpy as np
import pytest

from repro.core import freqfns, hashing
from repro.launch.faults import (
    PROC_KINDS,
    FaultInjector,
    FaultSchedule,
    WallClock,
)
from repro.stats.procshard import ProcShardTier, SupervisorConfig
from repro.stats.query import Query
from repro.stats.service import StatsConfig
from repro.stats.shardtier import ShardTier, TierConfig

CFG = StatsConfig(k=64, ls=(1.0, 8.0), chunk=32)

QUERIES = [Query(freqfns.cap(8.0)), Query(freqfns.distinct()),
           Query(freqfns.total())]


def _stream(n, lo, hi, stream_id):
    idx = np.arange(n, dtype=np.int64)
    h = hashing.hash_combine_np(idx, np.int64(stream_id), np.int64(77))
    keys = (lo + (h % np.uint32(hi - lo)).astype(np.int64)).astype(np.int32)
    hw = hashing.hash_combine_np(idx, np.int64(stream_id), np.int64(78))
    weights = (1.0 + hashing.uniform01_np(hw) * 3.0).astype(np.float32)
    return keys, weights


def _oracle_exact(batches, root):
    """Fault-free in-process tier over the same stream: the bit-identity
    reference (same shard count/salt => same partition, same host_ids)."""
    tier = ShardTier(CFG, TierConfig(n_shards=2, checkpoint_every=4,
                                     retain_wal=True, fsync=False), root)
    for keys, weights in batches:
        tier.ingest(keys, weights)
    return tier.query_batch(QUERIES, mode="exact")


def _proc_tier(root, *, faults=None, max_restarts=3,
               merge_every_n_batches=None):
    tc = TierConfig(n_shards=2, checkpoint_every=4, retain_wal=True,
                    fsync=False, backoff_base_s=0.02, call_deadline_s=5.0,
                    merge_every_n_batches=merge_every_n_batches)
    sup = SupervisorConfig(max_restarts=max_restarts,
                           restart_backoff_s=0.05)
    return ProcShardTier(CFG, tc, root, faults=faults, supervisor=sup)


def test_sigkill_mid_ingest_recovery_bit_identity(tmp_path):
    batches = [_stream(200, 0, 500, i) for i in range(6)]
    with _proc_tier(tmp_path / "proc", max_restarts=1) as tier:
        for keys, weights in batches[:3]:
            tier.ingest(keys, weights)
        # REAL SIGKILL mid-stream; the next apply discovers the corpse,
        # marks the shard down, and auto-recovery respawns + replays
        tier.kill_shard(1)
        for keys, weights in batches[3:5]:
            tier.ingest(keys, weights)
        tier.check_health()
        for keys, weights in batches[5:]:
            tier.ingest(keys, weights)
        res = tier.query_batch(QUERIES, mode="exact")
        assert res.mode == "exact" and not res.degraded

        st = tier.status()
        s1 = st["shards"][1]
        assert s1["state"] == "up" and s1["alive"]
        assert s1["restarts"] == 1 and isinstance(s1["pid"], int)
        assert s1["applied_seq"] == 6  # caught all the way up
        assert any(e[2] == "recovered" for e in st["events"])

        oracle = _oracle_exact(batches, tmp_path / "oracle")
        assert np.array_equal(res.estimates, oracle.estimates)
        assert np.array_equal(res.variances, oracle.variances)

        # restart budget: max_restarts=1 is spent — a second SIGKILL must
        # leave the slot down and auto-mode queries DEGRADED, not raising
        tier.kill_shard(1)
        tier.check_health()
        assert tier.slots[1] == "down"
        deg = tier.query_batch(QUERIES, mode="auto")
        assert deg.degraded and deg.mode == "approx"
        total = sum(tier._routed)
        assert deg.coverage == pytest.approx(tier._routed[0] / total)
        assert np.all(np.isfinite(deg.estimates))


def test_chaos_schedule_realized_against_processes(tmp_path):
    # Real-process chaos: tiny latencies (wall clock!) and every PROC kind,
    # including partition (socket sever + reconnect) and crash (SIGKILL).
    sched = FaultSchedule.generate(
        29, n_shards=2, n_events=10, kinds=PROC_KINDS,
        max_call_no=6, max_latency_s=0.05)
    assert sched.events, "seed 29 must produce events"
    faults = FaultInjector(sched, clock=WallClock())
    batches = [_stream(150, 0, 400, 100 + i) for i in range(8)]
    with _proc_tier(tmp_path / "proc", faults=faults,
                    max_restarts=8) as tier:
        for i, (keys, weights) in enumerate(batches):
            tier.ingest(keys, weights)
            if i % 2 == 1:
                tier.check_health()
        # converge: bounded health rounds until every shard is back up
        for _ in range(20):
            if all(s == "up" for s in tier.slots):
                break
            tier.check_health()
        assert all(s == "up" for s in tier.slots)
        res = tier.query_batch(QUERIES, mode="exact")
        # the schedule really fired, physically
        fired = {e.kind for e in faults.fired}
        assert fired, "chaos schedule never fired"
    oracle = _oracle_exact(batches, tmp_path / "oracle")
    assert np.array_equal(res.estimates, oracle.estimates)


def test_refuses_worker_processes_when_coordinator_holds_tpu(monkeypatch,
                                                             tmp_path):
    """On a TPU host the coordinator already holds the chip every worker
    process would need: construction fails loudly, naming the rule, before
    any worker is spawned — no restart loop into degraded answers."""
    from repro.stats import procshard

    spawned = []
    monkeypatch.setattr(procshard.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(procshard.ShardProcess, "spawn",
                        lambda self, *a, **k: spawned.append(self.shard_id))
    with pytest.raises(RuntimeError, match="one process per chip"):
        ProcShardTier(CFG, TierConfig(n_shards=2, fsync=False),
                      tmp_path / "tier")
    assert spawned == []
