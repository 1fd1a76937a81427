"""Subprocess body for distributed sampler tests.

Run as: python tests/_distributed_runner.py [ndev]
(default 8 host devices; 3 / 6 exercise the non-power-of-two butterfly
fallback).  Prints "OK" on success; assertion errors otherwise.
"""
import os
import sys

NDEV = int(sys.argv[1]) if len(sys.argv) > 1 else 8
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={NDEV}"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import distributed as DD  # noqa: E402
from repro.core import vectorized as V  # noqa: E402
from repro.core.samplers import shard_eids_np  # noqa: E402
from repro.core.segments import EMPTY  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

EMPTY = int(EMPTY)


def _make_mesh():
    return make_mesh((NDEV,), ("data",))


def _reference(keys, w, l, salt):
    """Per-key (min seed, total weight) with the device's shard-hashed eids.

    Scores via the device scorer (V.element_scores, float32) so key sets and
    thresholds are bit-comparable with the shard_map program.
    """
    shard_len = len(keys) // NDEV
    ref_seeds, ref_w = {}, {}
    for s in range(NDEV):
        sk = keys[s * shard_len:(s + 1) * shard_len]
        sw = w[s * shard_len:(s + 1) * shard_len]
        eids = shard_eids_np(s, np.arange(shard_len)).astype(np.int32)
        sc = np.asarray(V.element_scores(
            "continuous", jnp.asarray(sk), jnp.asarray(eids),
            jnp.asarray(sw), jnp.float32(l), jnp.uint32(salt)))
        for key_, s_, w_ in zip(sk.tolist(), sc.tolist(), sw.tolist()):
            ref_seeds[key_] = min(ref_seeds.get(key_, np.inf), s_)
            ref_w[key_] = ref_w.get(key_, 0.0) + w_
    return ref_seeds, ref_w


def _check_lane(skeys, sw, ref_seeds, ref_w, k, label):
    ref_sorted = sorted(ref_seeds.items(), key=lambda kv: kv[1])[: k + 1]
    ref_keys = sorted(k_ for k_, _ in ref_sorted)
    got = sorted(int(x) for x in skeys if x != EMPTY)
    assert got == ref_keys, f"{label}: key sets differ: {got[:5]} vs {ref_keys[:5]}"
    key_order = {int(x): i for i, x in enumerate(skeys.tolist())}
    for key_ in ref_keys:
        np.testing.assert_allclose(sw[key_order[key_]], ref_w[key_], rtol=1e-3)


def main():
    assert len(jax.devices()) == NDEV
    mesh = _make_mesh()

    rng = np.random.default_rng(0)
    n = NDEV * 2048
    keys = (rng.zipf(1.4, size=n) % 3000).astype(np.int32)
    w = np.ones(n, dtype=np.float32)
    k = 64
    salt, l = 9, 5.0

    ref_seeds, ref_w = _reference(keys, w, l, salt)

    # single-l program, both merge topologies (tree falls back to all_gather
    # for non-power-of-two NDEV — same result either way)
    for merge in ("tree", "allgather"):
        fn = DD.make_distributed_two_pass(
            mesh, kind="continuous", l=l, salt=salt, k=k, chunk=512, merge=merge
        )
        skeys, _, sw = (np.asarray(a)[0] for a in fn(keys, w))
        _check_lane(skeys, sw, ref_seeds, ref_w, k, f"single-l merge={merge}")
        print(f"merge={merge} OK")

    # multi-l program: the whole grid in one launch (fused capscore scoring)
    ls = (2.0, 5.0, 64.0)
    fn = DD.make_distributed_two_pass_multi(
        mesh, ls=ls, salt=salt, k=k, chunk=512, merge="tree")
    mkeys, _, mw = (np.asarray(a)[0] for a in fn(keys, w))
    assert mkeys.shape == (len(ls), k + 1), mkeys.shape
    for j, lj in enumerate(ls):
        rs, rw = (ref_seeds, ref_w) if lj == l else _reference(keys, w, lj, salt)
        _check_lane(mkeys[j], mw[j], rs, rw, k, f"multi-l l={lj}")
    # lane scored at the single-l program's l must agree with it exactly
    print("multi-l OK")

    print("OK")


if __name__ == "__main__":
    main()
