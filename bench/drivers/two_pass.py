"""The exact distributed two-pass program over a mesh, jobs back to back
in rotation over streams placed on the chips in set-up."""
from __future__ import annotations

import time

import numpy as np

from bench.harness import compare
from bench.harness import reference as R
from bench.harness.drive import Base, Window
from bench.harness.streams import seeded_rng


class Driver(Base):
    def setup(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from repro.core.distributed import make_distributed_two_pass_multi

        (axis, P), = self.config["mesh"].items()
        self.P = int(P)
        n = int(self.config["job_elements"])
        if n % (self.P * self.chunk):
            raise ValueError("job_elements must split into whole chunks")
        self.n = n
        with self.spans("generate"):
            dist, rng = self.keys(), seeded_rng(self.seed, 1)
            self.streams = [dist.draw(rng, n)
                            for _ in range(int(self.mix["streams"]))]
        devs = jax.devices()[:self.P]
        mesh = Mesh(np.asarray(devs), (axis,))
        sh = NamedSharding(mesh, PartitionSpec(axis))
        self.inputs = [(jax.device_put(s, sh),
                        jax.device_put(np.ones(n, np.float32), sh))
                       for s in self.streams]
        self.program = make_distributed_two_pass_multi(
            mesh, ls=tuple(self.ls), salt=self.salt, k=self.k,
            chunk=self.chunk, axis_name=axis)
        self.jobs = []   # (stream index, output handles)
        w = Window(self.mix["inflight"], self.spans)
        self._job(w)
        w.drain()

    def _job(self, w):
        w.admit()
        s = len(self.jobs) % len(self.inputs)
        with self.spans("job"):
            out = self.program(*self.inputs[s])
        self.jobs.append((s, out))
        w.push(out[2])

    def window(self, seconds: float) -> dict:
        w = Window(self.mix["inflight"], self.spans)
        first = len(self.jobs)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._job(w)
        w.drain()
        dt = time.perf_counter() - t0
        n = len(self.jobs) - first
        self.attempted = n
        return {"e2e": {"twopass_eps": n * self.n / dt},
                "counters": {"jobs": n, "chips": self.P,
                             "chunks": n * self.n // self.chunk}}

    def outputs(self) -> list:
        return [(s, tuple(np.asarray(a) for a in out))
                for s, out in self.jobs]

    def release(self):
        self.jobs = self.inputs = self.program = None

    def _eids(self) -> np.ndarray:
        per = self.n // self.P
        return np.concatenate([R.shard_element_ids(p, per)
                               for p in range(self.P)])

    def reference(self, precision: str = "float32") -> dict:
        eids = self._eids()
        out = {}
        for s, keys in enumerate(self.streams):
            st = R.Stream(keys)
            out[s] = {"summaries": st.summaries(
                eids, self.ls, salt=self.salt, cap=self.k + 1,
                precision=precision), "stream": st}
        return out

    def as_outputs(self, ref, precision: str = "float32") -> list:
        """One replica per stream: per lane the summary's keys in key
        order with their seeds and exact totals, rounded to
        ``precision``."""
        q = R.Precision(precision)
        out = []
        for s, r in ref.items():
            cap = self.k + 1
            keys = np.full((1, len(self.ls), cap), R.EMPTY, np.int32)
            seeds = np.full((1, len(self.ls), cap), np.inf, np.float32)
            w = np.zeros((1, len(self.ls), cap), np.float32)
            for j, l in enumerate(self.ls):
                kk, ss = r["summaries"][l]
                o = np.argsort(kk)
                keys[0, j, :len(kk)], seeds[0, j, :len(kk)] = kk[o], ss[o]
                w[0, j, :len(kk)] = q(r["stream"].totals_of(kk[o]))
            out.append((s, (keys, seeds, w)))
        return out

    def numbers(self, got, want: dict) -> dict:
        out = {"summary_key_miss": 0.0, "summary_seed_gap": 0.0,
               "weight_gap": 0.0, "replica_mismatch": 0.0}
        for s, (keys, seeds, weights) in got:
            mism = sum(int(np.sum(a[0] != a[d])) for a in (keys, seeds,
                                                           weights)
                       for d in range(1, a.shape[0]))
            out["replica_mismatch"] = max(out["replica_mismatch"], mism)
            summ = {l: (keys[0, j], seeds[0, j])
                    for j, l in enumerate(self.ls)}
            nums = compare.summary_numbers(summ, want[s]["summaries"])
            for j in range(len(self.ls)):
                kk = keys[0, j]
                live = kk != R.EMPTY
                exact = want[s]["stream"].totals_of(kk[live])
                nums["weight_gap"] = max(
                    nums.get("weight_gap", 0.0),
                    float(np.max(np.abs(weights[0, j][live] - exact),
                                 initial=0.0)))
            for name, v in nums.items():
                out[name] = max(out[name], v)
        return out
