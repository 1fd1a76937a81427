"""One ``StreamStatsService`` fed by one closed-loop client at saturation:
batches drawn from a seeded pool, at most ``inflight`` in flight."""
from __future__ import annotations

import time

import numpy as np

from bench.harness import compare
from bench.harness import reference as R
from bench.harness.drive import Base, Marker, Window, stats_config
from bench.harness.streams import seeded_rng


class Driver(Base):
    def setup(self):
        from repro.stats.service import StreamStatsService

        batch = int(self.mix["batch"])
        with self.spans("generate"):
            dist, rng = self.keys(), seeded_rng(self.seed, 1)
            self.pool = [dist.draw(rng, batch)
                         for _ in range(int(self.mix["pool_batches"]))]
        self.svc = StreamStatsService(stats_config(self.svc_cfg))
        self.marker = Marker()
        self.fed = 0   # batches fed so far: batch i is pool[i % len(pool)]
        w = Window(self.mix["inflight"], self.spans)
        for _ in range(int(self.mix["warmup_batches"])):
            self._feed(w)
        w.drain()

    def _feed(self, w):
        w.admit()
        with self.spans("observe"):
            self.svc.observe(self.pool[self.fed % len(self.pool)])
        w.push(self.marker())
        self.fed += 1

    def window(self, seconds: float) -> dict:
        w = Window(self.mix["inflight"], self.spans)
        first = self.fed
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._feed(w)
        w.drain()
        dt = time.perf_counter() - t0
        n = self.fed - first
        self.attempted = n
        batch = int(self.mix["batch"])
        return {"e2e": {"ingest_eps": n * batch / dt},
                "counters": {"program": "update_multi", "batches": n,
                             "chunks": n * batch // self.chunk,
                             "lanes": len(self.ls), "chunk": self.chunk}}

    def outputs(self) -> dict:
        sk = self.svc.sketches()
        sd = self.svc.state_dict()
        samples = {l: (np.asarray(sk[l].keys), np.asarray(sk[l].counts),
                       float(sk[l].tau)) for l in self.ls}
        summ = {l: (np.asarray(sd["bk_keys"][j]), np.asarray(sd["bk_seeds"][j]))
                for j, l in enumerate(self.ls)}
        return {"samples": samples, "summaries": summ}

    def release(self):
        self.svc = self.marker = None

    def _stream(self):
        return np.concatenate([self.pool[i % len(self.pool)]
                               for i in range(self.fed)])

    def reference(self, precision: str = "float32") -> dict:
        keys = self._stream()
        fk = R.FixedK(self.ls, k=self.k, chunk=self.chunk, salt=self.salt,
                      precision=precision)
        fk.feed(keys)
        summ = R.Stream(keys).summaries(np.arange(len(keys)), self.ls,
                                        salt=self.salt, cap=self.k + 1,
                                        precision=precision)
        return {"samples": fk.samples(), "summaries": summ}

    def numbers(self, got: dict, want: dict) -> dict:
        out = compare.sample_numbers(got["samples"], want["samples"])
        out.update(compare.summary_numbers(got["summaries"],
                                           want["summaries"]))
        return out
