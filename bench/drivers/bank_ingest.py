"""The tenant bank (``MultiTenantStats``) behind ``StatsScheduler``, one
closed-loop client per tenant: each round every tenant submits its next
request and one scheduler step admits and ticks them."""
from __future__ import annotations

import time

import numpy as np

from bench.harness import compare
from bench.harness import reference as R
from bench.harness.drive import Base, Marker, Window, stats_config
from bench.harness.streams import seeded_rng


class Driver(Base):
    def setup(self):
        from repro.stats.scheduler import ServeConfig, StatsScheduler
        from repro.stats.service import MultiTenantStats

        self.T = int(self.config["n_tenants"])
        req = int(self.mix["request"])
        with self.spans("generate"):
            dist, rng = self.keys(), seeded_rng(self.seed, 1)
            P = int(self.mix["pool_requests"])
            self.pool = dist.draw(rng, P * req).reshape(P, req)
            self.offset = rng.integers(0, P, size=self.T)
            self.check = np.sort(rng.choice(
                self.T, size=min(int(self.mix["check_tenants"]), self.T),
                replace=False))
        svc = MultiTenantStats(stats_config(self.svc_cfg), n_tenants=self.T)
        self.sched = StatsScheduler(svc, ServeConfig(**self.config["serve"]))
        self.svc = svc
        self.marker = Marker()
        self.rounds = 0
        w = Window(self.mix["inflight"], self.spans)
        for _ in range(int(self.mix["warmup_rounds"])):
            self._round(w)
        w.drain()

    def _request(self, t: int, i: int) -> np.ndarray:
        return self.pool[(self.offset[t] + i) % len(self.pool)]

    def _round(self, w):
        w.admit()
        with self.spans("submit"):
            for t in range(self.T):
                self.sched.submit_ingest(t, self._request(t, self.rounds))
        with self.spans("step"):
            self.sched.step()
        w.push(self.marker())
        self.rounds += 1

    def window(self, seconds: float) -> dict:
        w = Window(self.mix["inflight"], self.spans)
        first = self.rounds
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._round(w)
        w.drain()
        dt = time.perf_counter() - t0
        n = self.rounds - first
        self.attempted = n
        req = int(self.mix["request"])
        return {"e2e": {"ingest_eps": n * self.T * req / dt},
                "counters": {"program": "update_bank", "rounds": n,
                             "chunks": n * self.T * req // self.chunk,
                             "lanes": len(self.ls), "chunk": self.chunk,
                             "tenants": self.T}}

    def outputs(self) -> dict:
        if self.sched.pending_ingest:
            raise RuntimeError("ingest requests left unadmitted")
        sd = self.svc.state_dict()
        lanes = list(enumerate(self.ls))
        return {int(t): {
            "samples": {l: (sd["keys"][t, j], sd["counts"][t, j],
                            float(sd["tau"][t, j])) for j, l in lanes},
            "summaries": {l: (sd["bk_keys"][t, j], sd["bk_seeds"][t, j])
                          for j, l in lanes},
            "n_seen": int(sd["n_seen"][t])} for t in self.check}

    def release(self):
        self.sched = self.svc = self.marker = None

    def reference(self, precision: str = "float32") -> dict:
        """Each checked tenant's one-pass samples and summaries over its own
        stream, as a standalone service fed that stream would hold them."""
        out = {}
        for t in self.check:
            keys = np.concatenate([self._request(t, i)
                                   for i in range(self.rounds)])
            fk = R.FixedK(self.ls, k=self.k, chunk=self.chunk, salt=self.salt,
                          precision=precision)
            fk.feed(keys)
            summ = R.Stream(keys).summaries(
                np.arange(len(keys)), self.ls, salt=self.salt,
                cap=self.k + 1, precision=precision)
            out[int(t)] = {"samples": fk.samples(), "summaries": summ,
                           "n_seen": len(keys)}
        return out

    def numbers(self, got: dict, want: dict) -> dict:
        out: dict = {}
        for t, w in want.items():
            nums = compare.sample_numbers(got[t]["samples"], w["samples"])
            nums.update(compare.summary_numbers(got[t]["summaries"],
                                                w["summaries"]))
            nums["position_gap"] = abs(got[t]["n_seen"] - w["n_seen"])
            for name, v in nums.items():
                out[name] = max(out.get(name, 0.0), v)
        return out
