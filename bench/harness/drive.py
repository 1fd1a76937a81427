"""What the drivers (``bench/drivers/<driver>.py``) share.

A traffic mix (``bench/traffic/<mix>.json``) names its driver; the driver
builds the system under test through its normal entry point, warms the
shapes the mix uses, drives the window, and hands back what the window
produced for the comparison with the reference.  A driver file defines
``Driver``, a subclass of ``Base`` with:

* ``setup()``: make the data from the seed, build the program, warm up;
* ``window(seconds)``: drive the entry point; returns ``{"e2e": {...},
  "counters": {...}}``, the end-to-end metrics and what the per-layer
  readers count by;
* ``outputs()``: host copies of what the timed path produced;
* ``release()``: drop the program's state before the reference runs;
* ``reference(precision)``: the plain reference's outputs for the same
  inputs (``"bfloat16"`` gives the control);
* ``numbers(got, want)``: the compared numbers;
* ``as_outputs(ref, precision)``: a reference's outputs in the form
  ``outputs()`` gives, so that the control can stand in the program's
  place.

In-flight work is bounded by waiting on a public handle: the output of a
job, or a one-element marker computation enqueued on the device right
after a dispatch (a device runs its programs in the order they were
enqueued, so the marker completes after the dispatch before it).
"""
from __future__ import annotations

import collections

from .streams import ZipfKeys, seeded_rng


class Marker:
    """A tiny device computation whose completion stands for everything
    enqueued on that device before it."""

    def __init__(self, device=None):
        import jax
        import jax.numpy as jnp

        self._fn = jax.jit(lambda x: x + 1)
        self._x = jax.device_put(jnp.zeros((), jnp.int32), device)

    def __call__(self):
        return self._fn(self._x)


class Window:
    """Closed-loop pacing with at most ``depth`` units in flight."""

    def __init__(self, depth: int, spans):
        self.depth, self.spans = int(depth), spans
        self.inflight: collections.deque = collections.deque()

    def admit(self):
        while len(self.inflight) >= self.depth:
            with self.spans("wait"):
                self.inflight.popleft().block_until_ready()

    def push(self, handle):
        self.inflight.append(handle)

    def drain(self):
        with self.spans("wait"):
            while self.inflight:
                self.inflight.popleft().block_until_ready()


def stats_config(svc: dict):
    from repro.stats.service import StatsConfig

    return StatsConfig(k=int(svc["k"]), ls=tuple(float(l) for l in svc["ls"]),
                       chunk=int(svc["chunk"]), salt=int(svc["salt"]),
                       evict_every=int(svc.get("evict_every", 1)))


class Base:
    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.svc_cfg = config["service"]
        self.ls = [float(l) for l in self.svc_cfg["ls"]]
        self.k, self.chunk = int(self.svc_cfg["k"]), int(self.svc_cfg["chunk"])
        self.salt = int(self.svc_cfg["salt"])
        self.attempted = self.failed = 0

    def as_outputs(self, ref, precision: str = "float32"):
        return ref

    def keys(self) -> ZipfKeys:
        return ZipfKeys(seeded_rng(self.seed, 0),
                        int(self.config["key_universe"]),
                        float(self.config["zipf_a"]))
