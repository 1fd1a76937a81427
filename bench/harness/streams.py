"""Seeded element streams: Zipf ranks over the whole key universe, mapped
to int32 keys.

Ranks are drawn by rejection-inversion (Hormann and Derflinger, "Rejection-
inversion to generate variates from monotone discrete distributions", 1996),
which needs no table, so the universe can be the deployment's own (10^8
users) and not a size a CDF table fits.  A rank maps to a key id by a seeded
bijection of the 31-bit ids that skips the EMPTY sentinel, so hot keys are
spread over the id space and no two ranks share a key.
"""
from __future__ import annotations

import numpy as np

EMPTY = 2**31 - 1
_MASK31 = np.uint64(2**31 - 1)


class ZipfKeys:
    """Keys of a Zipf(a) law over ``n_keys`` ranks (a > 1)."""

    def __init__(self, rng: np.random.Generator, n_keys: int, a: float):
        if not a > 1.0:
            raise ValueError("the Zipf exponent must exceed 1")
        if not 0 < n_keys < EMPTY:
            raise ValueError("the key universe must fit 31-bit ids")
        self.n_keys, self.a = int(n_keys), float(a)
        self.h_x1 = self._H(1.5) - 1.0
        self.h_n = self._H(self.n_keys + 0.5)
        self.s = 2.0 - self._H_inv(self._H(2.5) - 2.0 ** -self.a)
        # the bijection: x -> (x * mul + add) mod 2^31, then an xorshift,
        # twice; each step is invertible on 31 bits
        self.mul = [int(m) | 1 for m in rng.integers(1 << 20, 1 << 31, 2)]
        self.add = [int(c) for c in rng.integers(0, 1 << 31, 2)]

    def _H(self, x):
        """Integral of x^-a: (x^(1-a) - 1) / (1 - a)."""
        return np.expm1((1.0 - self.a) * np.log(x)) / (1.0 - self.a)

    def _H_inv(self, y):
        return np.exp(np.log1p(y * (1.0 - self.a)) / (1.0 - self.a))

    def ranks(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` ranks in [1, n_keys], P(r) proportional to r^-a."""
        out = np.empty(n, np.int64)
        todo = np.arange(n)
        while len(todo):
            u = self.h_n + rng.random(len(todo)) * (self.h_x1 - self.h_n)
            x = self._H_inv(u)
            k = np.clip(np.floor(x + 0.5), 1, self.n_keys)
            ok = (k - x <= self.s) | (u >= self._H(k + 0.5) - k ** -self.a)
            out[todo[ok]] = k[ok].astype(np.int64)
            todo = todo[~ok]
        return out

    def _permute(self, x: np.ndarray) -> np.ndarray:
        x = x.astype(np.uint64)
        with np.errstate(over="ignore"):
            for m, c in zip(self.mul, self.add):
                x = (x * np.uint64(m) + np.uint64(c)) & _MASK31
                x ^= x >> np.uint64(13)
        return x

    def ids(self, ranks: np.ndarray) -> np.ndarray:
        """The key id of each rank: the bijection, walked on past EMPTY."""
        x = self._permute(np.asarray(ranks, np.int64) - 1)
        hit = x == np.uint64(EMPTY)
        while hit.any():
            x[hit] = self._permute(x[hit])
            hit = x == np.uint64(EMPTY)
        return x.astype(np.int32)

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return self.ids(self.ranks(rng, n))


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named sub-stream of a run's seed (any size of
    non-negative seed, beyond 32 bits too)."""
    return np.random.default_rng([int(seed), *map(int, stream)])
