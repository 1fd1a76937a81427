"""Plain references of the sampling semantics, in numpy alone.

Nothing here imports the program.  The semantics are the paper's
(arXiv:1502.05955): Algorithm 5, the fixed-size one-pass continuous SH_l
sampler with batched eviction per chunk (its section 5.2), and Algorithm 1,
the two-pass sampler (bottom-(k+1) by per-key seed, then exact weights).
The randomness is the stated counter-based hashing of the configuration:
element uniforms hash (element id, salt), key uniforms hash (key, salt),
eviction uniforms hash (key, round, salt), each through the splitmix32
finalizer below, so the reference draws the same randomness as a
conforming implementation and its answers can be compared one by one.

``precision`` is the float type the arithmetic rounds to after every
operation: ``"float32"`` as the configurations state it, ``"bfloat16"``
for the control.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

EMPTY = 2**31 - 1
SALT_ELEM = 0x01
SALT_KEYBASE = 0x03
SALT_EVICT_U = 0x04
SALT_EVICT_R = 0x05
SALT_SHARD = 0x06

_C1 = np.uint32(0x7FEB352D)
_C2 = np.uint32(0x846CA68B)
_GOLDEN = np.uint32(0x9E3779B9)
_H0 = np.uint32(0x243F6A88)
_U24 = 1.0 / 16777216.0


def _mix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(15))
    x = x * _C2
    return x ^ (x >> np.uint32(16))


def hash32(*parts) -> np.ndarray:
    """Order-sensitive uint32 hash of integer parts (broadcast together)."""
    parts = [np.asarray(p).astype(np.int64).astype(np.uint32) for p in parts]
    h = np.broadcast_to(_H0, np.broadcast_shapes(*(p.shape for p in parts)))
    with np.errstate(over="ignore"):
        for p in parts:
            h = _mix(h ^ (p + _GOLDEN + (h << np.uint32(6))
                          + (h >> np.uint32(2))))
    return h


def uniform(h: np.ndarray) -> np.ndarray:
    """uint32 -> (0, 1) from the top 24 bits; exact in float32."""
    return ((h >> np.uint32(8)).astype(np.float64) + 0.5) * _U24


def shard_element_ids(shard: int, n: int) -> np.ndarray:
    """Element ids of positions 0..n-1 of one shard of an element-split
    stream: hashed (SALT_SHARD, shard, position), read as int32."""
    return hash32(SALT_SHARD, shard, np.arange(n)).astype(np.int32)


class Precision:
    """Round-to-``name`` after every operation, carried in float32 arrays."""

    def __init__(self, name: str):
        if name not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name

    def __call__(self, x):
        x = np.asarray(x, np.float32)
        if self.name == "float32":
            return x
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)


# ---------------------------------------------------------------------------
# Element and key scores (the continuous scheme)
# ---------------------------------------------------------------------------


def element_exp(eids, salt, q: Precision) -> np.ndarray:
    """-log(1 - u) of each element's uniform: an Exp(1) draw."""
    u = q(uniform(hash32(eids, SALT_ELEM, salt)))
    with np.errstate(divide="ignore"):   # u rounds to 1 in bfloat16
        return q(-np.log1p(q(-u)))


def key_base(keys, salt, l, q: Precision) -> np.ndarray:
    """KeyBase(x) = u(x) / l, the per-key score floor of SH_l."""
    return q(q(uniform(hash32(keys, SALT_KEYBASE, salt))) / np.float32(l))


def element_scores(keys, e, w, l, kb, q: Precision) -> np.ndarray:
    """The element score of SH_l: KeyBase(x) if Exp/w <= 1/l, else Exp/w."""
    v = q(e / w)
    s = np.where(v <= np.float32(1.0 / l), kb, v)
    return np.where(keys == EMPTY, np.float32(np.inf), s).astype(np.float32)


# ---------------------------------------------------------------------------
# Algorithm 1, pass 1 and pass 2
# ---------------------------------------------------------------------------


class Stream:
    """A stream's elements grouped by key once: the exact per-key totals
    (pass 2) and, per lane, the bottom-``cap`` summary by per-key minimum
    seed (pass 1)."""

    def __init__(self, keys, weights=None):
        keys = np.asarray(keys, np.int64)
        w = (np.ones(len(keys), np.float32) if weights is None
             else np.asarray(weights, np.float32))
        self.live = keys != EMPTY
        self.order = np.argsort(keys[self.live], kind="stable")
        ks = keys[self.live][self.order]
        edge = np.r_[True, ks[1:] != ks[:-1]]
        self.starts = np.flatnonzero(edge)
        self.seg = np.cumsum(edge) - 1
        self.keys = ks[self.starts]
        self.w = w[self.live][self.order]
        self.totals = np.add.reduceat(self.w.astype(np.float64), self.starts)

    def totals_of(self, query_keys) -> np.ndarray:
        """Exact total weight of each query key (0 for an unseen key)."""
        q = np.asarray(query_keys, np.int64)
        pos = np.clip(np.searchsorted(self.keys, q), 0, len(self.keys) - 1)
        return np.where(self.keys[pos] == q, self.totals[pos], 0.0)

    def summaries(self, eids, ls, *, salt: int, cap: int,
                  precision: str = "float32") -> dict:
        """{l: (keys, seeds)}: per lane the ``cap`` keys of smallest
        per-key minimum element score (ties to the smaller key), ordered
        by seed; the elements carry the ids ``eids``."""
        q = Precision(precision)
        e = element_exp(np.asarray(eids)[self.live][self.order], salt, q)
        w = q(self.w)
        base = q(uniform(hash32(self.keys, SALT_KEYBASE, salt)))
        ks = self.keys[self.seg]
        out = {}
        for l in ls:
            kb = q(base / np.float32(l))[self.seg]
            mins = np.minimum.reduceat(element_scores(ks, e, w, l, kb, q),
                                       self.starts)
            sel = np.lexsort((self.keys, mins))[:cap]
            out[float(l)] = (self.keys[sel], mins[sel])
        return out


# ---------------------------------------------------------------------------
# Algorithm 5 with batched eviction per chunk (one lane)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Lane:
    """One fixed-k SH_l sample: sorted keys with counts, KeyBase, seed."""

    l: float
    keys: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    counts: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))
    kb: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))
    seed: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))
    tau: np.float32 = np.float32(np.inf)
    rounds: int = 0


class FixedK:
    """Algorithm 5 over a stream fed chunk by chunk, for a grid of l.

    Each chunk is scored under the threshold in force before it, folded
    into each lane's sample (a cached key adds its chunk weight; a new key
    enters at its first entry event with the weight from there on), and
    each lane then evicts back down to ``k`` keys by the race of section
    5.2, whose round number counts the chunks folded in so far.
    """

    def __init__(self, ls, *, k: int, chunk: int, salt: int,
                 precision: str = "float32"):
        self.k, self.chunk, self.salt = int(k), int(chunk), int(salt)
        self.q = Precision(precision)
        self.lanes = [Lane(float(l)) for l in ls]
        self.position = 0

    def feed(self, keys, weights=None) -> None:
        keys = np.asarray(keys, np.int64)
        if len(keys) % self.chunk:
            raise ValueError("feed whole chunks")
        w = (np.ones(len(keys), np.float32) if weights is None
             else np.asarray(weights, np.float32))
        for c in range(0, len(keys), self.chunk):
            self._chunk(keys[c:c + self.chunk], w[c:c + self.chunk])

    def _chunk(self, keys, w) -> None:
        q, C = self.q, len(keys)
        eids = np.arange(self.position, self.position + C)
        self.position += C
        e = element_exp(eids, self.salt, q)
        order = np.argsort(keys, kind="stable")
        ks, es, ws = keys[order], e[order], q(w[order])
        live = ks != EMPTY
        starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
        seg = np.cumsum(np.r_[False, ks[1:] != ks[:-1]])
        ukeys = ks[starts]
        ubase = q(uniform(hash32(ukeys, SALT_KEYBASE, self.salt)))
        w_total = np.add.reduceat(np.where(live, ws, 0), starts)
        idx = np.arange(C)
        for lane in self.lanes:
            l = np.float32(lane.l)
            inv_l = np.float32(1.0 / lane.l)
            tau = lane.tau
            kb_u = q(ubase / l)
            kb = kb_u[seg]
            score = element_scores(ks, es, ws, lane.l, kb, q)
            rate = max(inv_l, tau)
            delta = q(es / rate)
            high = bool(tau * l > 1)
            entry = (delta < ws) & (high | (kb < tau)) & live
            first = np.minimum.reduceat(np.where(entry, idx, C), starts)[seg]
            part = (np.where(idx > first, ws, 0)
                    + np.where((idx == first) & entry, q(ws - delta), 0))
            contrib = q(np.add.reduceat(q(part), starts))
            entered = np.maximum.reduceat(entry, starts)
            min_score = np.minimum.reduceat(score, starts)
            self._merge(lane, ukeys, q(w_total), entered, contrib, kb_u,
                        min_score)
            lane.rounds += 1
            self._evict(lane)

    def _merge(self, lane, ukeys, w_total, entered, contrib, kb, min_score):
        q = self.q
        keep = ukeys != EMPTY
        ukeys, w_total, entered = ukeys[keep], w_total[keep], entered[keep]
        contrib, kb, min_score = contrib[keep], kb[keep], min_score[keep]
        n = len(lane.keys)
        pos = np.searchsorted(lane.keys, ukeys)
        hit = (pos < n) & (lane.keys[np.minimum(pos, max(n - 1, 0))]
                           == ukeys) if n else np.zeros(len(ukeys), bool)
        counts, seed = lane.counts.copy(), lane.seed.copy()
        counts[pos[hit]] = q(counts[pos[hit]] + w_total[hit])
        seed[pos[hit]] = np.minimum(seed[pos[hit]], min_score[hit])
        new = ~hit & entered
        keys = np.concatenate([lane.keys, ukeys[new]])
        order = np.argsort(keys, kind="stable")
        lane.keys = keys[order]
        lane.counts = np.concatenate([counts, contrib[new]])[order]
        lane.kb = np.concatenate([lane.kb, kb[new]])[order]
        lane.seed = np.concatenate([seed, min_score[new]])[order]

    def _evict(self, lane) -> None:
        n = len(lane.keys)
        if n <= self.k:
            return
        q = self.q
        l, inv_l, tau = np.float32(lane.l), np.float32(1.0 / lane.l), lane.tau
        ux = q(uniform(hash32(lane.keys, SALT_EVICT_U, lane.rounds,
                              self.salt)))
        rx = q(uniform(hash32(lane.keys, SALT_EVICT_R, lane.rounds,
                              self.salt)))
        with np.errstate(divide="ignore"):
            ex = q(-np.log1p(q(-rx)))
        r = q(ex / np.maximum(lane.counts, np.float32(1e-30)))
        race = np.where(r >= inv_l, r, lane.kb)
        seed_part = q(tau * ux)
        entry_thresh = np.where(seed_part >= inv_l, seed_part, lane.kb)
        high = bool(tau * l > 1)
        z = np.minimum(entry_thresh, race) if high else lane.kb
        drop = n - self.k
        z_sel = np.partition(z, n - drop)[n - drop]
        evict = z >= z_sel
        new_rate = max(inv_l, z_sel)
        adjust = ~evict & (entry_thresh >= z_sel) & high
        counts = np.where(adjust, q(lane.counts - q(ex / new_rate)),
                          lane.counts)
        keep = ~evict
        lane.keys, lane.counts = lane.keys[keep], counts[keep]
        lane.kb, lane.seed = lane.kb[keep], lane.seed[keep]
        lane.tau = np.float32(z_sel)

    def samples(self) -> dict:
        """{l: (sorted keys, counts, tau)} per lane."""
        return {lane.l: (lane.keys, lane.counts, float(lane.tau))
                for lane in self.lanes}
