"""The comparisons that decide ``correct``: numbers, each with its limit.

Every number is a gap between what the timed path produced and the plain
reference (``reference.py``), taken at the worst lane (and tenant, or job).
A number passes when it is at or below its limit; ``correct`` is true when
every number that the configuration gives a limit passes.
"""
from __future__ import annotations

import math

import numpy as np

EMPTY = 2**31 - 1
#: the fixed query set the one-pass samples are held to: the caps of the
#: serving mix (``launch/stats_serve.serve_synthetic``), over the whole stream
CAPS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
COUNT_QUANTILE = 0.99


def _rel(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both_inf = np.isinf(a) & np.isinf(b) & (np.sign(a) == np.sign(b))
    with np.errstate(invalid="ignore"):
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1.0e-30)
    return np.where(both_inf, 0.0, gap)


def key_miss(got_keys, want_keys) -> float:
    """Share of keys in one set and not the other, over the reference's
    set size."""
    got = np.asarray(got_keys, np.int64)
    want = np.asarray(want_keys, np.int64)
    got, want = got[got != EMPTY], want[want != EMPTY]
    diff = len(np.setxor1d(got, want, assume_unique=True))
    return diff / max(len(want), 1)


def common_gap(got_keys, got_vals, want_keys, want_vals, *,
               relative: bool) -> float:
    """Widest gap of a per-key value over the keys both sides hold."""
    got_keys = np.asarray(got_keys, np.int64)
    want_keys = np.asarray(want_keys, np.int64)
    _, gi, wi = np.intersect1d(got_keys, want_keys, assume_unique=True,
                               return_indices=True)
    if not len(gi):
        return 0.0
    g = np.asarray(got_vals, np.float64)[gi]
    w = np.asarray(want_vals, np.float64)[wi]
    gap = _rel(g, w) if relative else np.abs(g - w)
    return float(np.max(gap))


def cap_estimates(counts, tau: float, l: float) -> np.ndarray:
    """The one-pass estimate of each cap statistic sum_x min(w_x, T) from a
    continuous SH_l sample: sum over sampled keys of
    min(c, T) / min(1, l tau) + 1[c < T] / tau (arXiv:1502.05955, Thm 5.3);
    with tau infinite the sample is the whole data set."""
    c = np.asarray(counts, np.float64)
    out = []
    for T in CAPS:
        if math.isinf(tau):
            beta = np.minimum(c, T)
        else:
            beta = np.minimum(c, T) / min(1.0, l * tau) + (c < T) / tau
        out.append(float(np.sum(beta)))
    return np.asarray(out)


def count_gap(got_keys, got_counts, want_keys, want_counts,
              rate: float) -> float:
    """The ``COUNT_QUANTILE`` quantile, over the keys both samples hold, of
    the count gap in units of the lane's sampling rate max(1/l, tau): the
    survivors' count adjustment subtracts Exp(1)/rate, so a gap of 1 is one
    whole adjustment."""
    got_keys = np.asarray(got_keys, np.int64)
    want_keys = np.asarray(want_keys, np.int64)
    _, gi, wi = np.intersect1d(got_keys, want_keys, assume_unique=True,
                               return_indices=True)
    if not len(gi):
        return math.inf if len(want_keys) else 0.0
    gap = np.abs(np.asarray(got_counts, np.float64)[gi]
                 - np.asarray(want_counts, np.float64)[wi]) * rate
    return float(np.quantile(gap, COUNT_QUANTILE))


def sample_numbers(got: dict, want: dict) -> dict:
    """One-pass samples {l: (keys, counts, tau)} of program and reference,
    each number at the worst lane: the key-set miss, the relative tau gap,
    the count gap (``count_gap``) and the widest relative gap of the cap
    estimates (``cap_estimates``)."""
    out = dict.fromkeys(("sample_key_miss", "tau_gap", "count_gap",
                         "estimate_gap"), 0.0)
    for l, (wk, wc, wt) in want.items():
        gk, gc, gt = got[l]
        live = np.asarray(gk) != EMPTY
        gk, gc = np.asarray(gk)[live], np.asarray(gc)[live]
        # a lane that never evicted (tau infinite) holds exact counts
        rate = max(1.0 / l, wt) if math.isfinite(wt) else 1.0 / l
        est_g, est_w = cap_estimates(gc, gt, l), cap_estimates(wc, wt, l)
        nums = {"sample_key_miss": key_miss(gk, wk),
                "tau_gap": float(_rel(gt, wt)),
                "count_gap": count_gap(gk, gc, wk, wc, rate),
                "estimate_gap": float(np.max(_rel(est_g, est_w)))}
        for name, v in nums.items():
            out[name] = max(out[name], v)
    return out


def summary_numbers(got: dict, want: dict) -> dict:
    """Bottom-(k+1) summaries {l: (keys, seeds)}: key-set miss and the
    widest relative seed gap on shared keys."""
    miss = seed = 0.0
    for l, (wk, ws) in want.items():
        gk, gs = got[l]
        live = np.asarray(gk) != EMPTY
        gk, gs = np.asarray(gk)[live], np.asarray(gs)[live]
        miss = max(miss, key_miss(gk, wk))
        seed = max(seed, common_gap(gk, gs, wk, ws, relative=True))
    return {"summary_key_miss": miss, "summary_seed_gap": seed}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers ``limits``
    names; one that is missing or not finite fails.  Numbers without a
    limit are readings only and are not compared."""
    out, ok = {}, bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        value = None if value is None else float(value)
        ok &= value is not None and math.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit}
    return ok, out
