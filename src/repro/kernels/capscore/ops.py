"""Public op: padding + backend dispatch for the capscore kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.segments import EMPTY
from .capscore import (
    capscore as _kernel,
    capscore_agg as _kernel_agg,
    capscore_multi as _kernel_multi,
    default_interpret,
)
from .ref import capscore_agg_ref, capscore_multi_ref, capscore_ref
from .tiling import resolve_backend as _resolve_backend
from .tiling import tile_config


def _pad_tile(tile, *cols):
    """Pad 1-D arrays to a multiple of ``tile`` with per-array fill values.

    ``cols`` are (array, fill) pairs; returns (padded_arrays..., pad).  The
    no-op case (already tile-aligned — every ``SamplerSpec.chunk`` in
    practice) skips the concatenates entirely, so the aligned hot path traces
    zero extra ops; tests/test_ingest_order.py pins padded-vs-aligned outputs
    slice-bit-identical.
    """
    n = cols[0][0].shape[0]
    pad = (-n) % tile
    if pad == 0:
        return tuple(a for a, _ in cols) + (0,)
    return tuple(
        jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)]) for a, fill in cols
    ) + (pad,)


def capscore(keys, eids, weights, l, tau, salt, *, backend: str | None = None):
    """Fused element scoring.  backend: 'pallas' | 'xla' | None (auto).

    On CPU the Pallas path runs in interpret mode (correctness only); 'xla'
    is the fast CPU path and the differentiation-friendly fallback.
    """
    backend = _resolve_backend(backend)
    if backend == "xla":
        return capscore_ref(keys, eids, weights, l, tau, salt)
    cfg = tile_config("capscore")
    n = keys.shape[0]
    keys, eids, weights, pad = _pad_tile(
        cfg.elements, (keys, 0), (eids, 0), (weights, 1.0))
    s, d, e = _kernel(keys, eids, weights, l, tau, salt,
                      interpret=default_interpret(), cfg=cfg)
    if pad:
        s, d, e = s[:n], d[:n], e[:n]
    return s, d, e


def capscore_multi(keys, eids, weights, ls, taus, salt, *, backend: str | None = None):
    """Fused multi-l element scoring: one pass over the elements scores every
    (ls[j], taus[j]) lane of a sketch grid.  backend: 'pallas' | 'xla' | None.

    Returns (score, delta, entry, kb), each shaped [len(ls), N].
    """
    backend = _resolve_backend(backend)
    if backend == "xla":
        return capscore_multi_ref(keys, eids, weights, ls, taus, salt)
    cfg = tile_config("capscore_multi")
    n = keys.shape[0]
    n_l = ls.shape[0] if hasattr(ls, "shape") else len(ls)
    keys, eids, weights, pad = _pad_tile(
        cfg.elements, (keys, 0), (eids, 0), (weights, 1.0))
    s, d, e, kb = _kernel_multi(keys, eids, weights, ls, taus, salt,
                                n_l=int(n_l), interpret=default_interpret(),
                                cfg=cfg)
    if pad:
        s, d, e, kb = s[:, :n], d[:, :n], e[:, :n], kb[:, :n]
    return s, d, e, kb


def capscore_agg(ks, eids, ws, seg, ls, taus, salt, *, backend: str | None = None):
    """Fused multi-l scoring + per-key chunk aggregation over a KEY-ORDERED
    chunk (the ChunkOrder pre-gathered view).  backend: 'pallas'|'xla'|None.

    One pass over the elements scores every (ls[j], taus[j]) lane AND reduces
    the scores into the per-unique-key ChunkAgg columns, so the [L, N]
    score/delta/entry/kb intermediates are never materialized between stages.

    Returns (w_total [C], entered bool [L, C], contrib [L, C], kb_min [L, C],
    min_score [L, C]); ``w_total`` is lane-independent and computed once.
    The 'xla' path (CPU/GPU production) is bit-identical to scoring then
    aggregating; the Pallas path reassociates the f32 sums in-block (mins,
    maxes and ``entered`` stay exact) — see the kernel docstring.
    """
    backend = _resolve_backend(backend)
    if backend == "xla":
        return capscore_agg_ref(ks, eids, ws, seg, ls, taus, salt)
    cfg = tile_config("capscore_agg")
    n = ks.shape[0]
    n_l = ls.shape[0] if hasattr(ls, "shape") else len(ls)
    # padding: EMPTY keys are masked to the reduction identities inside the
    # kernel, and segment id ``n`` (one past the last real segment) parks
    # them on output rows the slice below drops
    ks, eids, ws, seg, pad = _pad_tile(
        cfg.elements, (ks, int(EMPTY)), (eids, 0), (ws, 1.0), (seg, n))
    wt, ent, ctr, kbm, msc = _kernel_agg(ks, eids, ws, seg, ls, taus, salt,
                                         n_l=int(n_l),
                                         interpret=default_interpret(),
                                         cfg=cfg)
    lane_cols = lambda a: a[:n].T  # [rows, n_l] -> [n_l, C]
    return (wt[:n], lane_cols(ent) > 0, lane_cols(ctr), lane_cols(kbm),
            lane_cols(msc))
