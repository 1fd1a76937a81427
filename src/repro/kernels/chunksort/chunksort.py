"""Pallas kernels: block-local bitonic sort + cross-block bitonic merge.

The chunk-order sort (``segments.ChunkOrder``) is the single shared O(C log C)
stage of the ingest path — every lane consumes its permutation.  This module
replaces the XLA ``argsort`` with the classic bitonic sorting network over
``(key, index)`` pairs, cut into VMEM-sized pallas_calls:

  phase 1 — block-local sort: the padded chunk is cut into B-element blocks
    (B = tile config, power of two); each grid step runs the network's
    stages of sizes 2..B over its block entirely in VMEM.  The direction
    rule below makes even blocks ascending runs and odd blocks descending
    ones, so every adjacent pair of blocks is one bitonic sequence.

  phase 2 — cross-block merge: log2(P/B) further pallas_calls; each grid
    step loads one bitonic sequence of 2m pairs (two adjacent runs of m) and
    collapses it with the log2(2m) half-cleaner stages of network size 2m,
    doubling the run length per call until one ascending run spans the
    chunk.

Direction rule: a compare-exchange group is ascending iff bit ``size`` of
its elements' GLOBAL position is clear.  At the last size (P) every position
is below P, so the final run ascends — no reversal or flip stage needed.

Why pairs: the kernels order ``(key, idx)`` tuples lexicographically.  All
tuples are distinct (``idx`` is a permutation), so the network needs no
stability of its own — the tuple order *is* the stable argsort order, which
makes the result bit-identical to ``jnp.argsort(keys, stable=True)`` by
construction, not by numerical accident.  EMPTY (int32 max) needs no special
casing: it is maximal, so padded tails sort to the end on their own.

Every compare-exchange stage is elementwise over the (1, width) block: each
position fetches its partner ``stride`` away with two lane rotations
(``pltpu.roll``) and keeps itself or the partner — no data-dependent control
flow, no gathers, no relayouts; the network shape is fully static per
TileConfig, so each tile config is exactly one compile (metered by the
reprolint retrace budgets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..capscore.capscore import _compiler_params, default_interpret, out_struct
from ..capscore.tiling import TileConfig, tile_config


def _compare_exchange(keys, idx, pos, stride, size):
    """One bitonic stage on (1, width) pair blocks at global positions ``pos``.

    Partners sit ``stride`` apart: the lower one (bit ``stride`` of its
    position clear) pairs with ``pos + stride``, the upper one with
    ``pos - stride``.  The group ascends iff bit ``size`` of ``pos`` is
    clear; the lower slot then keeps the smaller pair and the upper slot the
    larger (descending groups the reverse).  Pairs are distinct, so the
    strict lexicographic ``>`` decides every case.
    """
    width = keys.shape[-1]
    lower = (pos & stride) == 0
    partner = lambda a: jnp.where(lower, pltpu.roll(a, width - stride, 1),
                                  pltpu.roll(a, stride, 1))
    kp, ip = partner(keys), partner(idx)
    self_gt = (keys > kp) | ((keys == kp) & (idx > ip))
    keep = self_gt == (lower != ((pos & size) == 0))
    return jnp.where(keep, keys, kp), jnp.where(keep, idx, ip)


def _bitonic_stages(block: int):
    """Static (stride, size) schedule of the block-local sort: for
    size = 2, 4, .., block, strides size/2 .. 1."""
    stages = []
    size = 2
    while size <= block:
        stride = size // 2
        while stride >= 1:
            stages.append((stride, size))
            stride //= 2
        size *= 2
    return stages


def _make_kernel(width: int, stages):
    """Kernel: run ``stages`` over one (1, width) pair block in VMEM."""

    def kernel(k_ref, i_ref, ko_ref, io_ref):
        pos = pl.program_id(0) * width + jax.lax.broadcasted_iota(
            jnp.int32, (1, width), 1)
        k, i = k_ref[...], i_ref[...]
        for stride, size in stages:
            k, i = _compare_exchange(k, i, pos, stride, size)
        ko_ref[...] = k
        io_ref[...] = i

    return kernel


def _merge_stages(merged: int):
    """Half-cleaner cascade of network size ``merged``: strides merged/2 .. 1."""
    stages = []
    stride = merged // 2
    while stride >= 1:
        stages.append((stride, merged))
        stride //= 2
    return stages


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def sort_pairs(keys, idx, *, cfg: TileConfig | None = None,
               interpret: bool | None = None):
    """Sort int32 ``(keys[j], idx[j])`` pairs lexicographically ascending.

    Args:
      keys: int32 [P], P a power of two and a multiple of the block size
        (use ops.sort_with_perm for padding; EMPTY-maximal padding keeps the
        real prefix exact).
      idx: int32 [P], all distinct (a permutation — normally ``arange(P)``).
      cfg: tile config (static); None selects the platform flavor.
      interpret: None resolves via ``default_interpret()``.
    Returns:
      (keys_sorted, idx_sorted) — bit-identical to the stable argsort dual
      ``segments.stable_sort_with_perm`` when ``idx = arange(P)``.
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg is None:
        cfg = tile_config("chunksort")
    P = keys.shape[0]
    block = min(cfg.block[0], P)
    assert P & (P - 1) == 0 and P % block == 0, (P, block)

    kw = {}
    params = _compiler_params(cfg, interpret)
    if params is not None:
        kw["compiler_params"] = params
    pair_shape = [out_struct((1, P), jnp.int32, keys, idx)] * 2

    def run(kernel, width, k2, i2):
        blk = lambda: pl.BlockSpec((1, width), lambda i: (0, i))
        return pl.pallas_call(
            kernel, grid=(P // width,),
            in_specs=[blk(), blk()], out_specs=[blk(), blk()],
            out_shape=pair_shape, interpret=interpret, **kw)(k2, i2)

    view = lambda a: a.reshape(1, P)
    k2, i2 = run(_make_kernel(block, _bitonic_stages(block)), block,
                 view(keys), view(idx))
    m = block
    while m < P:
        k2, i2 = run(_make_kernel(2 * m, _merge_stages(2 * m)), 2 * m, k2, i2)
        m *= 2
    return k2.reshape(P), i2.reshape(P)
