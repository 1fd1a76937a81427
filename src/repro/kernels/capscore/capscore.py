"""Pallas TPU kernel: fused element scoring for continuous SH_l (eq. 10).

The sampler hot loop is a pure elementwise pipeline

    eid --hash--> u --exp--> v ;  key --hash--> KeyBase ;
    score = v <= 1/l ? KeyBase : v ;
    Delta = -log1p(-u)/max(1/l,tau) ;  entry = Delta < w  &  regime-gate

i.e. two integer avalanche hashes + two transcendentals per element, fully
memory-bound.  Fusing it into one VMEM-resident kernel removes five HBM
round-trips (u, v, kb, score, Delta materializations) that the XLA path pays
when it can't fuse across the int->float boundary.

Layout: the element stream is viewed as (rows, 128) with (8, 128)-aligned
blocks (float32 native TPU tile); the grid walks row-blocks.  Scalars
(l, tau, salt) arrive in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# salts must match core.samplers
from ...core.samplers import SALT_ELEM, SALT_KEYBASE
from .tiling import TileConfig, tile_config


def default_interpret() -> bool:
    """Pallas interpret mode, derived from the platform and nothing else.

    False on a real TPU or GPU (the kernels compile through Mosaic resp.
    Triton and actually run fused), True everywhere else (interpret mode is
    the only way the kernels execute on CPU — correctness checking, not
    speed).
    """
    return jax.default_backend() not in ("tpu", "gpu")


def _compiler_params(cfg: TileConfig, interpret: bool):
    """Backend compiler params for a compiled run; None in interpret mode.

    TPU: 'arbitrary' grid semantics keep Mosaic's cross-step pipeline legal
    for the carry-accumulating aggregate kernel while still double-buffering
    the streamed element blocks.  GPU: Triton's num_stages is the software
    pipeline depth for the same streamed blocks.
    """
    if interpret or not cfg.compiled:
        return None
    if cfg.backend == "tpu":
        return pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    from jax.experimental.pallas import triton as plgpu
    return plgpu.CompilerParams(num_stages=cfg.num_stages)


def out_struct(shape, dtype, *operands):
    """A pallas_call output type that varies over every mesh axis any of
    ``operands`` varies over — inside ``jax.shard_map`` Pallas needs that
    stated (``vma``); outside it the set is empty."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _scalars(ls, taus, salt):
    """The kernels' two leading scalar operands: f32 ``[ls..., taus...]``
    and the int32 bit pattern of the uint32 salt.

    The floats travel as f32, not as i32 bit patterns: Mosaic bitcasts
    vectors only, so an SMEM scalar read as i32 cannot become an f32 in the
    kernel.
    """
    fs = jnp.concatenate([jnp.asarray(ls, jnp.float32).reshape(-1),
                          jnp.asarray(taus, jnp.float32).reshape(-1)])
    return fs, jnp.asarray(salt, jnp.uint32).astype(jnp.int32).reshape(1)


def _grid_call(kernel, scalars, *, cfg, interpret, grid, in_specs, out_specs,
               out_shape):
    """Build the pallas_call for one entry point under a TileConfig.

    Two grid styles, one kernel body: with ``cfg.scalar_prefetch`` the 1-D
    ``scalars`` ride Mosaic's SMEM prefetch (``PrefetchScalarGridSpec``);
    without it they arrive as plain leading operands whose blocks cover each
    whole vector (the Triton route — index maps use ``(i, *_)`` so both
    arities work).  Either way the kernel sees ``(*scalar_refs, *refs)``;
    the returned callable takes the scalars first, then the operands.
    """
    kw = {}
    params = _compiler_params(cfg, interpret)
    if params is not None:
        kw["compiler_params"] = params
    if cfg.scalar_prefetch:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=grid,
                in_specs=in_specs, out_specs=out_specs),
            out_shape=out_shape, interpret=interpret, **kw)
    scalar_specs = [pl.BlockSpec(a.shape, lambda i, *_: (0,)) for a in scalars]
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=scalar_specs + list(in_specs), out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, **kw)

import numpy as np

_C1 = np.uint32(0x7FEB352D)
_C2 = np.uint32(0x846CA68B)
_GOLDEN = np.uint32(0x9E3779B9)
_SEED0 = np.uint32(0x243F6A88)


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 15)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def _combine(h, p):
    return _mix32(h ^ (p + _GOLDEN + (h << 6) + (h >> 2)))


def _u01(h):
    # h >> 8 fits in 24 bits, so the int32 hop is exact (Mosaic has no
    # direct uint32 -> float32 cast)
    u = (h >> 8).astype(jnp.int32).astype(jnp.float32)
    return (u + 0.5) * jnp.float32(1.0 / 16777216.0)


def _capscore_kernel(fs_ref, salt_ref, keys_ref, eids_ref, w_ref, score_ref,
                     delta_ref, entry_ref):
    l = fs_ref[0]
    tau = fs_ref[1]
    salt = salt_ref[0].astype(jnp.uint32)

    keys = keys_ref[...].astype(jnp.uint32)
    eids = eids_ref[...].astype(jnp.uint32)
    w = w_ref[...]

    # element uniform: hash(eid, SALT_ELEM, salt)
    h = _combine(jnp.full_like(eids, _SEED0), eids)
    h = _combine(h, np.uint32(SALT_ELEM))
    h = _combine(h, salt)
    u = _u01(h)

    # KeyBase(x) = hash(key, SALT_KEYBASE, salt)/l
    hk = _combine(jnp.full_like(keys, _SEED0), keys)
    hk = _combine(hk, np.uint32(SALT_KEYBASE))
    hk = _combine(hk, salt)
    kb = _u01(hk) / l

    e = -jnp.log1p(-u)
    v = e / w
    inv_l = 1.0 / l
    score = jnp.where(v <= inv_l, kb, v)

    rate = jnp.maximum(inv_l, tau)
    delta = e / rate
    gate = (tau * l > 1.0) | (kb < tau)
    entry = ((delta < w) & gate).astype(jnp.int32)

    score_ref[...] = score
    delta_ref[...] = delta
    entry_ref[...] = entry


@functools.partial(jax.jit, static_argnames=("interpret", "cfg"))
def capscore(keys, eids, weights, l, tau, salt, *, interpret: bool | None = None,
             cfg: TileConfig | None = None):
    """Fused scoring over a stream chunk.

    Args:
      keys, eids: int32 [N], N a multiple of the tile (use ops.capscore for
        padding).
      weights: float32 [N].
      l, tau, salt: scalars (traced ok).
      interpret: None (default) resolves via ``default_interpret()`` —
        compiled on TPU/GPU, interpret elsewhere.
      cfg: tile config (static); None selects the platform flavor from the
        tiling registry.
    Returns:
      (score f32[N], delta f32[N], entry int32[N]).
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg is None:
        cfg = tile_config("capscore")
    br, lanes = cfg.block
    n = keys.shape[0]
    assert n % (br * lanes) == 0, n
    rows = n // lanes
    shape2d = (rows, lanes)
    keys2 = keys.reshape(shape2d)
    eids2 = eids.reshape(shape2d)
    w2 = weights.reshape(shape2d)

    grid = (rows // br,)
    blk = lambda: pl.BlockSpec((br, lanes), lambda i, *_: (i, 0))
    scalars = _scalars(l, tau, salt)
    operands = (*scalars, keys2, eids2, w2)
    out_shape = [out_struct(shape2d, dt, *operands)
                 for dt in (jnp.float32, jnp.float32, jnp.int32)]
    score, delta, entry = _grid_call(
        _capscore_kernel, scalars, cfg=cfg, interpret=interpret, grid=grid,
        in_specs=[blk(), blk(), blk()], out_specs=[blk(), blk(), blk()],
        out_shape=out_shape,
    )(*operands)
    return score.reshape(n), delta.reshape(n), entry.reshape(n)


# ---------------------------------------------------------------------------
# Multi-l variant: score every l lane of the sketch grid in one VMEM pass
# ---------------------------------------------------------------------------


def _make_capscore_multi_kernel(n_l: int):
    """Kernel closure over the (static) number of l lanes.

    The element hashes (eid avalanche -> u, e = -log1p(-u); key avalanche ->
    Hash(x)) are computed ONCE per element block and kept VMEM-resident while
    all ``n_l`` (l, tau) lanes are scored — the per-lane work is 4 cheap
    vector ops, so the whole l-grid costs barely more than one lane.
    """

    def kernel(fs_ref, salt_ref, keys_ref, eids_ref, w_ref,
               score_ref, delta_ref, entry_ref, kb_ref):
        keys = keys_ref[...].astype(jnp.uint32)
        eids = eids_ref[...].astype(jnp.uint32)
        w = w_ref[...]
        salt = salt_ref[0].astype(jnp.uint32)

        # shared element randomness (independent of l and tau)
        h = _combine(jnp.full_like(eids, _SEED0), eids)
        h = _combine(h, np.uint32(SALT_ELEM))
        h = _combine(h, salt)
        u = _u01(h)
        e = -jnp.log1p(-u)
        v = e / w

        hk = _combine(jnp.full_like(keys, _SEED0), keys)
        hk = _combine(hk, np.uint32(SALT_KEYBASE))
        hk = _combine(hk, salt)
        ku = _u01(hk)  # Hash(x) in (0,1); KeyBase = ku / l

        for j in range(n_l):
            l = fs_ref[j]
            tau = fs_ref[n_l + j]
            inv_l = 1.0 / l
            kb = ku / l  # division, not *inv_l: bit-identical to the XLA path
            score = jnp.where(v <= inv_l, kb, v)
            rate = jnp.maximum(inv_l, tau)
            delta = e / rate
            gate = (tau * l > 1.0) | (kb < tau)
            entry = ((delta < w) & gate).astype(jnp.int32)
            score_ref[j] = score
            delta_ref[j] = delta
            entry_ref[j] = entry
            kb_ref[j] = kb

    return kernel


@functools.partial(jax.jit, static_argnames=("n_l", "interpret", "cfg"))
def capscore_multi(keys, eids, weights, ls, taus, salt, *, n_l: int,
                   interpret: bool | None = None,
                   cfg: TileConfig | None = None):
    """Fused multi-l scoring over a stream chunk.

    Args:
      keys, eids: int32 [N], N a multiple of the tile (use ops.capscore_multi).
      weights: float32 [N].
      ls, taus: float32 [n_l] per-lane cap parameter / current threshold.
      salt: uint32 scalar shared by all lanes.
      interpret: None (default) resolves via ``default_interpret()``.
      cfg: tile config (static); None selects the platform flavor.
    Returns:
      (score f32[n_l, N], delta f32[n_l, N], entry int32[n_l, N],
       kb f32[n_l, N]) — lane j scored under (ls[j], taus[j]).
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg is None:
        cfg = tile_config("capscore_multi")
    br, lanes = cfg.block
    n = keys.shape[0]
    assert n % (br * lanes) == 0, n
    rows = n // lanes
    shape2d = (rows, lanes)
    keys2 = keys.reshape(shape2d)
    eids2 = eids.reshape(shape2d)
    w2 = weights.reshape(shape2d)

    grid = (rows // br,)
    in_blk = lambda: pl.BlockSpec((br, lanes), lambda i, *_: (i, 0))
    out_blk = lambda: pl.BlockSpec((n_l, br, lanes), lambda i, *_: (0, i, 0))
    shape3d = (n_l, rows, lanes)
    scalars = _scalars(ls, taus, salt)
    operands = (*scalars, keys2, eids2, w2)
    out_shape = [out_struct(shape3d, dt, *operands)
                 for dt in (jnp.float32, jnp.float32, jnp.int32, jnp.float32)]
    score, delta, entry, kb = _grid_call(
        _make_capscore_multi_kernel(n_l), scalars, cfg=cfg,
        interpret=interpret, grid=grid,
        in_specs=[in_blk(), in_blk(), in_blk()],
        out_specs=[out_blk(), out_blk(), out_blk(), out_blk()],
        out_shape=out_shape,
    )(*operands)
    return (score.reshape(n_l, n), delta.reshape(n_l, n),
            entry.reshape(n_l, n), kb.reshape(n_l, n))


# ---------------------------------------------------------------------------
# Fused score + segment-reduce: the [n_l, N] intermediates never leave VMEM
# ---------------------------------------------------------------------------

# block/window sizes for the fused-aggregate kernel come from the tiling
# registry: the block-local one-hot (window x bn) and the masked reductions
# over it are the per-block working set (~0.5 MB at bn=256); the output row
# window is bn segments + ``align`` slack rows (the dynamic row start is
# rounded down to a multiple of ``align`` so the store stays tile-aligned; a
# block of bn sorted elements spans < bn segments)

_EMPTY_KEY = np.int32(2**31 - 1)  # == core.segments.EMPTY (int32 max)
_NO_ENTRY = np.int32(2**30)       # > any element index: "no entry event"
_LANE = 128                       # TPU lane width: the packed output's quantum


def _agg_columns(n_l: int) -> int:
    """Width of ``capscore_agg``'s packed output: ``1 + 4 * n_l`` columns
    (w_total, then entered / contrib / kb_min / min_score, ``n_l`` each)
    rounded up to whole 128-lane tiles."""
    return -(-(1 + 4 * n_l) // _LANE) * _LANE


def _make_capscore_agg_kernel(n_l: int, bn: int, window: int, align: int):
    """Kernel closure for the fused multi-lane score + per-key aggregate.

    Consumes the chunk in KEY-SORTED order (the pre-gathered ``ChunkOrder``
    view): per grid step, one block of ``bn`` elements is scored for all
    ``n_l`` lanes entirely in VMEM, then segment-reduced into the per-key
    output columns through a block-local one-hot as masked VPU reductions
    (sums, mins, maxes alike).  Because ``seg`` is sorted, a block's
    segments span a contiguous id range starting at the block's first
    segment id (``start_ref``, prefetched), so each block touches one
    ``window``-row slice of the fully VMEM-resident output; the slice is
    read-modify-written, which is the **cross-block carry**: the boundary
    segment shared with the previous block combines via +/min/max, and the
    entered-before flag in the ``entered`` columns decides the contrib
    recurrence ``contrib = entered_before ? contrib + block_w :
    block_contrib`` (the first-entry-onward count semantics of Algorithm 4,
    folded left block by block).

    All five per-key columns share ONE packed f32 output, ``_agg_columns``
    lanes wide (``entered`` as 0/1): five separate ``[rows, n_l]`` outputs
    would each pad their lane dimension to 128 in VMEM.

    Contract vs the XLA path (``ref.capscore_agg_ref``): min/max columns and
    ``entered`` are bit-identical; the float sums (``w_total``, ``contrib``)
    are reassociated by the in-block reduce, so they agree up to f32
    summation order (tests pin mins exactly and sums to tight rtol).
    """
    c_ent, c_ctr, c_kbm, c_msc = (1 + q * n_l for q in range(4))

    def kernel(fs_ref, salt_ref, start_ref, keys_ref, eids_ref, w_ref,
               seg_ref, acc_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            col = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
            acc_ref[...] = jnp.where(col >= c_kbm, jnp.inf, 0.0)

        keys = keys_ref[...].astype(jnp.uint32)    # (1, BN)
        eids = eids_ref[...].astype(jnp.uint32)
        w = w_ref[...]
        seg = seg_ref[...]                         # (1, BN) int32, sorted
        salt = salt_ref[0].astype(jnp.uint32)

        # shared element randomness (independent of l and tau)
        h = _combine(jnp.full_like(eids, _SEED0), eids)
        h = _combine(h, np.uint32(SALT_ELEM))
        h = _combine(h, salt)
        u = _u01(h)
        e = -jnp.log1p(-u)
        v = e / w

        hk = _combine(jnp.full_like(keys, _SEED0), keys)
        hk = _combine(hk, np.uint32(SALT_KEYBASE))
        hk = _combine(hk, salt)
        ku = _u01(hk)  # Hash(x) in (0,1); KeyBase = ku / l

        # reprolint: disable=RPL006 -- Pallas kernel body: compares against the
        # kernel-local np mirror of segments.EMPTY (jnp helpers don't lower
        # inside the Mosaic kernel); _EMPTY_KEY is asserted == EMPTY in tests
        live = keys_ref[...] != _EMPTY_KEY         # (1, BN)
        w_live = jnp.where(live, w, 0.0)

        # block-local one-hot over the (sublane-aligned) segment window
        s0a = pl.multiple_of((start_ref[step] // align) * align, align)
        local = seg - s0a                          # (1, BN) in [0, window)
        oh = (jax.lax.broadcasted_iota(jnp.int32, (window, bn), 0)
              == local)                            # (W, BN) bool
        rows = pl.ds(s0a, window)

        seg_sum = lambda vals: jnp.sum(jnp.where(oh, vals, 0.0), axis=1,
                                       keepdims=True)   # (1, BN) -> (W, 1)
        seg_min = lambda vals: jnp.min(jnp.where(oh, vals, jnp.inf), axis=1,
                                       keepdims=True)

        bw = seg_sum(w_live)                       # (W, 1) block weight/segment
        acc_ref[rows, 0:1] += bw

        idx = step * bn + jax.lax.broadcasted_iota(
            jnp.int32, (1, bn), 1)

        for j in range(n_l):
            l = fs_ref[j]
            tau = fs_ref[n_l + j]
            inv_l = 1.0 / l
            kb = ku / l  # division, not *inv_l: bit-identical to the XLA path
            score = jnp.where(v <= inv_l, kb, v)
            rate = jnp.maximum(inv_l, tau)
            delta = e / rate
            gate = (tau * l > 1.0) | (kb < tau)
            es = (delta < w) & gate & live

            # first entry event per segment, then back to per-element form
            # via the same one-hot (no data-dependent gathers in VMEM)
            entry_idx = jnp.where(es, idx, _NO_ENTRY)
            fe_loc = jnp.min(jnp.where(oh, entry_idx, _NO_ENTRY), axis=1,
                             keepdims=True)                     # (W, 1)
            fe_elem = jnp.min(jnp.where(oh, fe_loc, _NO_ENTRY), axis=0,
                              keepdims=True)                    # (1, BN)
            at = (idx == fe_elem) & es
            after = (idx > fe_elem) & live
            contrib_elem = (jnp.where(after, w, 0.0)
                            + jnp.where(at, w - delta, 0.0))

            bc = seg_sum(contrib_elem)                          # (W, 1)
            be = jnp.max(jnp.where(oh, es.astype(jnp.float32), 0.0), axis=1,
                         keepdims=True)
            ms = seg_min(jnp.where(live, score, jnp.inf))
            bkb = seg_min(jnp.where(live, kb, jnp.inf))

            # cross-block carry: read the window BEFORE updating `entered`
            # so the contrib recurrence sees "entered in an earlier block"
            ent = pl.ds(c_ent + j, 1)
            ctr = pl.ds(c_ctr + j, 1)
            kbm = pl.ds(c_kbm + j, 1)
            msc = pl.ds(c_msc + j, 1)
            prev_ent = acc_ref[rows, ent]
            acc_ref[rows, ctr] = jnp.where(prev_ent > 0,
                                           acc_ref[rows, ctr] + bw, bc)
            acc_ref[rows, ent] = jnp.maximum(prev_ent, be)
            acc_ref[rows, kbm] = jnp.minimum(acc_ref[rows, kbm], bkb)
            acc_ref[rows, msc] = jnp.minimum(acc_ref[rows, msc], ms)

    return kernel


@functools.partial(jax.jit, static_argnames=("n_l", "interpret", "cfg"))
def capscore_agg(ks, eids, ws, seg, ls, taus, salt, *, n_l: int,
                 interpret: bool | None = None,
                 cfg: TileConfig | None = None):
    """Fused multi-l scoring + per-key chunk aggregation (Pallas).

    Args:
      ks, eids: int32 [C] in KEY-SORTED order (the ChunkOrder pre-gathered
        view), C a multiple of the block size ``cfg.block[1]`` (use
        ops.capscore_agg for padding); ``ks`` ascending with EMPTY last.
      ws: float32 [C] weights, same order.
      seg: int32 [C] sorted segment ids of ``ks`` (0..n_seg-1).
      ls, taus: float32 [n_l] per-lane cap parameter / current threshold.
      salt: uint32 scalar shared by all lanes.
      cfg: tile config (static); None selects the platform flavor.  The
        element stream is double-buffered across grid steps (Mosaic grid
        pipeline / Triton num_stages) while the output stays resident.
    Returns:
      (w_total f32 [C + window], then entered f32 (0/1) / contrib f32 /
       kb_min f32 / min_score f32, each [C + window, n_l]) —
      segment-id-indexed rows; rows past the real segment count hold the
      reduction identities (the wrapper slices and transposes).
      ``window = cfg.block[1] + cfg.align``.
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg is None:
        cfg = tile_config("capscore_agg")
    bn = cfg.block[-1]
    window = bn + cfg.align
    C = ks.shape[0]
    assert C % bn == 0, C
    scalars = _scalars(ls, taus, salt) + (seg[::bn],)  # + block start segs
    view = lambda a: a.reshape(1, C)
    rows_out = C + window
    in_blk = lambda: pl.BlockSpec((1, bn), lambda i, *_: (0, i))
    shape = (rows_out, _agg_columns(n_l))
    operands = (*scalars, view(ks), view(eids), view(ws), view(seg))
    acc = _grid_call(
        _make_capscore_agg_kernel(n_l, bn, window, cfg.align), scalars,
        cfg=cfg, interpret=interpret, grid=(C // bn,),
        in_specs=[in_blk(), in_blk(), in_blk(), in_blk()],
        out_specs=pl.BlockSpec(shape, lambda i, *_: (0, 0)),
        out_shape=out_struct(shape, jnp.float32, *operands),
    )(*operands)
    return (acc[:, 0],) + tuple(acc[:, 1 + q * n_l:1 + (q + 1) * n_l]
                                for q in range(4))
