"""Bytes the chunk step's Pallas kernels must move, from their shapes.

``capscore_agg`` (kernels/capscore) reads the key-sorted chunk view
(keys, element ids, weights, segment ids: four [C] 32-bit operands) with
its scalars, and writes one packed f32 output of ``C + window`` rows by
``1 + 4 * n_l`` columns rounded up to whole 128-lane tiles, where
``window`` is the block width plus the row alignment.  The block (256)
and alignment (8) are the TPU tile of the kernel as of this benchmark's
definition; they are part of the yardstick, not read from the program.
The kernel does no matrix product, so only the HBM bound applies.
"""
from __future__ import annotations

CAPSCORE_AGG_BLOCK = 256
CAPSCORE_AGG_ALIGN = 8
LANE_TILE = 128
WORD = 4


def capscore_agg_bytes(chunk: int, n_l: int) -> int:
    window = CAPSCORE_AGG_BLOCK + CAPSCORE_AGG_ALIGN
    cols = -(-(1 + 4 * n_l) // LANE_TILE) * LANE_TILE
    scalars = (2 * n_l + 1 + chunk // CAPSCORE_AGG_BLOCK) * WORD
    return 4 * chunk * WORD + scalars + (chunk + window) * cols * WORD
