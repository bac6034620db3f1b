"""Block sizes for the Pallas attention kernels, from a geometry table.

The decode kernel's kv tile (``block_size`` in
``repro.kernels.decode_attn``) and the training kernel's band tile
(``ModelConfig.attn_block_size``) are resolved here by a small
geometry-keyed heuristic. On TPU the tile is a multiple of the 128 lane
width, as large as the capacity or sequence warrants and bounded by what
(k + v + nope) tiles fit comfortably in VMEM. In interpret mode tile size
has no perf meaning, so the table returns the historic constants (decode
64, train 256) and CPU results are unchanged. A block measured faster on
the chip enters as a change to this table.

Lookups are pure host arithmetic — safe to call inside a jit trace (the
engine resolves ``block_size=None`` at trace time from the static cache
capacity).
"""
from __future__ import annotations

from typing import Optional, Sequence

from repro.kernels import default_interpret

#: VMEM budget the kv-side tiles of one grid step may occupy (bytes).
#: Conservative: k + v (+ nope k) tiles in fp32 plus scratch must fit in
#: ~16 MB/core alongside double buffering.
_VMEM_TILE_BUDGET = 1 << 20

DECODE_CANDIDATES: Sequence[int] = (64, 128, 256, 512)
TRAIN_CANDIDATES: Sequence[int] = (128, 256, 512)


def _pow2_le(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def decode_block(cap: int, *, dqk: int = 64, dv: int = 64,
                 interpret: Optional[bool] = None) -> int:
    """kv tile for the decode-attention kernel over a ``cap``-slot cache.

    Interpret mode returns the historic 64 (tile size is semantics-free
    there — the kernel pads cap to a block multiple either way). On TPU:
    the largest lane-aligned candidate that the capacity warrants and the
    VMEM budget admits.
    """
    interpret = default_interpret(interpret)
    if interpret:
        return 64
    # ~3 fp32 tiles of width (dqk + dqk + dv) stream per block step
    vmem_cap = _VMEM_TILE_BUDGET // max((2 * dqk + dv) * 4, 1)
    best = DECODE_CANDIDATES[0]
    for c in DECODE_CANDIDATES:
        if c <= max(_pow2_le(cap), 128) and c <= vmem_cap:
            best = c
    return best


def train_block(seq: int, head_dim: int, *,
                interpret: Optional[bool] = None) -> int:
    """Band tile for the windowed training kernel at sequence ``seq``.

    Interpret mode returns the historic 256 (``choose_block`` takes a
    shorter sequence whole and pads a longer ragged one to a block
    multiple). On TPU: the largest candidate the sequence and VMEM budget
    warrant.
    """
    interpret = default_interpret(interpret)
    if interpret:
        return 256
    vmem_cap = _VMEM_TILE_BUDGET // max(3 * head_dim * 4, 1)
    best = TRAIN_CANDIDATES[0]
    for c in TRAIN_CANDIDATES:
        if c <= max(_pow2_le(seq), 128) and c <= vmem_cap:
            best = c
    return best


__all__ = ["DECODE_CANDIDATES", "TRAIN_CANDIDATES", "decode_block",
           "train_block"]
