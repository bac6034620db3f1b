"""Block-size autotuning for the Pallas attention kernels.

The decode kernel's kv tile (``block_size`` in
``repro.kernels.decode_attn``) and the training kernel's band tile
(``ModelConfig.attn_block_size``) used to be fixed constants (64 / 256).
Both are now resolved here, from two sources consulted in order:

1. **measured table** — ``measure_decode`` / ``measure_train`` sweep the
   candidate tiles with real timed kernel calls and memoize the winner.
   Sweeps only ever *measure* on TPU: interpret-mode wall time profiles
   the Pallas interpreter, not the kernel, so off-TPU the sweep functions
   report the table default and store nothing. Benchmarks
   (``benchmarks.kernels_micro``) run the sweeps and publish the table.
2. **built-in defaults** — a small geometry-keyed heuristic. On TPU the
   kv tile wants to be a multiple of the 128 lane width and bounded by
   what (k + v + nope) tiles fit comfortably in VMEM; in interpret mode
   tile size has no perf meaning, so the defaults reproduce the historic
   constants exactly (decode 64, train 256) and CPU tests/benches are
   byte-for-byte unchanged.

Lookups are pure host arithmetic plus a dict probe — safe to call inside
a jit trace (the engine resolves ``block_size=None`` at trace time from
the static cache capacity). Only the ``measure_*`` entry points execute
device code, and they are called from benchmarks / startup paths, never
from inside a trace.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

from repro.kernels import default_interpret

#: measured winners: key -> block size (populated by measure_* on TPU)
_MEASURED: Dict[Tuple, int] = {}

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


def _decode_key(cap: int, dqk: int, dv: int) -> Tuple:
    return ("decode", _pow2_le(max(cap, 1)), int(dqk), int(dv))


def _train_key(seq: int, head_dim: int) -> Tuple:
    return ("train", _pow2_le(max(seq, 1)), int(head_dim))


def decode_block(cap: int, *, dqk: int = 64, dv: int = 64,
                 interpret: Optional[bool] = None) -> int:
    """kv tile for the decode-attention kernel over a ``cap``-slot cache.

    Interpret mode returns the historic 64 (tile size is semantics-free
    there — the kernel pads cap to a block multiple either way). On TPU:
    the largest lane-aligned candidate that the capacity warrants and the
    VMEM budget admits, unless a measured sweep recorded a winner.
    """
    interpret = default_interpret(interpret)
    if interpret:
        return 64
    hit = _MEASURED.get(_decode_key(cap, dqk, dv))
    if hit is not None:
        return hit
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
    multiple). On TPU: measured winner if any, else the largest candidate
    the sequence and VMEM budget warrant.
    """
    interpret = default_interpret(interpret)
    if interpret:
        return 256
    hit = _MEASURED.get(_train_key(seq, head_dim))
    if hit is not None:
        return hit
    vmem_cap = _VMEM_TILE_BUDGET // max(3 * head_dim * 4, 1)
    best = TRAIN_CANDIDATES[0]
    for c in TRAIN_CANDIDATES:
        if c <= max(_pow2_le(seq), 128) and c <= vmem_cap:
            best = c
    return best


def _time_best_of(fn, *args, iters: int = 5) -> float:
    import jax
    jax.block_until_ready(fn(*args))          # compile outside the clock
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_decode(cap: int, *, s: int = 8, hq: int = 8, hk: int = 2,
                   dqk: int = 64, dv: int = 64,
                   candidates: Optional[Sequence[int]] = None,
                   iters: int = 5,
                   interpret: Optional[bool] = None) -> Dict:
    """Sweep decode kv tiles with timed kernel calls; memoize the winner.

    Returns ``{"block", "measured", "timings_us"}``. Off-TPU (interpret)
    nothing is timed or stored — the report carries the table default so
    callers (kernels_micro) can still publish what a config resolves to.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attn.ops import decode_attention

    interpret = default_interpret(interpret)
    if interpret:
        return {"block": decode_block(cap, dqk=dqk, dv=dv,
                                      interpret=interpret),
                "measured": False, "timings_us": None}
    kk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kk[0], (1, s, hq, dqk), jnp.float32)
    k = jax.random.normal(kk[1], (1, cap, hk, dqk), jnp.float32)
    v = jax.random.normal(kk[2], (1, cap, hk, dv), jnp.float32)
    pos_q = jnp.full((1, s), cap - 1, jnp.int32)
    pos_k = jnp.arange(cap, dtype=jnp.int32)[None]
    timings = {}
    for blk in (candidates or DECODE_CANDIDATES):
        fn = jax.jit(lambda q, k, v, b=blk: decode_attention(
            q, k, v, pos_q, pos_k, window=0, block_size=b,
            interpret=interpret))
        timings[blk] = _time_best_of(fn, q, k, v, iters=iters) * 1e6
    best = min(timings, key=timings.get)
    _MEASURED[_decode_key(cap, dqk, dv)] = int(best)
    return {"block": int(best), "measured": True, "timings_us": timings}


def measure_train(seq: int, *, head_dim: int = 64, heads: int = 4,
                  window: int = 128,
                  candidates: Optional[Sequence[int]] = None,
                  iters: int = 5,
                  interpret: Optional[bool] = None) -> Dict:
    """Sweep the windowed training kernel's band tile; memoize the winner
    (TPU only, as in ``measure_decode``)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.windowed_attn.ops import windowed_attention

    interpret = default_interpret(interpret)
    if interpret:
        return {"block": train_block(seq, head_dim, interpret=interpret),
                "measured": False, "timings_us": None}
    kk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kk[0], (1, seq, heads, head_dim), jnp.float32)
    k = jax.random.normal(kk[1], (1, seq, heads, head_dim), jnp.float32)
    v = jax.random.normal(kk[2], (1, seq, heads, head_dim), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (1, seq))
    timings = {}
    for blk in (candidates or TRAIN_CANDIDATES):
        fn = jax.jit(lambda q, k, v, b=blk: windowed_attention(
            q, k, v, pos_q=pos, pos_k=pos, window=window, block_size=b,
            interpret=interpret))
        timings[blk] = _time_best_of(fn, q, k, v, iters=iters) * 1e6
    best = min(timings, key=timings.get)
    _MEASURED[_train_key(seq, head_dim)] = int(best)
    return {"block": int(best), "measured": True, "timings_us": timings}


def measured_table() -> Dict[str, int]:
    """Snapshot of the measured winners (JSON-friendly keys), for
    benchmark artifacts."""
    return {"/".join(str(p) for p in k): v for k, v in _MEASURED.items()}


__all__ = ["DECODE_CANDIDATES", "TRAIN_CANDIDATES", "decode_block",
           "train_block", "measure_decode", "measure_train",
           "measured_table"]
