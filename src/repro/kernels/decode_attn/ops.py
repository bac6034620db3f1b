"""Public decode-attention op: cache-layout operands, custom Pallas lowering.

``decode_attention`` is the serving analogue of
``repro.kernels.windowed_attn.ops.windowed_attention``: it normalises the
optional serve operands (SUM flags, in-burst segments, NoPE stream) to
concrete arrays plus hashable statics and lowers to the fused Pallas
kernel in ``decode_attn.py``. Differences from the training op:

* operands arrive in the serving cache layout — queries ``(B, s, H, Dqk)``,
  cache-side tensors ``(B, cap, Hk, D)`` — and the index maps do the GQA
  head-group addressing on a free ``(B, cap, Hk*D)`` view, so K/V are
  never transposed or head-replicated in memory;
* no VJP: decode never trains, so the op is forward-only (scores are read
  under ``jax.lax.stop_gradient`` semantics by construction);
* the capacity axis is padded to a kv-block multiple with ``pos = -1``
  slots, which the kernel's occupancy skip drops — arbitrary scheduler
  capacities stay legal without degrading the block size;
* paged KV reaches this op already gathered: the engine resolves each
  row's page table to a logical-slot-ordered ``(B, cap, ...)`` view
  before calling (``repro.serve.cache.physical_slots``), so the op's
  contract — and its outputs — are identical for paged and contiguous
  caches.

``interpret=None`` auto-resolves via ``repro.kernels.default_interpret``
(Mosaic on TPU, the Pallas interpreter elsewhere so the kernel *body* is
what CPU tests exercise).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import default_interpret
from repro.kernels.autotune import decode_block
from repro.kernels.decode_attn.decode_attn import (
    decode_attention_bshd, prepare_decode_inputs)


def decode_attention(
    q: jax.Array,                  # (B, s, H, Dqk)   RoPE'd queries
    k: jax.Array,                  # (B, cap, Hk, Dqk) read-time-RoPE'd keys
                                   #   (int8 unroped codes when quantized)
    v: jax.Array,                  # (B, cap, Hk, Dv)
    pos_q: jax.Array,              # (B, s) int32 query positions
    pos_k: jax.Array,              # (B, cap) int32 slot positions; -1 empty
    *,
    window: int,                   # 0 = unlimited (decode convention)
    is_sum_q: Optional[jax.Array] = None,   # (B, s) flags
    q_nope: Optional[jax.Array] = None,     # (B, s, H, Dqk)
    k_nope: Optional[jax.Array] = None,     # (B, cap, Hk, Dqk) unroped
    alibi: Optional[jax.Array] = None,      # (H,) f32
    seg_q: Optional[jax.Array] = None,      # (B, s) int32; -1 = shared
    seg_k: Optional[jax.Array] = None,      # (B, cap) int32; -1 = shared
    scale: Optional[float] = None,
    block_size: Optional[int] = None,       # None = autotuned
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,    # (B, cap, Hk, G) fp32; int8 KV
    v_scale: Optional[jax.Array] = None,    # (B, cap, Hk) fp32
    rope_start: int = 0,                    # first roped key dim (quant)
    rope_theta: float = 10000.0,
    mesh=None,                              # (data, model) serving mesh
) -> jax.Array:
    """Fused burst attention into the batched KV cache -> (B, s, H, Dv).

    ``block_size=None`` resolves the kv tile via
    ``repro.kernels.autotune.decode_block`` (its geometry table on TPU,
    the historic 64 in interpret mode). ``k_scale`` switches to the
    quantized-KV contract: ``k``/``v`` are raw int8 cache codes and
    dequant + RoPE (span ``[rope_start:]``, base ``rope_theta``) happen
    inside the kernel — see docs/kernels.md.

    ``mesh`` runs the kernel once per shard under ``shard_map`` (GSPMD
    cannot partition a Mosaic kernel): cache rows over ``data`` and KV
    heads — with their query-head groups — over ``model``, each axis only
    where it divides; an axis that does not divide stays replicated.
    """
    if mesh is not None:
        operands = dict(q=q, k=k, v=v, pos_q=pos_q, pos_k=pos_k,
                        is_sum_q=is_sum_q, q_nope=q_nope, k_nope=k_nope,
                        alibi=alibi, seg_q=seg_q, seg_k=seg_k,
                        k_scale=k_scale, v_scale=v_scale)
        operands = {n: x for n, x in operands.items() if x is not None}
        row = "data" if q.shape[0] % mesh.shape["data"] == 0 else None
        head = "model" if k.shape[2] % mesh.shape["model"] == 0 else None
        specs = {n: (P(head) if n == "alibi" else
                     P(row, None) if x.ndim == 2 else
                     P(row, None, head, *[None] * (x.ndim - 3)))
                 for n, x in operands.items()}
        local = functools.partial(
            decode_attention, window=window, scale=scale,
            block_size=block_size, interpret=interpret,
            rope_start=rope_start, rope_theta=rope_theta)
        return jax.shard_map(lambda ops: local(**ops), mesh=mesh,
                             in_specs=(specs,),
                             out_specs=P(row, None, head, None),
                             check_vma=False)(operands)
    interpret = default_interpret(interpret)
    if block_size is None:
        block_size = decode_block(k.shape[1], dqk=q.shape[-1],
                                  dv=v.shape[-1], interpret=interpret)
    use_nope = q_nope is not None and is_sum_q is not None
    rope_inv = None
    if k_scale is not None:
        from repro.models.layers import rope_freqs
        rope_inv = rope_freqs(q.shape[-1] - rope_start, rope_theta)
    st, arrays = prepare_decode_inputs(
        q, k, v, pos_q, pos_k, window=window,
        sum_q=is_sum_q if use_nope else None,
        seg_q=seg_q, seg_k=seg_k,
        q_nope=q_nope if use_nope else None,
        k_nope=k_nope if use_nope else None,
        alibi=alibi if use_nope else None,
        scale=scale, block_size=block_size, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, rope_inv=rope_inv,
        rope_start=rope_start)
    return decode_attention_bshd(st, *arrays)


__all__ = ["decode_attention"]
