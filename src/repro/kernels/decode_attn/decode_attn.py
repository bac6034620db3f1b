"""Pallas TPU kernel: fused decode attention for the serving burst path.

The scheduler's hot loop (`repro.serve.scheduler`) feeds one work unit per
busy cache row per step: a chunk of ``s <= bucket`` queries per row
attending into that row's slice of the batched KV cache — context-prefill
chunks and non-committing candidate bursts ride the same call. The dense
path in `repro.serve.engine` materialises two (B, H, s, cap) score tensors
(RoPE + NoPE), a (B, s, cap) mask and the full probability tensor per
layer; this kernel fuses the whole thing into one online-softmax pass over
the cache, so scores/probabilities never touch HBM and cost scales with
cache *occupancy* rather than capacity.

Schedule:

    grid = (B, H, n_kv)        n_kv = cap_padded // blk_kv

The kv axis is "arbitrary": each (row, head) walks the row's cache blocks
left to right carrying an online-softmax accumulator (m, l, acc) in VMEM
scratch. Two structural wins over the dense decode path:

* **occupancy skip** — a cache block whose every slot is empty
  (``pos_k < 0``) is skipped entirely (`pl.when`): a mostly-empty
  high-capacity cache costs what its occupancy costs, while the dense
  einsums always pay full capacity;
* **no (s, cap) materialisation** — mask terms (filled slot, causal,
  window, in-burst segment) are index arithmetic against the staged
  (blk,) ``pos``/``seg`` tiles.

Cache-native layout: K/V tiles are staged from a free ``(B, cap, Hk*D)``
view of the serving cache via index maps (query head h reads the lane
block of kv head ``h // n_rep``) — no transpose or head replication of
K/V in memory, mirroring the windowed training kernel. Only the small
query/output and scale-sidecar tensors are transposed, so that every
block meets Mosaic's (8, 128) tiling rule (docs/kernels.md). MLA runs
through the same kernel in absorbed MQA form (Hk=1): the engine folds q
through W_UK and concatenates the
latent/rope streams so ``Dqk = r_kv + d_rope`` while values stay in the
latent (``Dv = r_kv != Dqk``); see `repro.serve.engine._mla_decode_layer`.

The full serve feature set is fused:

* per-row cursors / right-padded chunks — empty and padded slots carry
  ``pos = -1`` and are never attendable (the ``valid`` operand of
  ``make_decode_fn`` writes them that way);
* ``commit=False`` scoring bursts — no kernel-side difference: the burst's
  own tokens are already written into the cache tensors for the step, the
  kernel just attends what ``pos_k``/``seg_k`` describe;
* in-burst candidate isolation — ``seg_k >= 0`` entries are attendable
  only by queries of the same segment; ``seg_k < 0`` (committed context +
  shared suffix) by everyone;
* ring/window semantics — the mask is purely positional, so a ring cache
  (wrapped physical slots, monotone logical positions) needs no special
  handling; ``window == 0`` means unlimited (decode convention, matching
  ``_decode_mask``), ``window > 0`` bounds the attendable distance;
* SUM NoPE+ALiBi — rows flagged ``is_sum_q`` score a second (q_nope,
  k_nope) stream with the ALiBi distance bias instead of the RoPE'd
  stream, fused as a second matmul on the same tiles;
* GQA head groups and MLA ``Dv != Dqk`` — value tiles block on ``Dv``,
  score tiles on ``Dqk``.

Queries with no attendable key (fully padded rows) produce exactly zero
output, matching the dense path's ``any_ok`` guard. All index/flag
operands are int32 (no sub-byte loads); scores accumulate in fp32.

**Paged caches need no kernel changes.** When the scheduler runs the
paged KV layout (`repro.serve.cache` with a page table), the engine
gathers each row's pages into logical-slot order *before* this op —
``k``/``v``/``pos_k``/``seg_k`` arrive as the same per-row ``(B, cap,
...)`` views a contiguous cache would produce, holding identical values
at identical logical slots (RoPE is applied per-row positions on the
gathered view, so it cannot move inside the kernel). The kernel
therefore computes bit-identical outputs for paged and contiguous
layouts; see ``make_decode_fn`` and tests/test_paged_cache.py.

**Quantized KV (int8 codes + fp32 scale sidecar) is dequantized in the
kernel body.** On the quant path (``k_scale`` operand present) the k/v
tiles are staged as raw int8 codes straight from the cache — unroped,
undequantized — so quantized KV never round-trips through bf16 in HBM.
Per kv block the kernel: casts codes to fp32 in VMEM, RoPEs the
``[rope_start:]`` span using the staged slot positions (GQA rotates the
whole head dim, ``rope_start = 0``; absorbed MLA only the ``kpe`` tail,
``rope_start = r_kv``), then multiplies in the per-(slot, head) scale —
legal in either order because the rotation stays inside one scale group
(see ``repro.core.quant``). Two scale groups (``k_scale[..., 2]``) split
at ``rope_start`` cover MLA's separately-quantized latent/rope streams.
The NoPE stream needs no second cache operand when quantized: it is the
same codes dequantized without rotation, halving the kernel's
full-capacity HBM traffic vs the bf16 NoPE path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.windowed_attn.windowed_attn import NEG_INF

class DecodeStatics(NamedTuple):
    """Hashable per-call configuration of the decode kernel."""
    window: int          # 0 = unlimited (decode convention)
    scale: float
    block: int           # kv block size (divides the padded capacity)
    use_seg: bool        # in-burst candidate isolation active
    use_nope: bool       # SUM rows score the NoPE+ALiBi stream
    quant: bool          # int8 KV codes + fp32 scales; dequant in VMEM
    rope_start: int      # first key dim RoPE rotates (quant path only)
    kv_heads: int        # Hk: K/V are staged from a (B, cap, Hk*D) view
    interpret: bool


def _kernel(pos_q_ref, pos_k_ref, sum_q_ref, seg_q_ref, seg_k_ref, alibi_ref,
            q_ref, k_ref, v_ref, qn_ref, kn_ref, ks_ref, vs_ref, rinv_ref,
            o_ref,
            m_ref, l_ref, acc_ref,
            *, n_kv: int, window: int, scale: float,
            use_seg: bool, use_nope: bool, quant: bool, rope_start: int):
    hi = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-row operands arrive as lane-major (1, n) rows; query-side ones are
    # transposed to (s, 1) columns so every mask term broadcasts 2-D
    pos_k = pos_k_ref[0]                                   # (1, blk) int32

    # occupancy skip: an all-empty cache block (padding, or capacity the
    # row never reached) contributes nothing — skip its matmuls entirely
    @pl.when(jnp.any(pos_k >= 0))
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                # (s, Dqk)
        k = k_ref[0].astype(jnp.float32)                   # (blk, Dqk)
        kn = None
        if quant:
            # int8 path: the staged k tile is raw *unroped* codes. Build
            # the per-dim scale row (one scale per head group; two groups
            # when the latent/rope streams of absorbed MLA are separately
            # quantized, split at rope_start), dequantize for the NoPE
            # stream, and RoPE the [rope_start:] span in VMEM. Scales are
            # per (slot, head), so rope-then-scale == scale-then-rope (the
            # rotation is within the group) — scaling last keeps one
            # multiply off the trig path.
            dk = k.shape[-1]
            sc = ks_ref[0, 0].T                            # (blk, G)
            if sc.shape[-1] == 1:
                sc_vec = sc
            else:
                col = jax.lax.broadcasted_iota(jnp.int32, (1, dk), 1)
                sc_vec = jnp.where(col < rope_start,
                                   sc[:, 0:1], sc[:, 1:2])
            if use_nope:
                kn = k * sc_vec                            # unroped dequant
            p = jnp.maximum(pos_k, 0).astype(jnp.float32).T    # (blk, 1)
            ang = p * rinv_ref[...]                        # (blk, span/2)
            cosv, sinv = jnp.cos(ang), jnp.sin(ang)
            span = k[:, rope_start:]
            half = span.shape[-1] // 2
            x1, x2 = span[:, :half], span[:, half:]
            rot = jnp.concatenate([x1 * cosv - x2 * sinv,
                                   x1 * sinv + x2 * cosv], axis=-1)
            if rope_start:
                rot = jnp.concatenate([k[:, :rope_start], rot], axis=-1)
            k = rot * sc_vec
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        pos_q = pos_q_ref[0].T                             # (s, 1) int32
        d = pos_q - pos_k                                  # (s, blk)
        if use_nope:
            qn = qn_ref[0, 0].astype(jnp.float32)
            if not quant:
                kn = kn_ref[0].astype(jnp.float32)
            sn = jax.lax.dot_general(qn, kn, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sn = sn * scale - alibi_ref[hi] * d.astype(jnp.float32)
            s = jnp.where(sum_q_ref[0].T != 0, sn, s)

        # mask: filled slot + causal (+ window) (+ in-burst segment)
        mask = (pos_k >= 0) & (d >= 0)
        if window > 0:
            mask &= d <= window
        if use_seg:
            seg_k = seg_k_ref[0]
            mask &= (seg_k < 0) | (seg_k == seg_q_ref[0].T)
        s = jnp.where(mask, s, NEG_INF)

        # online softmax across the kv blocks
        m_prev = m_ref[...]                                # (s, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        w = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=-1, keepdims=True)
        m_ref[...] = m_new

        v = v_ref[0].astype(jnp.float32)                   # (blk, Dv)
        if quant:
            v = v * vs_ref[0, 0].T                         # (blk, 1) scale
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            w, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ikv == n_kv - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        # rows with no attendable key output exactly 0 (dense any_ok guard)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _pad_cap(x: jax.Array, cap_pad: int, fill) -> jax.Array:
    """Pad the capacity axis (axis 1) of a cache-side operand to cap_pad."""
    cap = x.shape[1]
    if cap == cap_pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, cap_pad - cap)
    return jnp.pad(x, widths, constant_values=fill)


def prepare_decode_inputs(
    q: jax.Array,                 # (B, s, H, Dqk)   RoPE'd queries
    k: jax.Array,                 # (B, cap, Hk, Dqk) read-time-RoPE'd keys
                                  #   (int8 unroped codes on the quant path)
    v: jax.Array,                 # (B, cap, Hk, Dv)
    pos_q: jax.Array,             # (B, s) int32
    pos_k: jax.Array,             # (B, cap) int32; -1 = empty slot
    *,
    window: int,
    sum_q: Optional[jax.Array],
    seg_q: Optional[jax.Array],
    seg_k: Optional[jax.Array],
    q_nope: Optional[jax.Array],
    k_nope: Optional[jax.Array],
    alibi: Optional[jax.Array],
    scale: Optional[float],
    block_size: int,
    interpret: bool,
    k_scale: Optional[jax.Array] = None,    # (B, cap, Hk, G) fp32, G in {1,2}
    v_scale: Optional[jax.Array] = None,    # (B, cap, Hk) fp32
    rope_inv: Optional[jax.Array] = None,   # ((Dqk - rope_start)/2,) fp32
    rope_start: int = 0,
) -> Tuple[DecodeStatics, Tuple[jax.Array, ...]]:
    """Normalise optional operands to concrete arrays + hashable statics.

    Pads the capacity axis to a multiple of the kv block (padding slots
    carry ``pos = -1`` so the occupancy skip drops them for free) — the
    scheduler's ``capacity = ctx + bucket`` need not be block-aligned.

    ``k_scale`` switches the kernel to the quantized-KV contract
    (docs/kernels.md): ``k``/``v`` are raw int8 codes straight from the
    cache — unroped, undequantized — and the kernel dequantizes and RoPEs
    ([``rope_start``:] span, inverse frequencies ``rope_inv``) in VMEM.
    The NoPE stream then needs no separate ``k_nope`` operand: it is the
    same codes dequantized without rotation.
    """
    b, s_len, h, d = q.shape
    cap, hk = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    # pad the cache operands up to a block multiple: the scheduler's
    # capacity is arbitrary and padding slots (pos = -1) are skipped
    blk = min(block_size, cap)
    cap_pad = ((cap + blk - 1) // blk) * blk

    quant = k_scale is not None
    if quant:
        assert v_scale is not None and rope_inv is not None, \
            "quantized decode needs k_scale, v_scale and rope_inv together"
        assert k_nope is None, \
            "quantized decode derives the NoPE stream from the codes"
    use_nope = q_nope is not None and sum_q is not None
    use_seg = seg_q is not None and seg_k is not None

    def row(x, fill=None):              # (B, n) -> lane-major (B, 1, n)
        x = jnp.asarray(x, jnp.int32)
        return (x if fill is None else _pad_cap(x, cap_pad, fill))[:, None]

    def heads(x):                       # (B, s, H, D) -> (B, H, s, D)
        return jnp.swapaxes(x, 1, 2)

    def kv(x):                          # (B, cap, Hk, D) -> (B, cap_pad, Hk*D)
        x = _pad_cap(x, cap_pad, 0)
        return x.reshape(b, cap_pad, -1)

    def side(x):                        # (B, cap, Hk, G) -> (B, Hk, G, cap_pad)
        return jnp.transpose(_pad_cap(x.astype(jnp.float32), cap_pad, 0),
                             (0, 2, 3, 1))

    zeros_q = jnp.zeros((b, s_len), jnp.int32)
    alibi_f = (alibi if alibi is not None
               else jnp.zeros((h,))).astype(jnp.float32)
    # without the NoPE stream the kernel never reads qn/kn: stage single-
    # element placeholders (their BlockSpecs shrink to match) instead of a
    # full-capacity zero tensor per layer per step
    use_kn = use_nope and not quant
    one = jnp.zeros((b, 1, 1, 1), jnp.float32)
    qn = heads(q_nope) if use_nope else one.astype(q.dtype)
    kn = kv(k_nope) if use_kn else one[:, 0].astype(k.dtype)
    # scale sidecars: padded slots get scale 0 (their pos = -1 already
    # makes them unattendable; 0-scale dequant is exact zeros either way)
    ks = side(k_scale) if quant else one
    vs = side(v_scale[..., None]) if quant else one
    rinv = (rope_inv.astype(jnp.float32)[None] if quant
            else jnp.zeros((1, 1), jnp.float32))

    arrays = (row(pos_q), row(pos_k, -1),
              row(sum_q if sum_q is not None else zeros_q),
              row(seg_q if use_seg else zeros_q),
              row(seg_k if use_seg else jnp.zeros((b, cap)), -1),
              alibi_f, heads(q), kv(k), kv(v), qn, kn, ks, vs, rinv)
    st = DecodeStatics(window=int(window), scale=float(scale), block=blk,
                       use_seg=use_seg, use_nope=use_nope,
                       quant=quant, rope_start=int(rope_start),
                       kv_heads=int(hk),
                       interpret=bool(interpret))
    return st, arrays


def decode_attention_bshd(st: DecodeStatics, pos_q, pos_k, sum_q, seg_q,
                          seg_k, alibi, q, k, v, qn, kn, ks, vs,
                          rinv) -> jax.Array:
    """Normalised forward over prepared operands: returns o (B, s, H, Dv).

    Kernel layouts (``prepare_decode_inputs``): queries ``(B, H, s, D)``;
    K/V as the free ``(B, cap, Hk*D)`` view, so a head is a ``D``-lane
    block of the last axis; per-row ints as ``(B, 1, n)`` rows; scale
    sidecars ``(B, Hk, G, cap)``; ALiBi slopes whole in SMEM.
    """
    b, h, s_len, d = q.shape
    cap = k.shape[1]
    hk = st.kv_heads
    dv = v.shape[-1] // hk
    n_rep = h // hk
    blk = st.block
    assert cap % blk == 0, f"cap={cap} not divisible by block {blk}"
    n_kv = cap // blk

    def q_idx(bi, hi, ki):
        return (bi, hi, 0, 0)

    def kv_idx(bi, hi, ki):
        return (bi, ki, hi // n_rep)

    def kvh_idx(bi, hi, ki):              # for single-head nope caches
        return (bi, ki, 0)

    def side_idx(bi, hi, ki):
        return (bi, hi // n_rep, 0, ki)

    one = lambda bi, hi, ki: (bi, 0, 0, 0)    # single-element placeholders
    use_kn = st.use_nope and not st.quant
    qn_map = q_idx if st.use_nope else one
    kn_map = (lambda bi, hi, ki: (bi, 0, 0)) if not use_kn else (
        kv_idx if kn.shape[2] == hk * d else kvh_idx)
    qn_spec = ((1, 1, s_len, qn.shape[-1]) if st.use_nope else (1, 1, 1, 1))
    kn_spec = ((1, blk, d) if use_kn else (1, 1, 1))
    # quant sidecars ride the same kv-block schedule as k/v; the rope
    # inverse-frequency row is tiny and staged whole per grid step
    side_map = side_idx if st.quant else one
    ks_spec = ((1, 1, ks.shape[2], blk) if st.quant else (1, 1, 1, 1))
    vs_spec = ((1, 1, 1, blk) if st.quant else (1, 1, 1, 1))

    def row_q(bi, hi, ki):
        return (bi, 0, 0)

    def row_k(bi, hi, ki):
        return (bi, 0, ki)

    grid = (b, h, n_kv)
    out = pl.pallas_call(
        functools.partial(_kernel, n_kv=n_kv, window=st.window,
                          scale=st.scale, use_seg=st.use_seg,
                          use_nope=st.use_nope, quant=st.quant,
                          rope_start=st.rope_start),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, s_len), row_q),                     # pos_q
            pl.BlockSpec((1, 1, blk), row_k),                       # pos_k
            pl.BlockSpec((1, 1, s_len), row_q),                     # sum_q
            pl.BlockSpec((1, 1, s_len), row_q),                     # seg_q
            pl.BlockSpec((1, 1, blk), row_k),                       # seg_k
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # alibi
            pl.BlockSpec((1, 1, s_len, d), q_idx),                  # q
            pl.BlockSpec((1, blk, d), kv_idx),                      # k
            pl.BlockSpec((1, blk, dv), kv_idx),                     # v
            pl.BlockSpec(qn_spec, qn_map),                          # qn
            pl.BlockSpec(kn_spec, kn_map),                          # kn
            pl.BlockSpec(ks_spec, side_map),                        # k scales
            pl.BlockSpec(vs_spec, side_map),                        # v scales
            pl.BlockSpec(rinv.shape, lambda bi, hi, ki: (0, 0)),    # rinv
        ],
        out_specs=pl.BlockSpec((1, 1, s_len, dv), q_idx),
        out_shape=jax.ShapeDtypeStruct((b, h, s_len, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((s_len, 1), jnp.float32),      # m (row max)
            pltpu.VMEM((s_len, 1), jnp.float32),      # l (row denom)
            pltpu.VMEM((s_len, dv), jnp.float32),     # acc (value accum)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=st.interpret,
    )(pos_q, pos_k, sum_q, seg_q, seg_k, alibi, q, k, v, qn, kn, ks, vs,
      rinv)
    return jnp.swapaxes(out, 1, 2)


__all__ = ["DecodeStatics", "prepare_decode_inputs", "decode_attention_bshd"]
