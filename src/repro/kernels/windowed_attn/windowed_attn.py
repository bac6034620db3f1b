"""Pallas TPU kernel: flash-style windowed causal attention with the DTI
semantics fused (SUM isolation, SUM NoPE+ALiBi dual scores, distance-based
hidden-state reset) — the compute hot-spot of the paper's training step.

TPU adaptation (DESIGN.md §3): the paper's GPU implementation is a masked
SDPA; here the window becomes a *blocked local* schedule tuned for the MXU
and VMEM:

  grid = (B, H, n_q_blocks, n_kv_blocks)     n_kv = window//blk + 1

Each (q-block, kv-block) step stages (blk, D) tiles HBM->VMEM, runs the
score matmul on the MXU in fp32, applies every DTI mask term via index
arithmetic (no S x S mask ever materialises), and maintains an online-
softmax accumulator in VMEM scratch across the kv dimension (declared
"arbitrary" so the accumulator carries). The hidden-state reset rides the
same pass as a second value stream: acc_r += w * a(d) * (v0 - v), folded
into the final normalisation — zero extra HBM traffic for the reset beyond
reading v0.

All mask/positional inputs are int32 (pos) / int32 (flags) so the kernel
has no sub-byte loads. GQA is handled by index-mapping query head h onto
kv head h // n_rep — K/V are never repeated in memory.

The forward also emits the per-row softmax logsumexp (B, H, 1, S) — the flash
residual the backward kernels (``windowed_attn_bwd``) use to recompute
probabilities blockwise instead of storing them; see docs/kernels.md for
the fwd/bwd contract.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


class AttnStatics(NamedTuple):
    """Hashable per-call configuration shared by the fwd and bwd kernels
    (the ``nondiff`` argument of the custom_vjp in ops.py)."""
    window: int
    scale: float
    block: int
    sum_isolated: bool
    use_seg: bool
    use_nope: bool
    use_reset: bool
    y_min: float
    y_max: float
    midpoint: float
    interpret: bool


def choose_block(s: int, block_size: int) -> Tuple[int, int]:
    """-> (block, padded S). One block when S fits in ``block_size``;
    otherwise ``block_size`` tiles over S padded up to a block multiple.
    Mosaic only tiles a sequence axis in lane-aligned blocks (or takes it
    whole), so ragged S is padded rather than cut into gcd-sized blocks."""
    blk = min(block_size, s)
    return blk, -(-s // blk) * blk


def n_kv_blocks(window: int, blk: int, n_q: int) -> int:
    """KV-band depth: how many kv blocks each q block attends (window plus
    in-block causal tail, +1 when the window is not block-aligned)."""
    n_kv = min(window // blk + 1, n_q) + (0 if window % blk == 0 else 1)
    return min(max(n_kv, 1), n_q)


def _kernel(pos_q_ref, pos_k_ref, sum_q_ref, sum_k_ref, valid_k_ref,
            seg_q_ref, seg_k_ref,
            alibi_ref,
            q_ref, k_ref, v_ref, qn_ref, kn_ref, v0_ref,
            o_ref, lse_ref,
            m_ref, l_ref, acc_ref,
            *, blk: int, n_kv: int, window: int, scale: float,
            sum_isolated: bool, use_seg: bool, use_nope: bool,
            use_reset: bool, y_min: float, y_max: float, midpoint: float):
    ikv = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (blk, D)
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # per-row operands arrive as (1, blk) rows; query-side ones are
    # transposed to (blk, 1) columns so every mask term broadcasts 2-D
    pos_q = pos_q_ref[0].T                                # (blk, 1) int32
    pos_k = pos_k_ref[0]                                  # (1, blk)
    d = pos_q - pos_k                                     # (blk, blk)
    sum_q = sum_q_ref[0].T != 0                           # (blk, 1)

    if use_nope:
        qn = qn_ref[0, 0].astype(jnp.float32)
        kn = kn_ref[0, 0].astype(jnp.float32)
        sn = jax.lax.dot_general(qn, kn, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        sn = sn - alibi_ref[pl.program_id(1)] * d.astype(jnp.float32)
        s = jnp.where(sum_q, sn, s)

    # mask: causal + window + key-padding (+ SUM isolation) (+ same packed
    # segment) + real kv block
    mask = (d >= 0) & (d <= window) & (valid_k_ref[0] != 0)
    if sum_isolated:
        mask &= (sum_k_ref[0] == 0) | (d == 0)
    if use_seg:
        mask &= seg_q_ref[0].T == seg_k_ref[0]
    j_actual = iq - (n_kv - 1) + ikv
    mask &= j_actual >= 0                                  # clamped block
    s = jnp.where(mask, s, NEG_INF)

    # online softmax
    m_prev = m_ref[...]                                    # (blk, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    w = jnp.exp(s - m_new)
    w = jnp.where(mask, w, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=-1, keepdims=True)
    m_ref[...] = m_new

    v = v_ref[0, 0].astype(jnp.float32)
    acc = acc_ref[...] * alpha
    acc += jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    if use_reset:
        a = y_min + (y_max - y_min) * jax.nn.sigmoid(
            d.astype(jnp.float32) - midpoint)
        wr = w * a * sum_q.astype(jnp.float32)
        dv = v0_ref[0, 0].astype(jnp.float32) - v
        acc += jax.lax.dot_general(wr, dv, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(ikv == n_kv - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)
        # flash residual: rows with no attendable key get +1e30 so the bwd
        # recompute exp(s - lse) underflows to exactly 0 for every key
        lse_ref[0, 0] = jnp.where(l > 0, m_ref[...] + jnp.log(safe),
                                  -NEG_INF).T


def prepare_inputs(
    q: jax.Array,                 # (B, H, S, D)
    k: jax.Array,                 # (B, Hk, S, D)
    v: jax.Array,
    pos_q: jax.Array,             # (B, S) int32
    pos_k: jax.Array,
    *,
    window: int,
    sum_q: Optional[jax.Array],
    sum_k: Optional[jax.Array],
    valid_k: Optional[jax.Array],
    seg_q: Optional[jax.Array],
    seg_k: Optional[jax.Array],
    q_nope: Optional[jax.Array],
    k_nope: Optional[jax.Array],
    alibi: Optional[jax.Array],
    v0: Optional[jax.Array],
    reset: Optional[tuple],
    sum_isolated: bool,
    scale: Optional[float],
    block_size: int,
    interpret: bool,
) -> Tuple[AttnStatics, Tuple[jax.Array, ...]]:
    """Normalise optional operands to concrete arrays + hashable statics.

    The array tuple is exactly the differentiable-argument order of the
    custom_vjp in ops.py: (q, k, v, qn, kn, v0, alibi, pos_q, pos_k,
    sum_q, sum_k, valid_k, seg_q, seg_k). The sequence axis is padded to
    a block multiple (padded keys carry ``valid_k = 0``; the caller drops
    the padded query rows), and every per-row int operand is laid out as
    ``(B, 1, S_pad)`` so its kernel block ``(1, blk)`` is lane-major.
    """
    b, h, s, d = q.shape
    blk, s_pad = choose_block(s, block_size)
    if scale is None:
        scale = d ** -0.5

    use_nope = q_nope is not None
    use_reset = reset is not None and v0 is not None
    use_seg = seg_q is not None and seg_k is not None

    def seq(x):                         # pad axis 2 of a (B, H, S, D) array
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))

    def row(x, fill=0):                 # (B, S) -> (B, 1, S_pad) int32
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, ((0, 0), (0, s_pad - s)),
                       constant_values=fill)[:, None, :]

    zeros = jnp.zeros((b, s), jnp.int32)
    alibi_f = (alibi if alibi is not None
               else jnp.zeros((h,))).astype(jnp.float32)
    # value dim may differ from the qk dim (MLA: v_head_dim != qk_head)
    zero_qk = jnp.zeros((b, 1, s_pad, d), q.dtype)
    qn = seq(q_nope) if use_nope else zero_qk
    kn = seq(k_nope) if use_nope else zero_qk
    v0_ = (seq(v0) if use_reset
           else jnp.zeros((b, 1, s_pad, v.shape[-1]), q.dtype))
    y_min, y_max, midpoint = reset if use_reset else (0.0, 0.0, 0.0)

    st = AttnStatics(window=int(window), scale=float(scale), block=blk,
                     sum_isolated=bool(sum_isolated), use_seg=use_seg,
                     use_nope=use_nope, use_reset=use_reset,
                     y_min=float(y_min), y_max=float(y_max),
                     midpoint=float(midpoint), interpret=bool(interpret))
    arrays = (seq(q), seq(k), seq(v), qn, kn, v0_, alibi_f,
              row(pos_q), row(pos_k),
              row(sum_q if sum_q is not None else zeros),
              row(sum_k if sum_k is not None else zeros),
              row(valid_k if valid_k is not None else zeros + 1),
              row(seg_q if use_seg else zeros),
              row(seg_k if use_seg else zeros))
    return st, arrays


def windowed_attention_fwd_bhsd(
        st: AttnStatics, q, k, v, qn, kn, v0, alibi,
        pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k,
) -> Tuple[jax.Array, jax.Array]:
    """Normalised forward: returns (o (B,H,S,Dv), lse (B,H,1,S) fp32)."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    hk = k.shape[1]
    n_rep = h // hk
    blk = st.block
    assert s % blk == 0, f"S={s} not divisible by block {blk}"
    n_q = s // blk
    n_kv = n_kv_blocks(st.window, blk, n_q)

    def kv_idx(bi, hi, qi, ki):
        j = qi - (n_kv - 1) + ki
        return (bi, hi // n_rep, jnp.maximum(j, 0), 0)

    def kvh_idx(bi, hi, qi, ki):          # for arrays already (B,1,S,D)
        j = qi - (n_kv - 1) + ki
        return (bi, 0, jnp.maximum(j, 0), 0)

    def q_idx(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def row_q_idx(bi, hi, qi, ki):
        return (bi, 0, qi)

    def row_k_idx(bi, hi, qi, ki):
        return (bi, 0, jnp.maximum(qi - (n_kv - 1) + ki, 0))

    kn_map = kv_idx if st.use_nope and kn.shape[1] == hk else kvh_idx
    qn_map = q_idx if st.use_nope else kvh_idx
    v0_map = kv_idx if st.use_reset else kvh_idx

    grid = (b, h, n_q, n_kv)
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel, blk=blk, n_kv=n_kv, window=st.window, scale=st.scale,
            sum_isolated=st.sum_isolated, use_seg=st.use_seg,
            use_nope=st.use_nope, use_reset=st.use_reset, y_min=st.y_min,
            y_max=st.y_max, midpoint=st.midpoint),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk), row_q_idx),               # pos_q
            pl.BlockSpec((1, 1, blk), row_k_idx),               # pos_k
            pl.BlockSpec((1, 1, blk), row_q_idx),               # sum_q
            pl.BlockSpec((1, 1, blk), row_k_idx),               # sum_k
            pl.BlockSpec((1, 1, blk), row_k_idx),               # valid_k
            pl.BlockSpec((1, 1, blk), row_q_idx),               # seg_q
            pl.BlockSpec((1, 1, blk), row_k_idx),               # seg_k
            pl.BlockSpec(memory_space=pltpu.SMEM),              # alibi (H,)
            pl.BlockSpec((1, 1, blk, d), q_idx),                # q
            pl.BlockSpec((1, 1, blk, d), kv_idx),               # k
            pl.BlockSpec((1, 1, blk, dv), kv_idx),              # v
            pl.BlockSpec((1, 1, blk, d), qn_map),               # qn
            pl.BlockSpec((1, 1, blk, d), kn_map),               # kn
            pl.BlockSpec((1, 1, blk, dv), v0_map),              # v0
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, dv), q_idx),
            pl.BlockSpec((1, 1, 1, blk),
                         lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, 1), jnp.float32),      # m (row max)
            pltpu.VMEM((blk, 1), jnp.float32),      # l (row denom)
            pltpu.VMEM((blk, dv), jnp.float32),     # acc (value accum)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=st.interpret,
    )(pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, alibi, q, k, v,
      qn, kn, v0)
    return out, lse


__all__ = ["AttnStatics", "choose_block", "n_kv_blocks", "prepare_inputs",
           "windowed_attention_fwd_bhsd"]
