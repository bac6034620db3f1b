"""Flash-style backward Pallas kernels for the windowed DTI attention.

Two passes over the same window-banded block schedule as the forward
(docs/kernels.md has the full contract):

* **dq pass** — grid ``(B, H, n_q, n_kv)``, identical banding to the
  forward: each q block walks its kv band, recomputes the probabilities
  from the saved per-row logsumexp (``p = exp(s - lse)``, no S x S tensor),
  and accumulates ``dq`` (RoPE stream) and ``dq_nope`` (SUM rows) in VMEM
  scratch, writing once at the end of the band.

* **dk/dv pass** — grid ``(B, H, n_kv_j, band)``: for kv block j the
  attending q blocks are ``i = j .. j+n_kv-1``; the kernel accumulates
  ``dk``/``dv`` (and ``dk_nope``/``dv0`` when those streams are live) per
  *query* head, and the wrapper reduces query-head groups onto kv heads
  (GQA) outside — K/V are never repeated in memory, matching the forward.

DTI semantics and where their gradients flow:

* mask terms (causal window, ``valid_k``, SUM isolation, packed segments)
  are recomputed from index arithmetic — pure zero/one gates, no grads;
* SUM NoPE+ALiBi rows took their score from the (q_nope, k_nope) matmul,
  so their ``ds`` flows to dq_nope/dk_nope and contributes *nothing* to
  dq/dk (and vice versa for non-SUM rows); the ALiBi bias is additive in a
  position constant, so it has no input gradient (slopes are non-learned);
* the hidden-state reset output o = sum p * (v + a(d)*sigma * (v0 - v))
  modifies the *per-pair value*, not the normalisation, so the classic
  flash identity D_i = sum_j p_ij dp_ij = <do_i, o_i> still holds;
  dv picks up the (1 - a*sigma) weight and dv0 the a*sigma weight.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.windowed_attn.windowed_attn import (AttnStatics,
                                                       n_kv_blocks)

_f32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_f32)


def _recompute_tile(pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k,
                    alibi, q, k, qn, kn, v, v0, do, lse, delta, band_ok,
                    *, window, scale, sum_isolated, use_seg, use_nope,
                    use_reset, y_min, y_max, midpoint):
    """Shared (q-block, kv-block) tile math for both backward passes.

    Query-side rows (pos_q, sum_q, seg_q, lse, delta) arrive as (blk, 1)
    columns and key-side ones as (1, blk) rows. Returns (p, ds_rope,
    ds_nope, asig): probabilities, the score gradient split by stream (RoPE
    rows vs SUM NoPE rows), and the reset weight a(d)*sigma (None unless
    the reset stream is live). All fp32.
    """
    s = _dot(q, k, ((1,), (1,))) * scale                  # (blk_q, blk_k)
    d = pos_q - pos_k
    sum_row = sum_q != 0
    if use_nope:
        sn = _dot(qn, kn, ((1,), (1,))) * scale
        sn = sn - alibi * d.astype(_f32)
        s = jnp.where(sum_row, sn, s)

    mask = (d >= 0) & (d <= window) & (valid_k != 0)
    if sum_isolated:
        mask &= (sum_k == 0) | (d == 0)
    if use_seg:
        mask &= seg_q == seg_k
    mask &= band_ok

    # p == softmax probs exactly: lse = m + log(l) (or +1e30 on empty rows,
    # in which case every exp underflows to 0 and so does delta)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    dp = _dot(do, v, ((1,), (1,)))                        # do . v_j
    asig = None
    if use_reset:
        a = y_min + (y_max - y_min) * jax.nn.sigmoid(
            d.astype(_f32) - midpoint)
        asig = a * sum_row.astype(_f32)
        dp = dp + asig * _dot(do, v0 - v, ((1,), (1,)))
    ds = p * (dp - delta)
    if use_nope:
        ds_nope = ds * sum_row.astype(_f32)
        ds_rope = ds - ds_nope
    else:
        ds_rope, ds_nope = ds, None
    return p, ds_rope, ds_nope, asig


def _load_tile(pos_q_ref, pos_k_ref, sum_q_ref, sum_k_ref, valid_k_ref,
               seg_q_ref, seg_k_ref, alibi_ref, q_ref, k_ref, v_ref,
               qn_ref, kn_ref, v0_ref, do_ref, lse_ref, delta_ref):
    # query-side rows transpose to (blk, 1) columns, key-side stay (1, blk)
    return dict(
        pos_q=pos_q_ref[0].T, pos_k=pos_k_ref[0], sum_q=sum_q_ref[0].T,
        sum_k=sum_k_ref[0], valid_k=valid_k_ref[0], seg_q=seg_q_ref[0].T,
        seg_k=seg_k_ref[0], alibi=alibi_ref[pl.program_id(1)],
        q=q_ref[0, 0].astype(_f32), k=k_ref[0, 0].astype(_f32),
        qn=qn_ref[0, 0].astype(_f32), kn=kn_ref[0, 0].astype(_f32),
        v=v_ref[0, 0].astype(_f32), v0=v0_ref[0, 0].astype(_f32),
        do=do_ref[0, 0].astype(_f32), lse=lse_ref[0, 0].T,
        delta=delta_ref[0, 0].T)


def _dq_kernel(*refs, n_kv: int, use_nope: bool, scale: float, math_kw):
    ins, refs = refs[:17], refs[17:]
    if use_nope:
        dq_ref, dqn_ref, dq_acc, dqn_acc = refs
    else:
        (dq_ref, dq_acc), dqn_ref, dqn_acc = refs, None, None
    ikv = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if use_nope:
            dqn_acc[...] = jnp.zeros_like(dqn_acc)

    t = _load_tile(*ins)
    band_ok = (iq - (n_kv - 1) + ikv) >= 0                # clamped kv block
    _, ds_rope, ds_nope, _ = _recompute_tile(
        t["pos_q"], t["pos_k"], t["sum_q"], t["sum_k"], t["valid_k"],
        t["seg_q"], t["seg_k"], t["alibi"], t["q"], t["k"], t["qn"],
        t["kn"], t["v"], t["v0"], t["do"], t["lse"], t["delta"], band_ok,
        **math_kw)
    dq_acc[...] += scale * _dot(ds_rope, t["k"], ((1,), (0,)))
    if use_nope:
        dqn_acc[...] += scale * _dot(ds_nope, t["kn"], ((1,), (0,)))

    @pl.when(ikv == n_kv - 1)
    def _finish():
        dq_ref[0, 0, ...] = dq_acc[...].astype(dq_ref.dtype)
        if use_nope:
            dqn_ref[0, 0, ...] = dqn_acc[...].astype(dqn_ref.dtype)


def _dkv_kernel(*refs, n_kv: int, n_q: int, use_nope: bool,
                use_reset: bool, scale: float, math_kw):
    ins, refs = refs[:17], refs[17:]
    n_out = 2 + int(use_nope) + int(use_reset)
    outs, accs = refs[:n_out], refs[n_out:]
    dk_ref, dv_ref = outs[0], outs[1]
    dk_acc, dv_acc = accs[0], accs[1]
    dkn_ref = outs[2] if use_nope else None
    dkn_acc = accs[2] if use_nope else None
    dv0_ref = outs[2 + int(use_nope)] if use_reset else None
    dv0_acc = accs[2 + int(use_nope)] if use_reset else None
    ib = pl.program_id(3)                                  # band position
    j = pl.program_id(2)                                   # kv block

    @pl.when(ib == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    t = _load_tile(*ins)
    band_ok = (j + ib) <= (n_q - 1)                        # clamped q block
    p, ds_rope, ds_nope, asig = _recompute_tile(
        t["pos_q"], t["pos_k"], t["sum_q"], t["sum_k"], t["valid_k"],
        t["seg_q"], t["seg_k"], t["alibi"], t["q"], t["k"], t["qn"],
        t["kn"], t["v"], t["v0"], t["do"], t["lse"], t["delta"], band_ok,
        **math_kw)
    pv = p if not use_reset else p * (1.0 - asig)
    dv_acc[...] += _dot(pv, t["do"], ((0,), (0,)))
    if use_reset:
        dv0_acc[...] += _dot(p * asig, t["do"], ((0,), (0,)))
    dk_acc[...] += scale * _dot(ds_rope, t["q"], ((0,), (0,)))
    if use_nope:
        dkn_acc[...] += scale * _dot(ds_nope, t["qn"], ((0,), (0,)))

    @pl.when(ib == n_kv - 1)
    def _finish():
        for ref, acc in zip(outs, accs):
            ref[0, 0, ...] = acc[...].astype(ref.dtype)


def _head_sum(x: jax.Array, n_out: int) -> jax.Array:
    """Reduce per-query-head grads (B, H, S, D) onto n_out kv heads."""
    b, h, s, d = x.shape
    if n_out == h:
        return x
    if n_out == 1:
        return x.sum(axis=1, keepdims=True)
    return x.reshape(b, n_out, h // n_out, s, d).sum(axis=2)


def windowed_attention_bwd_bhsd(
        st: AttnStatics, q, k, v, qn, kn, v0, alibi,
        pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k,
        o, lse, do) -> Tuple[jax.Array, ...]:
    """Backward over normalised operands. Returns (dq, dk, dv, dqn, dkn,
    dv0); streams that are not live come back as zeros of the dummy
    operand's shape (dropped by the caller)."""
    b, h, s, d = q.shape
    dv_d = v.shape[-1]                  # value dim (MLA: != qk dim)
    hk = k.shape[1]
    n_rep = h // hk
    blk = st.block
    n_q = s // blk
    n_kv = n_kv_blocks(st.window, blk, n_q)
    kn_heads = kn.shape[1]

    # flash delta: D_i = <do_i, o_i> (holds with the reset stream too)
    delta = jnp.sum(o.astype(_f32) * do.astype(_f32),
                    axis=-1)[:, :, None, :]                   # (B,H,1,S)

    math_kw = dict(window=st.window, scale=st.scale,
                   sum_isolated=st.sum_isolated, use_seg=st.use_seg,
                   use_nope=st.use_nope, use_reset=st.use_reset,
                   y_min=st.y_min, y_max=st.y_max, midpoint=st.midpoint)
    sem = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel",
                                                    "parallel", "arbitrary"))
    grid = (b, h, n_q, n_kv)

    # ---- dq pass: q-block major, walk the kv band (same maps as fwd) ----
    def kv_idx(bi, hi, qi, ki):
        j = qi - (n_kv - 1) + ki
        return (bi, hi // n_rep, jnp.maximum(j, 0), 0)

    def kvh_idx(bi, hi, qi, ki):
        j = qi - (n_kv - 1) + ki
        return (bi, 0, jnp.maximum(j, 0), 0)

    def q_idx(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def seq_q_idx(bi, hi, qi, ki):
        return (bi, 0, qi)

    def seq_k_idx(bi, hi, qi, ki):
        j = qi - (n_kv - 1) + ki
        return (bi, 0, jnp.maximum(j, 0))

    def row_q_idx(bi, hi, qi, ki):
        return (bi, hi, 0, qi)

    kn_map = kv_idx if st.use_nope and kn_heads == hk else kvh_idx
    qn_map = q_idx if st.use_nope else kvh_idx
    v0_map = kv_idx if st.use_reset else kvh_idx

    def in_specs(sq, sk, qm, km, vm, qnm, knm, v0m, rowm):
        return [
            pl.BlockSpec((1, 1, blk), sq),                  # pos_q
            pl.BlockSpec((1, 1, blk), sk),                  # pos_k
            pl.BlockSpec((1, 1, blk), sq),                  # sum_q
            pl.BlockSpec((1, 1, blk), sk),                  # sum_k
            pl.BlockSpec((1, 1, blk), sk),                  # valid_k
            pl.BlockSpec((1, 1, blk), sq),                  # seg_q
            pl.BlockSpec((1, 1, blk), sk),                  # seg_k
            pl.BlockSpec(memory_space=pltpu.SMEM),          # alibi (H,)
            pl.BlockSpec((1, 1, blk, d), qm),               # q
            pl.BlockSpec((1, 1, blk, d), km),               # k
            pl.BlockSpec((1, 1, blk, dv_d), vm),            # v
            pl.BlockSpec((1, 1, blk, d), qnm),              # qn
            pl.BlockSpec((1, 1, blk, d), knm),              # kn
            pl.BlockSpec((1, 1, blk, dv_d), v0m),           # v0
            pl.BlockSpec((1, 1, blk, dv_d), qm),            # do
            pl.BlockSpec((1, 1, 1, blk), rowm),             # lse
            pl.BlockSpec((1, 1, 1, blk), rowm),             # delta
        ]

    operands = (pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, alibi,
                q, k, v, qn, kn, v0, do, lse, delta)

    dq_outs = [jax.ShapeDtypeStruct((b, h, s, d), q.dtype)]
    dq_specs = [pl.BlockSpec((1, 1, blk, d), q_idx)]
    dq_scratch = [pltpu.VMEM((blk, d), _f32)]
    if st.use_nope:
        dq_outs.append(jax.ShapeDtypeStruct((b, h, s, d), qn.dtype))
        dq_specs.append(pl.BlockSpec((1, 1, blk, d), q_idx))
        dq_scratch.append(pltpu.VMEM((blk, d), _f32))
    res = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv=n_kv, use_nope=st.use_nope,
                          scale=st.scale, math_kw=math_kw),
        grid=grid,
        in_specs=in_specs(seq_q_idx, seq_k_idx, q_idx, kv_idx, kv_idx,
                          qn_map, kn_map, v0_map, row_q_idx),
        out_specs=dq_specs, out_shape=dq_outs, scratch_shapes=dq_scratch,
        compiler_params=sem, interpret=st.interpret,
    )(*operands)
    dq = res[0]
    dqn = res[1] if st.use_nope else jnp.zeros_like(qn)

    # ---- dk/dv pass: kv-block major, walk the attending q blocks --------
    # for kv block j the forward visited it from q blocks j .. j+n_kv-1
    def b_q_idx(bi, hi, j, ib):
        return (bi, hi, jnp.minimum(j + ib, n_q - 1), 0)

    def b_qh_idx(bi, hi, j, ib):
        return (bi, 0, jnp.minimum(j + ib, n_q - 1), 0)

    def b_seq_q_idx(bi, hi, j, ib):
        return (bi, 0, jnp.minimum(j + ib, n_q - 1))

    def b_seq_k_idx(bi, hi, j, ib):
        return (bi, 0, j)

    def b_kv_idx(bi, hi, j, ib):
        return (bi, hi // n_rep, j, 0)

    def b_kvh_idx(bi, hi, j, ib):
        return (bi, 0, j, 0)

    def b_row_idx(bi, hi, j, ib):
        return (bi, hi, 0, jnp.minimum(j + ib, n_q - 1))

    def b_out_idx(bi, hi, j, ib):
        return (bi, hi, j, 0)

    b_kn_map = b_kv_idx if st.use_nope and kn_heads == hk else b_kvh_idx
    b_qn_map = b_q_idx if st.use_nope else b_kvh_idx
    b_v0_map = b_kv_idx if st.use_reset else b_kvh_idx

    # outputs: dk (qk dim), dv (value dim), then dkn / dv0 when live
    out_dims = [d, dv_d] + ([d] if st.use_nope else []) \
        + ([dv_d] if st.use_reset else [])
    dkv_outs = [jax.ShapeDtypeStruct((b, h, s, dd), _f32)
                for dd in out_dims]
    dkv_specs = [pl.BlockSpec((1, 1, blk, dd), b_out_idx)
                 for dd in out_dims]
    dkv_scratch = [pltpu.VMEM((blk, dd), _f32) for dd in out_dims]
    res = pl.pallas_call(
        functools.partial(_dkv_kernel, n_kv=n_kv, n_q=n_q,
                          use_nope=st.use_nope, use_reset=st.use_reset,
                          scale=st.scale, math_kw=math_kw),
        grid=grid,
        in_specs=in_specs(b_seq_q_idx, b_seq_k_idx, b_q_idx, b_kv_idx,
                          b_kv_idx, b_qn_map, b_kn_map, b_v0_map,
                          b_row_idx),
        out_specs=dkv_specs, out_shape=dkv_outs, scratch_shapes=dkv_scratch,
        compiler_params=sem, interpret=st.interpret,
    )(*operands)
    dk = _head_sum(res[0], hk).astype(k.dtype)
    dv = _head_sum(res[1], hk).astype(v.dtype)
    dkn = (_head_sum(res[2], kn_heads).astype(kn.dtype)
           if st.use_nope else jnp.zeros_like(kn))
    dv0 = (_head_sum(res[2 + int(st.use_nope)], hk).astype(v0.dtype)
           if st.use_reset else jnp.zeros_like(v0))
    return dq, dk, dv, dqn, dkn, dv0


__all__ = ["windowed_attention_bwd_bhsd"]
