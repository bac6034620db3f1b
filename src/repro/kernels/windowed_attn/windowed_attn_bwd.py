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

Both passes gate the SUM rows' work by the forward's sub-tile gate words
(``run_gated``): a q block with no SUM query runs whole and plain, one
with SUM queries runs sub-tile by sub-tile.

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
                                                       n_kv_blocks,
                                                       run_gated)

_f32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_f32)


def _recompute_rows(pos_q, sum_q, seg_q, q, do, lse, delta,
                    pos_k, sum_k, valid_k, seg_k, alibi, k, v,
                    qn, kn, v0, *, with_sum, window, scale, sum_isolated,
                    use_seg, use_nope, use_reset, y_min, y_max, midpoint):
    """Shared tile math of both backward passes, for some query rows.

    Query-side operands (pos_q, sum_q, seg_q, lse, delta) arrive as
    (rows, 1) columns with q and do as (rows, D) tiles, key-side ones as
    (1, blk) rows with k and v as (blk, D) tiles. ``qn``, ``kn`` and ``v0``
    are read only in the SUM variant (``with_sum``), which takes the SUM
    rows' score from the NoPE stream and adds the reset term to their
    ``dp``. Returns (pv, ds_rope, ds_nope, pa): the main value stream's
    weight (``p * (1 - a*sigma)``, or the probabilities p), the score
    gradient split by stream (RoPE rows vs SUM NoPE rows) and the reset
    weight ``p * a*sigma`` (None where a stream does not run). All fp32.
    """
    s = _dot(q, k, ((1,), (1,))) * scale                  # (rows, blk)
    d = pos_q - pos_k
    sum_row = sum_q != 0
    if with_sum and use_nope:
        sn = _dot(qn(), kn(), ((1,), (1,))) * scale
        sn = sn - alibi * d.astype(_f32)
        s = jnp.where(sum_row, sn, s)

    mask = (d >= 0) & (d <= window) & (valid_k != 0)
    if sum_isolated:
        mask &= (sum_k == 0) | (d == 0)
    if use_seg:
        mask &= seg_q == seg_k

    # p == softmax probs exactly: lse = m + log(l) (or +1e30 on empty rows,
    # in which case every exp underflows to 0 and so does delta)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    dp = _dot(do, v, ((1,), (1,)))                        # do . v_j
    pv = pa = ds_nope = None
    if with_sum and use_reset:
        a = y_min + (y_max - y_min) * jax.nn.sigmoid(
            d.astype(_f32) - midpoint)
        asig = a * sum_row.astype(_f32)
        dp = dp + asig * _dot(do, v0() - v, ((1,), (1,)))
        pv, pa = p * (1.0 - asig), p * asig
    ds = p * (dp - delta)
    if with_sum and use_nope:
        ds_nope = ds * sum_row.astype(_f32)
        ds = ds - ds_nope
    return (p if pv is None else pv), ds, ds_nope, pa


def _tile_operands(head, lo, hi, pos_q_ref, pos_k_ref, sum_q_ref, sum_k_ref,
                   valid_k_ref, seg_q_ref, seg_k_ref, alibi_ref, q_ref, k_ref,
                   v_ref, qn_ref, kn_ref, v0_ref, do_ref, lse_ref, delta_ref):
    """``_recompute_rows``' operands for query rows lo:hi: query-side rows
    transpose to (rows, 1) columns, key-side ones stay (1, blk) rows, and
    the SUM streams' tiles are read on call, in the SUM variant only."""
    return dict(
        pos_q=pos_q_ref[0, :, lo:hi].T, sum_q=sum_q_ref[0, :, lo:hi].T,
        seg_q=seg_q_ref[0, :, lo:hi].T,
        q=q_ref[0, 0, lo:hi, :].astype(_f32),
        do=do_ref[0, 0, lo:hi, :].astype(_f32),
        lse=lse_ref[0, 0, :, lo:hi].T, delta=delta_ref[0, 0, :, lo:hi].T,
        pos_k=pos_k_ref[0], sum_k=sum_k_ref[0], valid_k=valid_k_ref[0],
        seg_k=seg_k_ref[0], alibi=alibi_ref[head],
        k=k_ref[0, 0].astype(_f32), v=v_ref[0, 0].astype(_f32),
        qn=lambda: qn_ref[0, 0, lo:hi, :].astype(_f32),
        kn=lambda: kn_ref[0, 0].astype(_f32),
        v0=lambda: v0_ref[0, 0].astype(_f32))


def _dq_kernel(*refs, blk: int, n_q: int, n_kv: int, use_nope: bool,
               gated: bool, scale: float, math_kw):
    ins, refs = refs[:17], refs[17:]
    gate_ref, refs = (refs[0], refs[1:]) if gated else (None, refs)
    if use_nope:
        dq_ref, dqn_ref, dq_acc, dqn_acc = refs
    else:
        (dq_ref, dq_acc), dqn_ref, dqn_acc = refs, None, None
    ib, ih = pl.program_id(0), pl.program_id(1)
    iq, ikv = pl.program_id(2), pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if use_nope:
            dqn_acc[...] = jnp.zeros_like(dqn_acc)

    @pl.when(iq - (n_kv - 1) + ikv >= 0)      # kv block inside the band
    def _step():
        def rows(lo, hi, with_sum):
            t = _tile_operands(ih, lo, hi, *ins)
            _, ds_rope, ds_nope, _ = _recompute_rows(
                **t, with_sum=with_sum, **math_kw)
            dq_acc[lo:hi, :] += scale * _dot(ds_rope, t["k"], ((1,), (0,)))
            if with_sum and use_nope:
                dqn_acc[lo:hi, :] += scale * _dot(ds_nope, t["kn"](),
                                                  ((1,), (0,)))

        run_gated(gate_ref[ib * n_q + iq] if gated else None, blk, rows)

    @pl.when(ikv == n_kv - 1)
    def _finish():
        dq_ref[0, 0, ...] = dq_acc[...].astype(dq_ref.dtype)
        if use_nope:
            dqn_ref[0, 0, ...] = dqn_acc[...].astype(dqn_ref.dtype)


def _dkv_kernel(*refs, blk: int, n_kv: int, n_q: int, use_nope: bool,
                use_reset: bool, scale: float, math_kw):
    ins, refs = refs[:17], refs[17:]
    gated = use_nope or use_reset
    gate_ref, refs = (refs[0], refs[1:]) if gated else (None, refs)
    n_out = 2 + int(use_nope) + int(use_reset)
    outs, accs = refs[:n_out], refs[n_out:2 * n_out]
    # gated calls stage pv and ds_rope per sub-tile for the full-tile dots
    pv_ref, ds_ref = refs[2 * n_out:] if gated else (None, None)
    dk_acc, dv_acc = accs[0], accs[1]
    dkn_acc = accs[2] if use_nope else None
    dv0_acc = accs[2 + int(use_nope)] if use_reset else None
    ibt, ih = pl.program_id(0), pl.program_id(1)
    j, ib = pl.program_id(2), pl.program_id(3)

    @pl.when(ib == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(j + ib <= n_q - 1)               # q block inside the sequence
    def _step():
        def dkv(pv, ds_rope, do, q):
            dv_acc[...] += _dot(pv, do, ((0,), (0,)))
            dk_acc[...] += scale * _dot(ds_rope, q, ((0,), (0,)))

        def whole():
            t = _tile_operands(ih, 0, blk, *ins)
            pv, ds_rope, _, _ = _recompute_rows(
                **t, with_sum=False, **math_kw)
            dkv(pv, ds_rope, t["do"], t["q"])

        def rows(lo, hi, with_sum):
            # a sub-tile of a block that holds a SUM: stage its pv and
            # ds_rope rows for the block's dk/dv products in ``then``
            t = _tile_operands(ih, lo, hi, *ins)
            pv, ds_rope, ds_nope, pa = _recompute_rows(
                **t, with_sum=with_sum, **math_kw)
            pv_ref[lo:hi, :] = pv
            ds_ref[lo:hi, :] = ds_rope
            if with_sum and use_nope:
                dkn_acc[...] += scale * _dot(ds_nope, t["qn"](),
                                             ((0,), (0,)))
            if with_sum and use_reset:
                dv0_acc[...] += _dot(pa, t["do"], ((0,), (0,)))

        def then():
            q_ref, do_ref = ins[8], ins[14]       # in_specs order
            dkv(pv_ref[...], ds_ref[...], do_ref[0, 0].astype(_f32),
                q_ref[0, 0].astype(_f32))

        # the walked q block, through the clamp of the index maps
        bits = (gate_ref[ibt * n_q + jnp.minimum(j + ib, n_q - 1)]
                if gated else None)
        run_gated(bits, blk, rows, whole=whole, then=then)

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
        pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, gate,
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

    # the SUM gate words ride after the tiles, in SMEM like alibi
    gated = gate is not None
    gate_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)] if gated else []

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
        ] + gate_spec

    operands = (pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, alibi,
                q, k, v, qn, kn, v0, do, lse, delta) + (
                    (gate,) if gated else ())

    dq_outs = [jax.ShapeDtypeStruct((b, h, s, d), q.dtype)]
    dq_specs = [pl.BlockSpec((1, 1, blk, d), q_idx)]
    dq_scratch = [pltpu.VMEM((blk, d), _f32)]
    if st.use_nope:
        dq_outs.append(jax.ShapeDtypeStruct((b, h, s, d), qn.dtype))
        dq_specs.append(pl.BlockSpec((1, 1, blk, d), q_idx))
        dq_scratch.append(pltpu.VMEM((blk, d), _f32))
    res = pl.pallas_call(
        functools.partial(_dq_kernel, blk=blk, n_q=n_q, n_kv=n_kv,
                          use_nope=st.use_nope, gated=gated,
                          scale=st.scale, math_kw=math_kw),
        grid=grid,
        in_specs=in_specs(seq_q_idx, seq_k_idx, q_idx, kv_idx, kv_idx,
                          qn_map, kn_map, v0_map, row_q_idx),
        out_specs=dq_specs, out_shape=dq_outs, scratch_shapes=dq_scratch,
        compiler_params=sem, interpret=st.interpret, name="winattn_dq",
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
    dkv_scratch = [pltpu.VMEM((blk, dd), _f32) for dd in out_dims] + (
        [pltpu.VMEM((blk, blk), _f32)] * 2 if gated else [])
    res = pl.pallas_call(
        functools.partial(_dkv_kernel, blk=blk, n_kv=n_kv, n_q=n_q,
                          use_nope=st.use_nope, use_reset=st.use_reset,
                          scale=st.scale, math_kw=math_kw),
        grid=grid,
        in_specs=in_specs(b_seq_q_idx, b_seq_k_idx, b_q_idx, b_kv_idx,
                          b_kv_idx, b_qn_map, b_kn_map, b_v0_map,
                          b_row_idx),
        out_specs=dkv_specs, out_shape=dkv_outs, scratch_shapes=dkv_scratch,
        compiler_params=sem, interpret=st.interpret, name="winattn_dkv",
    )(*operands)
    dk = _head_sum(res[0], hk).astype(k.dtype)
    dv = _head_sum(res[1], hk).astype(v.dtype)
    dkn = (_head_sum(res[2], kn_heads).astype(kn.dtype)
           if st.use_nope else jnp.zeros_like(kn))
    dv0 = (_head_sum(res[2 + int(st.use_nope)], hk).astype(v0.dtype)
           if st.use_reset else jnp.zeros_like(v0))
    return dq, dk, dv, dqn, dkn, dv0


__all__ = ["windowed_attention_bwd_bhsd"]
