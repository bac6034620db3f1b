"""jit'd public wrapper: same signature as ``repro.core.windowed.attention_dense``.

Transposes (B, S, H, D) -> (B, H, S, D) for the kernel's tiling, forwards
every DTI option, and untransposes. ``interpret=True`` by default off-TPU so
the kernel body runs (and is tested) on CPU; on TPU it compiles to Mosaic.

The op is differentiable: a ``jax.custom_vjp`` pairs the forward kernel
(which saves the per-row softmax logsumexp) with the flash-style backward
kernels in ``windowed_attn_bwd`` — dq and dk/dv passes over the same
window-banded block schedule, recomputing probabilities from the residual.
Gradients flow to q/k/v, q_nope/k_nope (SUM rows) and v0 (reset stream);
positions, flags, segment ids and the (non-learned) ALiBi slopes get zero
cotangents. See docs/kernels.md.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.windowed import ResetConfig
from repro.kernels import default_interpret
from repro.kernels.windowed_attn.windowed_attn import (
    AttnStatics, prepare_inputs, windowed_attention_fwd_bhsd)
from repro.kernels.windowed_attn.windowed_attn_bwd import (
    windowed_attention_bwd_bhsd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attn(st: AttnStatics, q, k, v, qn, kn, v0, alibi,
          pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, gate):
    out, _ = windowed_attention_fwd_bhsd(
        st, q, k, v, qn, kn, v0, alibi, pos_q, pos_k, sum_q, sum_k,
        valid_k, seg_q, seg_k, gate)
    return out


def _attn_fwd(st: AttnStatics, q, k, v, qn, kn, v0, alibi,
              pos_q, pos_k, sum_q, sum_k, valid_k, seg_q, seg_k, gate):
    out, lse = windowed_attention_fwd_bhsd(
        st, q, k, v, qn, kn, v0, alibi, pos_q, pos_k, sum_q, sum_k,
        valid_k, seg_q, seg_k, gate)
    return out, (q, k, v, qn, kn, v0, alibi, pos_q, pos_k, sum_q, sum_k,
                 valid_k, seg_q, seg_k, gate, out, lse)


def _attn_bwd(st: AttnStatics, res, do):
    (q, k, v, qn, kn, v0, alibi, pos_q, pos_k, sum_q, sum_k, valid_k,
     seg_q, seg_k, gate, out, lse) = res
    dq, dk, dv, dqn, dkn, dv0 = windowed_attention_bwd_bhsd(
        st, q, k, v, qn, kn, v0, alibi, pos_q, pos_k, sum_q, sum_k,
        valid_k, seg_q, seg_k, gate, out, lse, do)
    # ALiBi slopes are head constants (alibi_slopes(n_heads)), not params
    return (dq, dk, dv, dqn, dkn, dv0, jnp.zeros_like(alibi),
            None, None, None, None, None, None, None, None)


_attn.defvjp(_attn_fwd, _attn_bwd)


def windowed_attention(
    q: jax.Array,                      # (B, Sq, H, D)
    k: jax.Array,                      # (B, Sk, Hk, D)
    v: jax.Array,                      # (B, Sk, Hk, Dv)
    *,
    pos_q: jax.Array,
    pos_k: jax.Array,
    window: int,
    is_sum_q: Optional[jax.Array] = None,
    is_sum_k: Optional[jax.Array] = None,
    valid_k: Optional[jax.Array] = None,
    seg_q: Optional[jax.Array] = None,
    seg_k: Optional[jax.Array] = None,
    q_nope: Optional[jax.Array] = None,
    k_nope: Optional[jax.Array] = None,
    alibi: Optional[jax.Array] = None,
    v0: Optional[jax.Array] = None,
    reset: Optional[ResetConfig] = None,
    sum_isolated: bool = True,
    scale: Optional[float] = None,
    block_size: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    assert window > 0, "pallas path needs a window"
    interpret = default_interpret(interpret)
    t = lambda x: None if x is None else jnp.swapaxes(x, 1, 2)
    use_nope = q_nope is not None and is_sum_q is not None
    use_reset = reset is not None and v0 is not None
    st, arrays = prepare_inputs(
        t(q), t(k), t(v), pos_q, pos_k, window=window,
        sum_q=is_sum_q, sum_k=is_sum_k, valid_k=valid_k,
        seg_q=seg_q, seg_k=seg_k,
        q_nope=t(q_nope) if use_nope else None,
        k_nope=t(k_nope) if use_nope else None,
        alibi=alibi if use_nope else None,
        v0=t(v0) if use_reset else None,
        reset=((reset.y_min, reset.y_max, reset.midpoint)
               if use_reset else None),
        sum_isolated=sum_isolated and is_sum_k is not None,
        scale=scale, block_size=block_size, interpret=interpret)
    out = _attn(st, *arrays)[:, :, :q.shape[1]]      # drop padded rows
    return jnp.swapaxes(out, 1, 2)


__all__ = ["windowed_attention"]
