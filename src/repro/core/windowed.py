"""Windowed causal attention with the DTI extensions (the paper's core math).

Three execution paths, all semantically identical (tests assert this):

* ``attention_dense``   — materialises the (Sq, Sk) score matrix. Reference
  path; used for smoke tests, decode steps and as the oracle for the others.
* ``attention_blocked`` — block-local attention: the sequence is split into
  blocks of the window size W and each query block attends to (previous block,
  own block) only. O(S * 2W) time/memory instead of O(S^2). This is the shape
  the Pallas kernel (`repro.kernels.windowed_attn`) implements on TPU and the
  shape used by every large compile cell.
* ``repro.kernels.windowed_attn.ops.windowed_attention`` — the fused TPU
  kernel (validated against ``attention_dense`` in interpret mode). It is
  differentiable: a custom VJP pairs the forward (which saves per-row
  logsumexp residuals) with flash-style dq and dk/dv backward kernels, so
  ``attn_impl="pallas"`` trains end-to-end on the kernel path
  (tests/test_kernel_grads.py asserts gradient equivalence to this dense
  reference; docs/kernels.md documents the contract).

DTI semantics implemented here (paper sections 3.3, 4.1, 4.2):

* window mask        — each token attends to at most its ``window`` predecessors.
* SUM isolation      — [SUM] readout tokens are masked out of every *other*
  token's keys: readout states never pollute the stream (they do not exist in
  sliding-window inference prompts).
* SUM NoPE + ALiBi   — rows belonging to [SUM] queries score against the
  *unrotated* (no position id) q/k with a relative ALiBi bias, fixing
  positional-bias overfitting. Non-SUM rows use plain RoPE'd q/k.
* hidden-state reset — for [SUM] query rows the attended value is
  ``(1 - a(d)) * V(h_s) + a(d) * V(h_s_init)`` with the logistic
  ``a(d) = y_min + (y_max - y_min) * sigmoid(d - N/2)``; d = query-key distance.
  Implemented as a second value aggregation with per-(t,s) weights, so each
  target reads its own distance-reset view of the context while the shared
  stream stays untouched ("dynamic target isolation").
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class ResetConfig:
    """Distance-based hidden-state forgetting (paper eq. in section 4.1)."""
    y_min: float = 0.0
    y_max: float = 0.3
    midpoint: float = 512.0   # N/2 in tokens


def reset_alpha(dist: jax.Array, cfg: ResetConfig) -> jax.Array:
    """Logistic interpolation ratio a(d); dist is query_pos - key_pos >= 0."""
    d = dist.astype(jnp.float32)
    return cfg.y_min + (cfg.y_max - cfg.y_min) * jax.nn.sigmoid(d - cfg.midpoint)


def dti_mask(pos_q: jax.Array, pos_k: jax.Array, *, window: int,
             is_sum_k: Optional[jax.Array] = None,
             valid_k: Optional[jax.Array] = None,
             seg_q: Optional[jax.Array] = None,
             seg_k: Optional[jax.Array] = None,
             seg_shared: Optional[int] = None) -> jax.Array:
    """Boolean (..., Sq, Sk) mask: True = attendable.

    causal     : pos_q >= pos_k
    window     : pos_q - pos_k <= window (window == 0 -> unlimited, pure causal)
    SUM-iso    : keys that are [SUM] tokens only attend-able by themselves
    valid_k    : padding mask for keys
    segment    : packed rows — queries only attend keys of their own segment
                 (positions restart per segment, so without this term a later
                 segment's small pos_q would alias into earlier segments)
    seg_shared : multi-target serving rows — keys of segment ``seg_shared``
                 (the user context) are additionally attendable by *every*
                 segment, so k candidate segments share one context prefix
                 while staying isolated from each other. Candidate positions
                 continue after the context (they do not restart at 0), so
                 the causal/window/ALiBi distances equal the ones of a
                 standalone context+candidate prompt.
    """
    d = pos_q[..., :, None] - pos_k[..., None, :]
    m = d >= 0
    if window > 0:
        m = m & (d <= window)
    if is_sum_k is not None:
        m = m & (~is_sum_k[..., None, :] | (d == 0))
    if valid_k is not None:
        m = m & valid_k[..., None, :]
    if seg_q is not None and seg_k is not None:
        same = seg_q[..., :, None] == seg_k[..., None, :]
        if seg_shared is not None:
            same = same | (seg_k[..., None, :] == seg_shared)
        m = m & same
    return m


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hk, D) -> (B, S, Hk * n_rep, D) by head repetition (GQA)."""
    if n_rep == 1:
        return x
    b, s, hk, dd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, hk, n_rep, dd)).reshape(b, s, hk * n_rep, dd)


def _scores(q, k):
    """(B,Sq,H,D),(B,Sk,H,D) -> fp32 (B,H,Sq,Sk)."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)


def attention_dense(
    q: jax.Array,                      # (B, Sq, H, Dqk)  (RoPE'd)
    k: jax.Array,                      # (B, Sk, Hk, Dqk) (RoPE'd)
    v: jax.Array,                      # (B, Sk, Hk, Dv)
    *,
    pos_q: jax.Array,                  # (B, Sq) int32 token positions
    pos_k: jax.Array,                  # (B, Sk)
    window: int = 0,
    is_sum_q: Optional[jax.Array] = None,   # (B, Sq) bool
    is_sum_k: Optional[jax.Array] = None,   # (B, Sk) bool
    valid_k: Optional[jax.Array] = None,    # (B, Sk) bool
    seg_q: Optional[jax.Array] = None,      # (B, Sq) int32 packed segments
    seg_k: Optional[jax.Array] = None,      # (B, Sk) int32
    seg_shared: Optional[int] = None,       # shared-prefix segment id
    q_nope: Optional[jax.Array] = None,     # unrotated q for SUM rows
    k_nope: Optional[jax.Array] = None,     # unrotated k for SUM rows
    alibi: Optional[jax.Array] = None,      # (H,) slopes for SUM rows
    v0: Optional[jax.Array] = None,         # (B, Sk, Hk, Dv) values of h_init
    reset: Optional[ResetConfig] = None,
    sum_isolated: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference DTI attention. Returns (B, Sq, H, Dv)."""
    b, sq, h, dqk = q.shape
    hk = k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = dqk ** -0.5

    k_r = _repeat_kv(k, n_rep)
    v_r = _repeat_kv(v, n_rep)

    logits = _scores(q, k_r) * scale                       # (B,H,Sq,Sk) fp32

    use_sum_rows = is_sum_q is not None and (q_nope is not None)
    if use_sum_rows:
        kn_r = _repeat_kv(k_nope, n_rep)
        logits2 = _scores(q_nope, kn_r) * scale
        if alibi is not None:
            d = (pos_q[:, None, :, None] - pos_k[:, None, None, :]).astype(jnp.float32)
            logits2 = logits2 - alibi[None, :, None, None] * d
        logits = jnp.where(is_sum_q[:, None, :, None], logits2, logits)

    mask = dti_mask(pos_q, pos_k, window=window,
                    is_sum_k=is_sum_k if sum_isolated else None,
                    valid_k=valid_k, seg_q=seg_q, seg_k=seg_k,
                    seg_shared=seg_shared)                      # (B,Sq,Sk)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no attendable key (padding) -> zero output
    any_ok = jnp.any(mask, axis=-1)[:, None, :, None]
    probs = jnp.where(any_ok, probs, 0.0)

    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_r.dtype), v_r)

    if reset is not None and v0 is not None and is_sum_q is not None:
        v0_r = _repeat_kv(v0, n_rep)
        dist = jnp.clip(pos_q[:, :, None] - pos_k[:, None, :], 0)   # (B,Sq,Sk)
        a = reset_alpha(dist, reset)[:, None, :, :]                  # (B,1,Sq,Sk)
        probs_a = (probs * a) * is_sum_q[:, None, :, None]
        out = out + jnp.einsum("bhqk,bkhd->bqhd",
                               probs_a.astype(v_r.dtype), (v0_r - v_r))
    return out


# ---------------------------------------------------------------------------
# blocked (O(S * 2W)) path
# ---------------------------------------------------------------------------

def _to_blocks(x: jax.Array, blk: int) -> jax.Array:
    """(B, S, ...) -> (B, nb, blk, ...). S must be divisible by blk."""
    b, s = x.shape[:2]
    return x.reshape(b, s // blk, blk, *x.shape[2:])


def _with_prev(xb: jax.Array) -> jax.Array:
    """(B, nb, blk, ...) -> (B, nb, 2*blk, ...): concat(prev block, own block)."""
    prev = jnp.pad(xb[:, :-1], [(0, 0), (1, 0)] + [(0, 0)] * (xb.ndim - 2))
    return jnp.concatenate([prev, xb], axis=2)


def attention_blocked(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    pos_q: jax.Array, pos_k: jax.Array, window: int,
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
    q_chunk: int = 4,
) -> jax.Array:
    """Block-local windowed attention; semantics == attention_dense.

    Requires Sq == Sk == S and window > 0; S is padded up to a multiple of
    the window (padded keys are invalid, padded query rows are dropped).
    Each query block i
    attends kv blocks {i-1, i}; the (pos_q - pos_k <= window) mask inside the
    pair keeps semantics exact. Packed rows keep the block-pair invariant:
    positions restart per segment and segments are contiguous, so physical
    distance == positional distance for every same-segment pair, and the
    seg_q == seg_k mask term kills cross-segment aliases inside the pair.

    ``q_chunk``: when the sequence has more than q_chunk blocks, q-block
    chunks are processed sequentially (lax.map) so live fp32 logits stay
    O(q_chunk * H * W * 2W) instead of O(S/W * ...) — at 32k tokens with
    unsharded heads the difference is 19 GiB vs ~2 GiB of temp per device.
    This mirrors the grid schedule of the Pallas kernel.
    """
    assert window > 0, "blocked path needs a window"
    b, s, h, dqk = q.shape
    hk = k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = dqk ** -0.5
    blk = window
    if s % blk:
        def pad(x, fill=0):
            if x is None:
                return None
            widths = [(0, 0), (0, blk - s % blk)] + [(0, 0)] * (x.ndim - 2)
            return jnp.pad(x, widths, constant_values=fill)
        valid = jnp.ones((b, s), bool) if valid_k is None else valid_k
        return attention_blocked(
            pad(q), pad(k), pad(v), pos_q=pad(pos_q), pos_k=pad(pos_k),
            window=window, is_sum_q=pad(is_sum_q), is_sum_k=pad(is_sum_k),
            valid_k=pad(valid, False), seg_q=pad(seg_q), seg_k=pad(seg_k),
            q_nope=pad(q_nope), k_nope=pad(k_nope), alibi=alibi, v0=pad(v0),
            reset=reset, sum_isolated=sum_isolated, scale=scale,
            q_chunk=q_chunk)[:, :s]
    nb = s // blk

    k_r = _repeat_kv(k, n_rep)
    v_r = _repeat_kv(v, n_rep)

    qb = _to_blocks(q, blk)                             # (B,nb,blk,H,D)
    kb = _with_prev(_to_blocks(k_r, blk))               # (B,nb,2blk,H,D)
    vb = _with_prev(_to_blocks(v_r, blk))
    pq = _to_blocks(pos_q, blk)                         # (B,nb,blk)
    pk = _with_prev(_to_blocks(pos_k, blk))             # (B,nb,2blk)
    # previous-of-block-0 is padding: mark invalid via huge negative position
    pad_valid = _with_prev(_to_blocks(jnp.ones_like(pos_k, dtype=bool)
                                      if valid_k is None else valid_k, blk))
    first = jnp.zeros((1, nb, 1), dtype=bool).at[:, 0, :].set(True)
    blk_pad = jnp.concatenate(
        [jnp.broadcast_to(first, (b, nb, blk)),
         jnp.zeros((b, nb, blk), dtype=bool)], axis=2)
    pad_valid = pad_valid & ~blk_pad

    use_nope = is_sum_q is not None and q_nope is not None
    use_reset = reset is not None and v0 is not None and is_sum_q is not None
    xs = {"qb": qb, "kb": kb, "vb": vb, "pq": pq, "pk": pk,
          "pad_valid": pad_valid}
    if use_nope:
        xs["qnb"] = _to_blocks(q_nope, blk)
        xs["knb"] = _with_prev(_to_blocks(_repeat_kv(k_nope, n_rep), blk))
    if is_sum_q is not None:
        xs["sq_b"] = _to_blocks(is_sum_q, blk)
    if sum_isolated and is_sum_k is not None:
        xs["sk_b"] = _with_prev(_to_blocks(is_sum_k, blk))
    if seg_q is not None and seg_k is not None:
        xs["sgq_b"] = _to_blocks(seg_q, blk)
        # prev-of-block-0 zero padding is already masked via pad_valid
        xs["sgk_b"] = _with_prev(_to_blocks(seg_k, blk))
    if use_reset:
        xs["v0b"] = _with_prev(_to_blocks(_repeat_kv(v0, n_rep), blk))

    def compute(c):
        logits = jnp.einsum("bnqhd,bnkhd->bnhqk", c["qb"], c["kb"],
                            preferred_element_type=jnp.float32) * scale
        if use_nope:
            logits2 = jnp.einsum("bnqhd,bnkhd->bnhqk", c["qnb"], c["knb"],
                                 preferred_element_type=jnp.float32) * scale
            if alibi is not None:
                dd = (c["pq"][:, :, None, :, None]
                      - c["pk"][:, :, None, None, :]).astype(jnp.float32)
                logits2 = logits2 - alibi[None, None, :, None, None] * dd
            logits = jnp.where(c["sq_b"][:, :, None, :, None], logits2,
                               logits)

        d = c["pq"][:, :, :, None] - c["pk"][:, :, None, :]
        mask = (d >= 0) & (d <= window) & c["pad_valid"][:, :, None, :]
        if "sk_b" in c:
            mask = mask & (~c["sk_b"][:, :, None, :] | (d == 0))
        if "sgq_b" in c:
            mask = mask & (c["sgq_b"][:, :, :, None] == c["sgk_b"][:, :, None, :])

        logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        any_ok = jnp.any(mask, axis=-1)[:, :, None, :, None]
        probs = jnp.where(any_ok, probs, 0.0)

        out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs.astype(c["vb"].dtype),
                         c["vb"])
        if use_reset:
            a = reset_alpha(jnp.clip(d, 0), reset)[:, :, None, :, :]
            probs_a = (probs * a) * c["sq_b"][:, :, None, :, None]
            out = out + jnp.einsum("bnhqk,bnkhd->bnqhd",
                                   probs_a.astype(c["vb"].dtype),
                                   (c["v0b"] - c["vb"]))
        return out

    if q_chunk and nb > q_chunk and nb % q_chunk == 0:
        nc = nb // q_chunk
        # (B, nb, ...) -> (nc, B, q_chunk, ...); lax.map over chunks
        split = jax.tree_util.tree_map(
            lambda t: jnp.moveaxis(
                t.reshape(b, nc, q_chunk, *t.shape[2:]), 1, 0), xs)
        out = jax.lax.map(compute, split)                # (nc,B,qc,blk,H,Dv)
        out = jnp.moveaxis(out, 0, 1).reshape(b, nb, blk, h, v.shape[-1])
    else:
        out = compute(xs)

    return out.reshape(b, s, h, v.shape[-1])


def attention(impl: str, *args, **kwargs) -> jax.Array:
    if impl != "dense" and kwargs.pop("seg_shared", None) is not None:
        # Multi-target serving rows interleave candidate segments whose
        # positions all continue from the context, so physical distance !=
        # positional distance — the block-pair schedule the banded paths
        # rely on does not hold.
        raise NotImplementedError(
            "shared-prefix segments (multi-target serving) require the "
            "dense attention path")
    if impl == "dense":
        return attention_dense(*args, **kwargs)
    if impl == "blocked":
        return attention_blocked(*args, **kwargs)
    if impl == "pallas":
        from repro.kernels.windowed_attn import ops as _ops
        return _ops.windowed_attention(*args, **kwargs)
    raise ValueError(f"unknown attention impl {impl!r}")


__all__ = ["ResetConfig", "reset_alpha", "dti_mask",
           "attention_dense", "attention_blocked", "attention"]
