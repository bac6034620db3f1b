"""Serving: prefill scoring + incremental decode with stacked-layer caches.

Inference follows the paper (section 3.6): sliding-window prompts with one
[SUM] readout at the end, scored by bi-dimensional softmax over yes/no. Two
execution paths:

* ``make_prefill_fn``   — full forward over the prompt (the paper's actual
  inference procedure). SUM rows keep their training-time semantics
  (NoPE + ALiBi, isolation) but **no hidden-state reset** — the reset is a
  training-only regularizer that mimics inference, inference itself is
  untouched.
* ``make_decode_fn``    — one-token incremental step against a KV cache
  (decode_32k / long_500k shapes). The cache stores **unroped** keys plus
  their logical positions; RoPE is applied at read time, which lets a [SUM]
  query score the same cache with NoPE+ALiBi while regular tokens see
  standard RoPE — one cache serves both semantics. MLA runs in absorbed
  form against the latent cache (q_nope folded through W_UK, values decoded
  through W_UV after aggregation).

``lax.scan`` over (stacked layer params, stacked cache layers) keeps the
lowered HLO O(1) in depth, so compile time does not grow with the layers.

Beyond the paper's one-prompt-per-candidate procedure, two shared-context
paths score a whole candidate slate against one user context (the serving
analog of the training paradigm; docs/serving.md):

* ``make_multi_target_prefill_fn`` — one prefill over a
  context-segment + k-isolated-candidate-segments row
  (``repro.core.dti.build_multi_target_request``);
* ``make_decode_fn``'s ``valid``/``commit``/``seg`` operands — chunked
  context prefill into the cache once, then non-committing segment-isolated
  candidate bursts against it (driven by ``repro.serve.scheduler``).
  Committed KV depends only on (token, logical position) — never on which
  step wrote it — so a context committed in budget-cut chunks of any size
  is byte-identical to a monolithic commit; this is what lets the
  scheduler cut chunk boundaries freely for tail latency.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.losses import ctr_logits
from repro.core.quant import dequantize_q8, quantize_q8
from repro.core.windowed import NEG_INF
from repro.kernels.decode_attn.ops import decode_attention
from repro.models.layers import alibi_slopes, apply_rope, dense, rmsnorm
from repro.models.moe import moe_ffn
from repro.models.transformer import ModelConfig, forward
from repro.serve.cache import (Cache, is_paged, kv_keys, physical_slots,
                               slot_indices)

Params = Dict[str, Any]


# ===========================================================================
# prefill
# ===========================================================================

def make_prefill_fn(cfg: ModelConfig, *, yes_id: int = 3, no_id: int = 4,
                    window: Optional[int] = None,
                    multi_target: bool = False) -> Callable:
    """(params, batch) -> p_click (B, S); valid only at [SUM] positions.

    ``multi_target=True`` scores shared-context rows instead of one-prompt
    rows: the batch must carry ``segment_ids`` and segment 0 is treated as
    a shared prefix (``seg_shared=0``); forces the dense attention path.
    """

    def prefill(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        # inference-time DTI: SUM NoPE+ALiBi + isolation, no reset
        icfg = dataclasses.replace(cfg, dti_reset=False)
        kw: Dict[str, Any] = {}
        if multi_target:
            icfg = dataclasses.replace(icfg, attn_impl="dense")
            kw = dict(segment_ids=batch["segment_ids"], seg_shared=0)
        out = forward(params, icfg, batch["tokens"],
                      positions=batch["positions"], is_sum=batch["is_sum"],
                      valid=batch["valid"], dti_enabled=True, window=window,
                      **kw)
        logits2 = ctr_logits(params, cfg, out["hidden"], yes_id, no_id)
        p = jax.nn.softmax(logits2.astype(jnp.float32), axis=-1)[..., 0]
        return jnp.where(batch["is_sum"], p, 0.0)

    return prefill


def make_multi_target_prefill_fn(cfg: ModelConfig, *, yes_id: int = 3,
                                 no_id: int = 4,
                                 window: Optional[int] = None) -> Callable:
    """(params, batch) -> p_click (B, S) for multi-target serving rows.

    ``batch`` rows come from ``repro.core.dti.build_multi_target_request``:
    one shared user context (segment 0) plus k [SUM]-terminated candidate
    segments whose positions continue after the context. Segment 0 is a
    shared prefix (``seg_shared=0``), so one prefill scores all k candidates
    with the context encoded once — O(n^2 + k·n) attention instead of the
    O(k·n^2) of k independent sliding-window prefills — and each [SUM]
    probability equals the standalone-prompt score exactly.

    Forces the dense attention path: the banded/Pallas schedules assume
    physical distance == positional distance, which the interleaved
    candidate segments break.
    """
    return make_prefill_fn(cfg, yes_id=yes_id, no_id=no_id, window=window,
                           multi_target=True)


# ===========================================================================
# decode
# ===========================================================================

def _rope_read(k: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rope cached (unroped) keys with their stored positions; slots with
    pos < 0 are masked later, rope them at 0."""
    return apply_rope(k, jnp.maximum(pos, 0), theta)


def _decode_mask(pos_buf, positions, window: int, seg_q=None, seg_buf=None):
    """(B, s, cap) attendability: filled slot, causal, and — matching
    ``dti_mask`` — the window term only when window > 0 (0 = pure causal).

    ``seg_q``/``seg_buf`` implement multi-candidate bursts: committed cache
    entries (the shared user context) carry segment -1 and are attendable by
    everyone; in-flight burst tokens carry their candidate index and only
    attend context + their own candidate — k candidates score in one step
    without seeing each other."""
    m = ((pos_buf[:, None, :] >= 0)
         & (positions[:, :, None] >= pos_buf[:, None, :]))
    if window > 0:
        m = m & ((positions[:, :, None] - pos_buf[:, None, :]) <= window)
    if seg_q is not None:
        m = m & ((seg_buf[:, None, :] < 0)
                 | (seg_buf[:, None, :] == seg_q[:, :, None]))
    return m


def _cache_write(buf, slots, new, *, bidx, write_idx):
    """Scatter freshly produced KV into the cache.

    Contiguous layout (``write_idx=None``): ``buf (B, cap, ...)`` is
    indexed per row at logical ``slots``. Paged layout: ``buf`` is the
    global ``(n_total, ...)`` pool and ``write_idx (B, s)`` carries the
    physical slot of each token (-1 where the logical slot's page is
    unmapped or past capacity — those writes drop). Either way writes only
    land on the row's private (never shared) slots; see docs/serving.md.
    """
    if write_idx is None:
        return buf.at[bidx, slots].set(new.astype(buf.dtype), mode="drop")
    b, s = write_idx.shape
    flat = new.astype(buf.dtype).reshape((b * s,) + new.shape[2:])
    # -1 sentinels must map PAST the pool, not onto its last slot: jax
    # wraps negative indices numpy-style before mode="drop" applies, so a
    # raw -1 would silently clobber the highest physical slot (a live page
    # once the pool fills).
    idx = write_idx.reshape(-1)
    idx = jnp.where(idx >= 0, idx, buf.shape[0])
    return buf.at[idx].set(flat, mode="drop")


def _cache_view(buf, read_idx):
    """Row-major read view of the cache: identity for the contiguous
    layout, page-index gather for the paged layout (``read_idx (B, cap)``
    physical slots, already clamped — unmapped entries gather arbitrary
    pool bytes that ``pos = -1`` masking keeps unattendable)."""
    return buf if read_idx is None else buf[read_idx]


def _decode_attend(scores_rope, scores_nope, alibi, d, mask, is_sum_q, v_agg):
    """Shared score->prob->value logic. scores_* are (B, H, s, cap) fp32."""
    if scores_nope is not None:
        biased = scores_nope - alibi[None, :, None, None] * d
        scores = jnp.where(is_sum_q[:, None, :, None], biased, scores_rope)
    else:
        scores = scores_rope
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    any_ok = jnp.any(mask, axis=-1)[:, None, :, None]
    return v_agg(jnp.where(any_ok, probs, 0.0))


def _gqa_decode_layer(lp: Params, h, kv: Params, *, cfg: ModelConfig, slots,
                      pos_buf, positions, is_sum, window, kind,
                      seg_q=None, seg_buf=None, impl="dense",
                      attend=decode_attention,
                      write_idx=None, read_idx=None):
    b, s, _ = h.shape
    hq, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_rep = hq // hk
    quant = "k_scale" in kv
    x = rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
    q = dense(lp["attn"]["q"], x).reshape(b, s, hq, hd)
    k_new = dense(lp["attn"]["k"], x).reshape(b, s, hk, hd)
    v_new = dense(lp["attn"]["v"], x).reshape(b, s, hk, hd)

    bidx = jnp.arange(b)[:, None]
    kv = dict(kv)
    # mode="drop": padded-to-bucket chunks may point past capacity; those
    # writes must vanish, not clamp onto the last slot (see decode docstring)
    if quant:
        # quantize-on-write: int8 codes and their per-(slot, head) scales
        # land on the same slots in one step, so pages stay self-describing
        k_new, k_sv = quantize_q8(k_new)
        v_new, v_sv = quantize_q8(v_new)
        kv["k_scale"] = _cache_write(kv["k_scale"], slots, k_sv,
                                     bidx=bidx, write_idx=write_idx)
        kv["v_scale"] = _cache_write(kv["v_scale"], slots, v_sv,
                                     bidx=bidx, write_idx=write_idx)
    kv["k"] = _cache_write(kv["k"], slots, k_new, bidx=bidx,
                           write_idx=write_idx)
    kv["v"] = _cache_write(kv["v"], slots, v_new, bidx=bidx,
                           write_idx=write_idx)
    k_raw = _cache_view(kv["k"], read_idx)
    v_raw = _cache_view(kv["v"], read_idx)

    q_rope = apply_rope(q, positions, cfg.rope_theta)
    scale = hd ** -0.5
    nope = cfg.dti_sum_alibi

    if impl == "pallas" and quant:
        # quantized-KV contract: hand the kernel the raw int8 codes plus
        # scale sidecars; dequant + read-time RoPE happen in VMEM, and the
        # NoPE stream is the same codes dequantized without rotation
        out = attend(
            q_rope, k_raw, v_raw, positions, pos_buf, window=window,
            is_sum_q=is_sum if nope else None,
            q_nope=q if nope else None, k_nope=None,
            alibi=alibi_slopes(hq) if nope else None,
            seg_q=seg_q, seg_k=seg_buf, scale=scale,
            k_scale=_cache_view(kv["k_scale"], read_idx)[..., None],
            v_scale=_cache_view(kv["v_scale"], read_idx),
            rope_start=0, rope_theta=cfg.rope_theta).astype(h.dtype)
        h = h + dense(lp["attn"]["o"], out.reshape(b, s, hq * hd))
        h, aux = _ffn(lp, h, cfg, kind)
        return h, kv, aux

    if quant:
        # dense oracle path: dequantize the row-major views up front
        k_raw = dequantize_q8(k_raw, _cache_view(kv["k_scale"], read_idx))
        v_raw = dequantize_q8(v_raw, _cache_view(kv["v_scale"], read_idx))
    k_rope = _rope_read(k_raw, pos_buf, cfg.rope_theta)

    if impl == "pallas":
        # fused burst attention into the cache: the kernel reads the
        # row-major cache view directly (contiguous storage, or the paged
        # page-index gather) via index maps, applies every mask term via
        # index arithmetic and keeps the softmax online — no (B,H,s,cap)
        # score/prob tensors, empty cache blocks skipped
        out = attend(
            q_rope, k_rope, v_raw, positions, pos_buf, window=window,
            is_sum_q=is_sum if nope else None,
            q_nope=q if nope else None, k_nope=k_raw if nope else None,
            alibi=alibi_slopes(hq) if nope else None,
            seg_q=seg_q, seg_k=seg_buf, scale=scale).astype(h.dtype)
        h = h + dense(lp["attn"]["o"], out.reshape(b, s, hq * hd))
        h, aux = _ffn(lp, h, cfg, kind)
        return h, kv, aux

    def rep(t):  # (B, cap, Hk, D) -> (B, cap, Hq, D)
        if n_rep == 1:
            return t
        bb, cap, _, dd = t.shape
        return jnp.broadcast_to(t[:, :, :, None, :],
                                (bb, cap, hk, n_rep, dd)).reshape(bb, cap, hq, dd)

    sc_rope = jnp.einsum("bshd,bkhd->bhsk", q_rope, rep(k_rope),
                         preferred_element_type=jnp.float32) * scale
    sc_nope = None
    if cfg.dti_sum_alibi:
        sc_nope = jnp.einsum("bshd,bkhd->bhsk", q, rep(k_raw),
                             preferred_element_type=jnp.float32) * scale

    d = (positions[:, None, :, None] - pos_buf[:, None, None, :]
         ).astype(jnp.float32)
    mask = _decode_mask(pos_buf, positions, window, seg_q, seg_buf)
    out = _decode_attend(sc_rope, sc_nope, alibi_slopes(hq), d, mask, is_sum,
                         lambda p: jnp.einsum("bhsk,bkhd->bshd",
                                              p.astype(h.dtype), rep(v_raw)))
    h = h + dense(lp["attn"]["o"], out.reshape(b, s, hq * hd))
    h, aux = _ffn(lp, h, cfg, kind)
    return h, kv, aux


def _mla_decode_layer(lp: Params, h, kv: Params, *, cfg: ModelConfig,
                      slots, pos_buf, positions, is_sum, window, kind,
                      seg_q=None, seg_buf=None, impl="dense",
                      attend=decode_attention,
                      write_idx=None, read_idx=None):
    """Absorbed-MLA decode: scores and values against the latent cache."""
    b, s, _ = h.shape
    hq = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    quant = "ckv_scale" in kv
    ap = lp["attn"]
    x = rmsnorm(lp["ln_attn"], h, cfg.norm_eps)

    if "q_down" in ap:
        qc = rmsnorm(ap["q_norm"], dense(ap["q_down"], x))
        q = dense(ap["q_up"], qc)
    else:
        q = dense(ap["q"], x)
    q = q.reshape(b, s, hq, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe_rope = apply_rope(q_pe, positions, cfg.rope_theta)

    c_new = rmsnorm(ap["kv_norm"], dense(ap["kv_down"], x))         # (B,s,r)
    kpe_new = dense(ap["k_rope"], x)                                # (B,s,dr)

    bidx = jnp.arange(b)[:, None]
    kv = dict(kv)
    if quant:
        # latent and rope streams quantize separately: per-token scales,
        # written on the same slots as their codes (self-describing pages)
        c_new, c_sv = quantize_q8(c_new)
        kpe_new, p_sv = quantize_q8(kpe_new)
        kv["ckv_scale"] = _cache_write(kv["ckv_scale"], slots, c_sv,
                                       bidx=bidx, write_idx=write_idx)
        kv["kpe_scale"] = _cache_write(kv["kpe_scale"], slots, p_sv,
                                       bidx=bidx, write_idx=write_idx)
    kv["ckv"] = _cache_write(kv["ckv"], slots, c_new, bidx=bidx,
                             write_idx=write_idx)
    kv["kpe"] = _cache_write(kv["kpe"], slots, kpe_new, bidx=bidx,
                             write_idx=write_idx)
    ckv_v = _cache_view(kv["ckv"], read_idx)
    kpe_v = _cache_view(kv["kpe"], read_idx)

    # absorb W_UK into the query, W_UV into the output
    w_up = ap["kv_up"]["w"].reshape(cfg.kv_lora_rank, hq, dn + dv)
    w_uk, w_uv = w_up[..., :dn], w_up[..., dn:]
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)              # (B,s,H,r)

    scale = (dn + dr) ** -0.5
    nope = cfg.dti_sum_alibi

    if quant:
        c_sv_view = _cache_view(kv["ckv_scale"], read_idx)          # (B,cap)
        p_sv_view = _cache_view(kv["kpe_scale"], read_idx)
        if impl == "pallas":
            # quantized MQA form: concatenated int8 codes with a 2-group
            # scale row split at rope_start = r_kv (latent | rope stream);
            # the kernel dequantizes and ropes the kpe tail in VMEM
            q_eff = jnp.concatenate([q_abs, q_pe_rope], axis=-1)
            k_codes = jnp.concatenate([ckv_v, kpe_v], axis=-1)[:, :, None, :]
            k_sc = jnp.stack([c_sv_view, p_sv_view],
                             axis=-1)[:, :, None, :]                # (B,cap,1,2)
            qn_eff = (jnp.concatenate([q_abs, q_pe], axis=-1)
                      if nope else None)
            o_lat = attend(
                q_eff, k_codes, ckv_v[:, :, None, :], positions, pos_buf,
                window=window, is_sum_q=is_sum if nope else None,
                q_nope=qn_eff, k_nope=None,
                alibi=alibi_slopes(hq) if nope else None,
                seg_q=seg_q, seg_k=seg_buf, scale=scale,
                k_scale=k_sc, v_scale=c_sv_view[:, :, None],
                rope_start=cfg.kv_lora_rank, rope_theta=cfg.rope_theta)
            out = jnp.einsum("bshr,rhd->bshd", o_lat.astype(h.dtype), w_uv)
            h = h + dense(ap["o"], out.reshape(b, s, hq * dv))
            h, aux = _ffn(lp, h, cfg, kind)
            return h, kv, aux
        ckv_v = dequantize_q8(ckv_v, c_sv_view)
        kpe_v = dequantize_q8(kpe_v, p_sv_view)

    kpe_rope = _rope_read(kpe_v[:, :, None, :], pos_buf,
                          cfg.rope_theta)[:, :, 0, :]               # (B,cap,dr)

    if impl == "pallas":
        # absorbed MLA as MQA for the fused kernel (Hk=1): concatenate the
        # latent and rope streams so one score matmul covers both terms —
        # q_eff . k_eff == q_abs . ckv + q_pe_rope . kpe_rope — and keep
        # values in the latent (Dv = r_kv != Dqk); W_UV folds after.
        q_eff = jnp.concatenate([q_abs, q_pe_rope], axis=-1)
        k_eff = jnp.concatenate([ckv_v, kpe_rope], axis=-1)[:, :, None, :]
        qn_eff = (jnp.concatenate([q_abs, q_pe], axis=-1) if nope else None)
        kn_eff = (jnp.concatenate([ckv_v, kpe_v], axis=-1)[:, :, None, :]
                  if nope else None)
        o_lat = attend(
            q_eff, k_eff, ckv_v[:, :, None, :], positions, pos_buf,
            window=window, is_sum_q=is_sum if nope else None,
            q_nope=qn_eff, k_nope=kn_eff,
            alibi=alibi_slopes(hq) if nope else None,
            seg_q=seg_q, seg_k=seg_buf, scale=scale)
        out = jnp.einsum("bshr,rhd->bshd", o_lat.astype(h.dtype), w_uv)
        h = h + dense(ap["o"], out.reshape(b, s, hq * dv))
        h, aux = _ffn(lp, h, cfg, kind)
        return h, kv, aux

    sc_rope = (jnp.einsum("bshr,bkr->bhsk", q_abs, ckv_v,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bshd,bkd->bhsk", q_pe_rope, kpe_rope,
                            preferred_element_type=jnp.float32)) * scale
    sc_nope = None
    if cfg.dti_sum_alibi:
        sc_nope = (jnp.einsum("bshr,bkr->bhsk", q_abs, ckv_v,
                              preferred_element_type=jnp.float32)
                   + jnp.einsum("bshd,bkd->bhsk", q_pe, kpe_v,
                                preferred_element_type=jnp.float32)) * scale

    d = (positions[:, None, :, None] - pos_buf[:, None, None, :]
         ).astype(jnp.float32)
    mask = _decode_mask(pos_buf, positions, window, seg_q, seg_buf)

    def v_agg(p):
        o_lat = jnp.einsum("bhsk,bkr->bshr", p.astype(h.dtype), ckv_v)
        return jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)

    out = _decode_attend(sc_rope, sc_nope, alibi_slopes(hq), d, mask, is_sum,
                         v_agg)
    h = h + dense(ap["o"], out.reshape(b, s, hq * dv))
    h, aux = _ffn(lp, h, cfg, kind)
    return h, kv, aux


def _ffn(lp: Params, h, cfg: ModelConfig, kind: str):
    from repro.models.layers import swiglu
    x = rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
    if kind == "moe":
        f, aux = moe_ffn(lp["ffn"], x, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                         norm_topk=cfg.norm_topk)
    else:
        f, aux = swiglu(lp["ffn"], x), jnp.zeros((), jnp.float32)
    return h + f, aux


def make_decode_fn(cfg: ModelConfig, *, window: int, ring: bool,
                   yes_id: int = 3, no_id: int = 4,
                   attn_impl: Optional[str] = None,
                   block_size: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   mesh=None) -> Callable:
    """(params, cache, tokens (B,s), positions (B,s), is_sum (B,s)[,
    valid (B,s), commit (B,), seg (B,s)]) -> (p_click (B, s), new_cache).

    ``attn_impl`` selects the per-layer attention path:

    * ``"dense"``  — masked einsums over the full cache capacity (the
      semantic oracle; also the fallback when ``attn_impl=None`` and the
      model config doesn't train on the kernel path).
    * ``"pallas"`` — the fused decode-attention kernel
      (``repro.kernels.decode_attn``): one online-softmax pass over the
      cache with every serve mask term fused, occupancy-skipping empty
      cache blocks. Covers the full operand set below (valid/commit/seg),
      GQA and absorbed MLA, ring and windowed caches.
    * ``None``     — inherit the model's training-time choice:
      ``"pallas"`` when ``cfg.attn_impl == "pallas"``, else ``"dense"``
      (so a config that trains on the kernel path serves on it too).

    ``block_size``/``interpret`` tune the kernel path only (interpret
    auto-resolves off-TPU, see ``repro.kernels.default_interpret``;
    ``block_size=None`` defers to ``repro.kernels.autotune.decode_block``).
    ``mesh`` (the scheduler's serving mesh) runs the kernel per shard under
    ``shard_map``: Mosaic kernels cannot be partitioned by GSPMD.

    Quantized caches (``init_lm_cache(kv_dtype="int8")``) are detected from
    the cache structure: layers quantize KV on write (codes + scale
    sidecars land on the same slots), the dense path dequantizes the
    row-major view up front, and the Pallas path hands the kernel raw int8
    codes with their scales so dequant happens in VMEM (docs/kernels.md).

    The three optional operands are what the continuous-batching scheduler
    (repro.serve.scheduler) runs on:

    * ``valid``  — right-padded chunks: invalid slots are written with
      position -1 (never attendable) and the per-row cursor advances by the
      number of *valid* tokens only, so rows of different real lengths share
      one padded-to-bucket jit shape.
    * ``commit`` — per-row bool. A row with ``commit=False`` is a *scoring
      burst*: its tokens attend the row's committed cache (the shared user
      context) plus themselves, but the returned cache keeps the row's
      ``pos``/``cursor`` unchanged, so the next burst sees the pristine
      context again — candidate k+1 never reads candidate k's KV. This is
      the decode-side shared-context reuse; it requires ``ring=False``
      (a wrapped burst write would orphan old positions onto burst KV).
    * ``seg``    — per-token segment for multi-candidate bursts: -1 = shared
      (context chunks), 0..k-1 = candidate index. Committed cache entries
      are shared by construction; burst tokens attend context + their own
      segment only, so one burst step scores a whole candidate slate — the
      decode-side analog of the training paradigm's k isolated targets.

    Paged caches (``init_lm_cache(page_size=...)``) are detected from the
    cache structure: reads and writes go through the page-index gather
    maps of ``repro.serve.cache.physical_slots``, everything else —
    including the Pallas kernel, which consumes the gathered row-major
    view — is unchanged. Since a gathered view holds the same values at
    the same logical slots as contiguous storage and unmapped slots carry
    ``pos = -1``, paged and contiguous decode are byte-identical
    (tests/test_paged_cache.py). Paged requires ``ring=False``.
    """
    mla = cfg.attn_type == "mla"
    layer_fn = _mla_decode_layer if mla else _gqa_decode_layer
    if attn_impl is None:
        attn_impl = "pallas" if cfg.attn_impl == "pallas" else "dense"
    assert attn_impl in ("dense", "pallas"), f"unknown decode attn_impl {attn_impl!r}"
    attend = partial(decode_attention, block_size=block_size,
                     interpret=interpret, mesh=mesh)

    def decode(params: Params, cache: Cache, tokens: jax.Array,
               positions: jax.Array, is_sum: jax.Array,
               valid: Optional[jax.Array] = None,
               commit: Optional[jax.Array] = None,
               seg: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Cache]:
        b, s = tokens.shape
        slots = slot_indices(cache, s, ring=ring)
        bidx = jnp.arange(b)[:, None]
        write_idx = read_idx = None
        if is_paged(cache):
            # page-index gather maps (docs/serving.md): flat (B, cap) is
            # logical->physical; reads gather a row-major view through it,
            # writes scatter at each token's physical slot. Unmapped pages
            # (flat == -1) drop writes and gather arbitrary pool bytes that
            # pos = -1 keeps unattendable.
            assert not ring, "paged caches are non-ring"
            cap = cache["pos"].shape[1]
            flat = physical_slots(cache)
            write_idx = jnp.take_along_axis(
                flat, jnp.clip(slots, 0, cap - 1), axis=1)
            write_idx = jnp.where(slots < cap, write_idx, -1)
            read_idx = jnp.maximum(flat, 0)
        pos_write = (positions if valid is None
                     else jnp.where(valid, positions, -1))
        # mode="drop": a chunk right-padded to its bucket may index past
        # capacity when a row's cursor sits near the top; dropping those
        # writes (instead of XLA's default clamp onto the last slot) keeps
        # the scheduler's "real tokens always fit" invariant sufficient.
        pos_buf = cache["pos"].at[bidx, slots].set(pos_write, mode="drop")
        seg_buf = None
        if seg is not None:
            cap = cache["pos"].shape[1]
            seg_buf = jnp.full((b, cap), -1, jnp.int32).at[bidx, slots].set(
                seg, mode="drop")
        n_new = (s if valid is None
                 else valid.sum(axis=-1).astype(jnp.int32))
        if commit is None:
            new_cache = dict(cache, pos=pos_buf,
                             cursor=cache["cursor"] + n_new)
        else:
            assert not ring, "non-committing bursts require ring=False"
            new_cache = dict(
                cache,
                pos=jnp.where(commit[:, None], pos_buf, cache["pos"]),
                cursor=jnp.where(commit, cache["cursor"] + n_new,
                                 cache["cursor"]))

        h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)

        n_prefix = cfg.first_dense_layers if cfg.moe else 0

        # The (L, B, cap, ...) cache tensors ride the scan CARRY and are
        # updated per layer with dynamic_update_index_in_dim: XLA keeps
        # while-loop carries in place, so the donated cache is mutated with
        # no xs/ys double buffer (which would cost a full extra cache).
        # The carry is a tuple over kv_keys(cache) — codes plus any
        # quantization-scale sidecars — so int8 caches thread their scales
        # through the scan without a second code path.
        kv_names = kv_keys(cache)

        def run_group(h, kv_all, group: Params, kind: str, lo: int):
            n = jax.tree_util.tree_leaves(group)[0].shape[0]

            def body(carry, xs):
                hc, full = carry
                lp, li = xs
                layer_kv = {nm: jax.lax.dynamic_index_in_dim(
                    t, li, 0, keepdims=False)
                    for nm, t in zip(kv_names, full)}
                hh, layer_kv, aux = layer_fn(
                    lp, hc, layer_kv, cfg=cfg, slots=slots, pos_buf=pos_buf,
                    positions=positions, is_sum=is_sum, window=window,
                    kind=kind, seg_q=seg, seg_buf=seg_buf, impl=attn_impl,
                    attend=attend, write_idx=write_idx, read_idx=read_idx)
                full = tuple(jax.lax.dynamic_update_index_in_dim(
                    t, layer_kv[nm].astype(t.dtype), li, 0)
                    for nm, t in zip(kv_names, full))
                return (hh, full), None

            idx = lo + jnp.arange(n, dtype=jnp.int32)
            (h, kv_all), _ = jax.lax.scan(body, (h, kv_all), (group, idx))
            return h, kv_all

        kv_all = tuple(cache[nm] for nm in kv_names)
        if "prefix" in params:
            h, kv_all = run_group(h, kv_all, params["prefix"], "dense", 0)
        h, kv_all = run_group(h, kv_all, params["stack"],
                              "moe" if cfg.moe else "dense", n_prefix)
        for nm, t in zip(kv_names, kv_all):
            new_cache[nm] = t

        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits2 = ctr_logits(params, cfg, h, yes_id, no_id)
        p = jax.nn.softmax(logits2.astype(jnp.float32), axis=-1)[..., 0]
        return p, new_cache

    return decode


# ===========================================================================
# batched CTR scoring server (example-facing)
# ===========================================================================

@dataclasses.dataclass
class CTRServer:
    """Batched pointwise CTR scorer over prefill rows.

    Two entry points, both scoring a stacked batch of ``max_len``-padded
    rows in one jitted prefill call:

    * ``score``              — one sliding-window prompt per candidate (the
      paper's inference procedure; re-encodes the context per candidate).
    * ``score_multi_target`` — one multi-target row per *request* (shared
      context + k isolated candidate segments); the context is encoded once
      per request. Same scores, O(n^2 + k·n) instead of O(k·n^2).

    The seq dim is fixed (``max_len``) but the batch dim is whatever the
    caller passes — each distinct batch size jit-compiles once, so feed
    fixed-size groups in steady state. For sustained traffic with
    admission/eviction, bucketed shapes and decode-side context KV reuse,
    use ``repro.serve.scheduler.ServeScheduler`` instead.
    """
    params: Params
    cfg: ModelConfig
    max_len: int
    yes_id: int = 3
    no_id: int = 4

    def __post_init__(self):
        self._prefill = jax.jit(make_prefill_fn(
            self.cfg, yes_id=self.yes_id, no_id=self.no_id))
        self._mt_prefill = jax.jit(make_multi_target_prefill_fn(
            self.cfg, yes_id=self.yes_id, no_id=self.no_id))

    def update_params(self, params) -> None:
        """Hot-swap serving weights (e.g. from a continual-training
        ``ParamPublisher``); params are a jit argument, so no recompile."""
        self.params = params

    def score(self, prompts) -> "list[float]":
        import numpy as np
        b = len(prompts)
        batch = {k: np.stack([p[k] for p in prompts])
                 for k in ("tokens", "positions", "is_sum", "valid")}
        p = np.asarray(self._prefill(self.params, batch))
        out = []
        for i in range(b):
            sums = np.flatnonzero(batch["is_sum"][i])
            out.append(float(p[i, sums[-1]]) if len(sums) else 0.5)
        return out

    def score_multi_target(self, requests) -> "list[list[float]]":
        """``requests``: (context_tokens, candidate_tokens) pairs, each a
        list of per-interaction / per-candidate token lists. Returns the k
        candidate scores per request, in candidate order."""
        import numpy as np
        from repro.core.dti import (build_multi_target_request,
                                    candidate_sum_slots)
        rows = [build_multi_target_request(ctx, cands, max_len=self.max_len)
                for ctx, cands in requests]
        batch = {k: np.stack([r[k] for r in rows]) for k in
                 ("tokens", "positions", "segment_ids", "is_sum", "valid")}
        p = np.asarray(self._mt_prefill(self.params, batch))
        return [[float(p[i, s]) for s in candidate_sum_slots(rows[i])]
                for i in range(len(rows))]


__all__ = ["make_prefill_fn", "make_multi_target_prefill_fn",
           "make_decode_fn", "CTRServer"]
