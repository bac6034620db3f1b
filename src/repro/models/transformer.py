"""Decoder-only transformer with ``lax.scan`` over stacked layer params.

One model covers every assigned LM arch: GQA (minicpm-2b, qwen2-1.5b), MLA
(minicpm3-4b, deepseek-v2-236b), MoE (qwen2-moe-a2.7b, deepseek-v2-236b), plus
the paper's own DTI-Llama configuration. DTI training features (streaming
prompts / windowed attention / SUM loss / reset / SUM-ALiBi) are enabled per
forward call via ``DTIAttnOpts`` so the same weights serve both paradigms.

Scan-over-layers keeps the lowered HLO O(1) in depth, which is what makes the
512-device dry-run compiles tractable; it also gives remat a natural unit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.windowed import ResetConfig
from repro.models import attention as attn_mod
from repro.models.attention import DTIAttnOpts, gqa_attention, init_gqa, init_mla, mla_attention
from repro.models.layers import (Params, dense, init_linear, init_rmsnorm,
                                 init_swiglu, normal_init, rmsnorm, swiglu)
from repro.models.moe import init_moe, moe_ffn


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 32000
    head_dim: Optional[int] = None
    attn_type: str = "gqa"              # "gqa" | "mla"
    qkv_bias: bool = False
    # MLA
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_d_ff: Optional[int] = None
    first_dense_layers: int = 0
    capacity_factor: float = 1.25
    norm_topk: bool = True
    # positional / attention
    rope_theta: float = 10000.0
    window: int = 0                     # 0 = full causal
    attn_impl: str = "dense"            # "dense" | "blocked" | "pallas"
    attn_q_chunk: int = 4               # q-block chunking (blocked impl)
    # pallas kernel tile; None = autotuned (repro.kernels.autotune)
    attn_block_size: Optional[int] = None
    # DTI
    dti_sum_token: bool = False         # model reserves a [SUM] token
    dti_sum_alibi: bool = True
    dti_sum_isolated: bool = True
    dti_reset: bool = True
    reset_y_min: float = 0.0
    reset_y_max: float = 0.3
    # training
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    lora_rank: int = 0
    remat: bool = True
    remat_policy: str = "nothing"       # "nothing" | "dots" | "none"
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    logits_chunk: int = 0               # 0 = unchunked LM loss

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def layer_kind(self, i: int) -> str:
        if self.moe and i >= self.first_dense_layers:
            return "moe"
        return "dense"

    def reset_config(self, window_tokens: int) -> Optional[ResetConfig]:
        if not self.dti_reset:
            return None
        return ResetConfig(self.reset_y_min, self.reset_y_max,
                           midpoint=window_tokens / 2.0)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(rng, cfg: ModelConfig, kind: str) -> Params:
    ka, kf = jax.random.split(rng)
    if cfg.attn_type == "mla":
        attn = init_mla(ka, cfg.d_model, cfg.n_heads,
                        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
                        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                        v_head_dim=cfg.v_head_dim, dtype=cfg.pdtype,
                        lora_rank=cfg.lora_rank)
    else:
        attn = init_gqa(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                        qkv_bias=cfg.qkv_bias, dtype=cfg.pdtype,
                        lora_rank=cfg.lora_rank)
    if kind == "moe":
        ffn = init_moe(kf, cfg.d_model, n_experts=cfg.n_experts,
                       moe_d_ff=cfg.moe_d_ff, top_k=cfg.top_k,
                       n_shared=cfg.n_shared_experts, shared_d_ff=cfg.shared_d_ff,
                       dtype=cfg.pdtype, lora_rank=cfg.lora_rank)
    else:
        ffn = init_swiglu(kf, cfg.d_model, cfg.d_ff, dtype=cfg.pdtype,
                          lora_rank=cfg.lora_rank)
    return {"attn": attn, "ffn": ffn,
            "ln_attn": init_rmsnorm(cfg.d_model, cfg.pdtype),
            "ln_ffn": init_rmsnorm(cfg.d_model, cfg.pdtype)}


def init_params(rng, cfg: ModelConfig) -> Params:
    ke, kh, *kl = jax.random.split(rng, 2 + cfg.n_layers)
    p: Params = {"embed": normal_init(ke, (cfg.vocab_size, cfg.d_model), 0.02,
                                      cfg.pdtype),
                 "ln_f": init_rmsnorm(cfg.d_model, cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = init_linear(kh, cfg.d_model, cfg.vocab_size,
                                   scale=0.02, dtype=cfg.pdtype)
    n_dense_pre = cfg.first_dense_layers if cfg.moe else 0
    if n_dense_pre:
        p["prefix"] = _stack([_init_layer(kl[i], cfg, "dense")
                              for i in range(n_dense_pre)])
    kind = "moe" if cfg.moe else "dense"
    p["stack"] = _stack([_init_layer(kl[i], cfg, kind)
                         for i in range(n_dense_pre, cfg.n_layers)])
    return p


def _stack(layers):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _attn_block(cfg: ModelConfig, seq: int) -> int:
    """Block size of the Pallas windowed attention at row length ``seq``."""
    if cfg.attn_block_size is not None:
        return cfg.attn_block_size
    from repro.kernels.autotune import train_block
    return train_block(seq, cfg.hd)


def attn_band(cfg: ModelConfig, window: int, seq: int):
    """The windowed kernels' ``Band`` at row length ``seq``: the forward's
    block, its SUM gate sub-tile, and whether the DTI loss's calls run the
    SUM stream (NoPE+ALiBi or the reset)."""
    from repro.kernels.windowed_attn.windowed_attn import (Band,
                                                           choose_block,
                                                           sub_tile)
    blk, s_pad = choose_block(seq, _attn_block(cfg, seq))
    return Band(window, blk, s_pad // blk, sub_tile(blk),
                cfg.dti_sum_token and (cfg.dti_sum_alibi or cfg.dti_reset))


def _layer_fwd(lp: Params, h: jax.Array, cfg: ModelConfig, kind: str, *,
               positions, window, impl, dti: Optional[DTIAttnOpts],
               valid, cache=None):
    with jax.named_scope("lm.attn"):
        x = rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
        block_size = _attn_block(cfg, x.shape[1])
        if cfg.attn_type == "mla":
            a, new_cache = mla_attention(
                lp["attn"], x, n_heads=cfg.n_heads,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                v_head_dim=cfg.v_head_dim, positions=positions,
                window=window, rope_theta=cfg.rope_theta, impl=impl,
                q_chunk=cfg.attn_q_chunk, block_size=block_size, dti=dti,
                cache=cache, valid=valid)
        else:
            a, new_cache = gqa_attention(
                lp["attn"], x, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, positions=positions, window=window,
                rope_theta=cfg.rope_theta, impl=impl, q_chunk=cfg.attn_q_chunk,
                block_size=block_size, dti=dti, cache=cache,
                valid=valid)
    h = h + a
    with jax.named_scope("lm.mlp"):
        x = rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
        if kind == "moe":
            f, aux = moe_ffn(lp["ffn"], x, n_experts=cfg.n_experts,
                             top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             norm_topk=cfg.norm_topk)
        else:
            f, aux = swiglu(lp["ffn"], x), jnp.zeros((), jnp.float32)
    return h + f, aux, new_cache


@partial(jax.named_call, name="lm.forward")
def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            positions: Optional[jax.Array] = None,
            is_sum: Optional[jax.Array] = None,
            valid: Optional[jax.Array] = None,
            segment_ids: Optional[jax.Array] = None,
            seg_shared: Optional[int] = None,
            dti_enabled: bool = False,
            window: Optional[int] = None,
            caches: Optional[list] = None,
            return_hidden: bool = False,
            ) -> Dict[str, Any]:
    """Run the decoder. Returns dict with 'hidden', 'aux_loss', 'caches'.

    ``segment_ids`` (packed rows, -1 on padding) enforce cross-segment
    isolation in every attention layer; positions are expected to restart
    per segment so RoPE/window/ALiBi/reset distances stay per-prompt.

    ``seg_shared`` marks one segment id (the user context of a multi-target
    serving row) as a shared prefix every other segment may attend;
    candidate segments keep positions continuing after the context instead
    of restarting. Dense attention path only.

    Logits are NOT materialised here — call ``lm_logits`` / the loss fns, so
    CTR training can touch only the two label rows of the vocab matrix.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    win = cfg.window if window is None else window
    impl = cfg.attn_impl

    from repro.sharding.act import constrain_tokens
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    h = constrain_tokens(h)
    h0 = h

    dti: Optional[DTIAttnOpts] = None
    if (dti_enabled and is_sum is not None) or segment_ids is not None:
        use_sum = dti_enabled and is_sum is not None
        dti = DTIAttnOpts(is_sum=is_sum if use_sum else None, h0=h0,
                          reset=(cfg.reset_config(win)
                                 if use_sum and cfg.dti_reset else None),
                          sum_alibi=cfg.dti_sum_alibi,
                          sum_isolated=cfg.dti_sum_isolated,
                          segment_ids=segment_ids, seg_shared=seg_shared)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: list = []
    cache_i = 0

    def run_group(h, group: Params, kind: str, aux_total, cache_i):
        nonlocal new_caches
        if caches is not None:
            # decode path: python loop (cache pytrees per layer)
            n = jax.tree_util.tree_leaves(group)[0].shape[0]
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda x: x[i], group)
                h, aux, nc = _layer_fwd(lp, h, cfg, kind, positions=positions,
                                        window=win, impl="dense", dti=dti,
                                        valid=valid, cache=caches[cache_i])
                new_caches.append(nc)
                aux_total = aux_total + aux
                cache_i += 1
            return h, aux_total, cache_i

        def body(carry, lp):
            h, aux_acc = carry
            h, aux, _ = _layer_fwd(lp, h, cfg, kind, positions=positions,
                                   window=win, impl=impl, dti=dti, valid=valid)
            # layer-boundary activation pinning (no-op off-mesh):
            # token-sharded residual stream, features replicated
            h = constrain_tokens(h)
            return (h, aux_acc + aux), None

        if cfg.remat and cfg.remat_policy != "none":
            # "nothing": save only the scan carry per layer (recompute all
            # intermediates in bwd) — the memory-lean default at seq 4k.
            # "dots": save weight-stationary matmul outputs (recompute only
            # attention) — faster bwd, ~8x the activation footprint.
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else
                      jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), group)
        return h, aux_total, cache_i

    if "prefix" in params:
        h, aux_total, cache_i = run_group(h, params["prefix"], "dense",
                                          aux_total, cache_i)
    kind = "moe" if cfg.moe else "dense"
    h, aux_total, cache_i = run_group(h, params["stack"], kind, aux_total, cache_i)

    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    out: Dict[str, Any] = {"hidden": h, "aux_loss": aux_total}
    if caches is not None:
        out["caches"] = new_caches
    return out


def lm_logits(params: Params, cfg: ModelConfig, hidden: jax.Array,
              rows: Optional[jax.Array] = None) -> jax.Array:
    """hidden @ vocab. ``rows`` selects a subset of vocab rows (e.g. yes/no)."""
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]["w"].T
    # w: (V, d) either way after this
    if not cfg.tie_embeddings:
        w = params["lm_head"]["w"].T
    if rows is not None:
        w = jnp.take(w, rows, axis=0)
    return jnp.einsum("...d,vd->...v", hidden, w.astype(hidden.dtype))


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))


__all__ = ["ModelConfig", "init_params", "forward", "lm_logits", "count_params",
           "attn_band"]
