"""Mixture-of-Experts FFN: shared experts + routed top-k with capacity dispatch.

Dispatch is the gather/scatter formulation (not the GShard dense-one-hot
einsum): tokens are gathered into per-expert capacity slots via indices built
from a token->expert cumsum, experts run as one batched einsum, and results
scatter back weighted by the gate. This wastes zero FLOPs on non-routed pairs
(the one-hot formulation costs O(T * E * C * d) in pure dispatch matmuls) and
under GSPMD the gather lowers to activation all-gathers along the expert axis,
which ``repro.launch.hlo.collective_bytes`` counts as collective traffic.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense, init_linear, init_swiglu, swiglu


def init_moe(rng, d_model: int, *, n_experts: int, moe_d_ff: int, top_k: int,
             n_shared: int = 0, shared_d_ff: Optional[int] = None,
             dtype=jnp.float32, lora_rank: int = 0) -> Params:
    kr, ke, ks = jax.random.split(rng, 3)
    # experts as stacked weights (E, d, f) so they shard over the expert axis
    kge, kue, kde = jax.random.split(ke, 3)
    scale = d_model ** -0.5
    p: Params = {
        "router": init_linear(kr, d_model, n_experts, dtype=jnp.float32),
        "w_gate": scale * jax.random.normal(kge, (n_experts, d_model, moe_d_ff)),
        "w_up": scale * jax.random.normal(kue, (n_experts, d_model, moe_d_ff)),
        "w_down": (moe_d_ff ** -0.5) * jax.random.normal(kde, (n_experts, moe_d_ff, d_model)),
    }
    p["w_gate"] = p["w_gate"].astype(dtype)
    p["w_up"] = p["w_up"].astype(dtype)
    p["w_down"] = p["w_down"].astype(dtype)
    if n_shared > 0:
        sdf = shared_d_ff or moe_d_ff
        p["shared"] = init_swiglu(ks, d_model, n_shared * sdf, dtype=dtype,
                                  lora_rank=lora_rank)
    return p


def _topk_gates(logits: jax.Array, top_k: int, norm_topk: bool):
    """(T, E) fp32 -> gates (T, k), expert ids (T, k)."""
    gates, ids = jax.lax.top_k(logits, top_k)          # (T,k)
    gates = jax.nn.softmax(gates, axis=-1) if norm_topk else \
        jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), ids, axis=-1)
    return gates, ids


def moe_ffn(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25, norm_topk: bool = True,
            aux_loss_coef: float = 0.001):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    logits = dense(p["router"], xt.astype(jnp.float32))          # (T, E)
    gates, ids = _topk_gates(logits, top_k, norm_topk)           # (T, k)

    # ---- capacity-slot assignment -------------------------------------
    cap = max(1, int(capacity_factor * t * top_k / n_experts))
    onehot = jax.nn.one_hot(ids, n_experts, dtype=jnp.int32)     # (T,k,E)
    flat = onehot.reshape(t * top_k, n_experts)
    pos_in_e = jnp.cumsum(flat, axis=0) * flat - 1               # (T*k, E)
    pos = pos_in_e.max(axis=-1)                                  # (T*k,)
    eid = ids.reshape(t * top_k)
    keep = (pos >= 0) & (pos < cap)
    slot = jnp.where(keep, eid * cap + pos, t * 0 + n_experts * cap)  # drop slot

    token_of_slot = jnp.full((n_experts * cap + 1,), 0, jnp.int32)
    token_of_slot = token_of_slot.at[slot].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k))
    slot_used = jnp.zeros((n_experts * cap + 1,), bool).at[slot].set(keep)
    token_of_slot, slot_used = token_of_slot[:-1], slot_used[:-1]

    # ---- expert compute ------------------------------------------------
    xe = jnp.take(xt, token_of_slot, axis=0).reshape(n_experts, cap, d)
    xe = xe * slot_used.reshape(n_experts, cap, 1).astype(xe.dtype)
    h_g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    h_u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h_g * h_u, p["w_down"])      # (E,cap,d)

    # ---- combine back --------------------------------------------------
    # scatter-add each slot's output (pre-scaled by its gate) straight into
    # (T, d): the (T, top_k, d) gather intermediate this replaces costs
    # T*k*d bytes (4 GiB/device at 1M tokens for deepseek-v2) for zero
    # extra information.
    yflat = ye.reshape(n_experts * cap, d)
    gate_flat = (gates.reshape(t * top_k) * keep).astype(yflat.dtype)
    gate_of_slot = jnp.zeros((n_experts * cap + 1,), yflat.dtype
                             ).at[slot].set(gate_flat)[:-1]
    out = jnp.zeros((t, d), yflat.dtype).at[token_of_slot].add(
        yflat * (gate_of_slot * slot_used.astype(yflat.dtype))[:, None])
    # GSPMD replicates data-dependent scatter outputs — re-pin to the token
    # sharding or every MoE layer materialises a full (T, d) copy per device
    # (86 GiB at 1M tokens for qwen2-moe prefill; §Perf log)
    from repro.sharding.act import constrain_tokens
    out = constrain_tokens(out)

    if "shared" in p:
        out = out + swiglu(p["shared"], xt)

    # ---- load-balance auxiliary loss (Switch, arXiv:2101.03961) --------
    probs = jax.nn.softmax(logits, axis=-1)                      # (T,E)
    frac_tokens = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (t * top_k)
    frac_probs = probs.mean(axis=0)
    aux = aux_loss_coef * n_experts * jnp.sum(frac_tokens * frac_probs)

    return out.reshape(b, s, d), aux


__all__ = ["init_moe", "moe_ffn"]
