"""Shared neural-net building blocks (pure-functional, pytree params).

Every module here follows the same convention:
  init_*(rng, ...) -> params pytree of jnp arrays
  apply fn(params, x, ...) -> output

Params are plain dicts so they stack cleanly under ``jax.lax.scan`` (layer
stacking) and shard cleanly under GSPMD (leaf-path -> PartitionSpec rules in
``repro.sharding.partition``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def normal_init(rng, shape, scale: float, dtype=jnp.float32):
    return (scale * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


def init_linear(rng, d_in: int, d_out: int, *, bias: bool = False,
                scale: Optional[float] = None, dtype=jnp.float32,
                lora_rank: int = 0, lora_alpha: float = 16.0) -> Params:
    """A linear layer, optionally with a LoRA adapter (A: d_in x r, B: r x d_out).

    LoRA follows arXiv:2106.09685: W_eff = W + (alpha / r) * A @ B, with A
    gaussian-initialised and B zero-initialised so training starts at W.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    k_w, k_a = jax.random.split(rng)
    p: Params = {"w": normal_init(k_w, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    if lora_rank > 0:
        p["lora_a"] = normal_init(k_a, (d_in, lora_rank), 1.0 / math.sqrt(d_in), dtype)
        p["lora_b"] = jnp.zeros((lora_rank, d_out), dtype)
        p["lora_scale"] = jnp.asarray(lora_alpha / lora_rank, dtype)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    """Apply a (possibly LoRA-augmented) linear layer."""
    y = x @ p["w"]
    if "lora_a" in p:
        # the scope holds the adapter term and not the sum: XLA fuses the
        # sum into the projection's own matmul, whose op_name is its root's
        with jax.named_scope("lora"):
            d = (x @ p["lora_a"]) @ p["lora_b"] * p["lora_scale"]
        y = y + d
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(rng, d_model: int, d_ff: int, *, dtype=jnp.float32,
                lora_rank: int = 0) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "gate": init_linear(k1, d_model, d_ff, dtype=dtype, lora_rank=lora_rank),
        "up": init_linear(k2, d_model, d_ff, dtype=dtype, lora_rank=lora_rank),
        "down": init_linear(k3, d_ff, d_model, dtype=dtype, lora_rank=lora_rank),
    }


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    from repro.sharding.act import constrain_tokens
    h = jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x)
    return dense(p["down"], constrain_tokens(h, kind="ffn"))


def init_mlp(rng, dims: Sequence[int], *, bias: bool = True, dtype=jnp.float32) -> Params:
    """Plain MLP used by recsys / GNN heads: dims = [in, h1, ..., out]."""
    keys = jax.random.split(rng, len(dims) - 1)
    return {f"fc{i}": init_linear(keys[i], dims[i], dims[i + 1], bias=bias, dtype=dtype)
            for i in range(len(dims) - 1)}


def mlp(p: Params, x: jax.Array, *, act=jax.nn.relu, final_act: bool = False) -> jax.Array:
    n = len(p)
    for i in range(n):
        x = dense(p[f"fc{i}"], x)
        if i < n - 1 or final_act:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# positional encodings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies for RoPE (arXiv:2104.09864)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotate ``x`` [..., S, H, D] by ``positions`` [..., S] (RoPE).

    Uses the (x1, x2) half-split convention (Llama / NeoX style).
    """
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                      # (D/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(n_heads: int) -> jax.Array:
    """Standard geometric ALiBi slopes (arXiv:2108.12409)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]
    if math.log2(n_heads).is_integer():
        s = pow2_slopes(n_heads)
    else:
        closest = 2 ** math.floor(math.log2(n_heads))
        s = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
        s = s + extra
    return jnp.asarray(s, jnp.float32)


__all__ = [
    "Params", "init_linear", "dense", "init_rmsnorm", "rmsnorm",
    "init_layernorm", "layernorm", "init_swiglu", "swiglu", "init_mlp", "mlp",
    "rope_freqs", "apply_rope", "alibi_slopes", "normal_init",
]
