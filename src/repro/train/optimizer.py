"""AdamW + LR schedules (cosine and WSD) + grad clipping. No optax installed —
built from scratch, optax-compatible in spirit (init/update pair).

Supports fp32 master weights over bf16 params (``master_fp32``), trainable-
subset masking (LoRA fine-tuning trains only lora_a/lora_b leaves), and
ZeRO-1-style optimizer-state sharding hooks (state pytree mirrors the param
pytree, so ``repro.sharding.partition`` can lay it out over the data axis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

Params = Any


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 1e-3
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.001
    grad_clip: float = 1.0
    schedule: str = "cosine"        # "cosine" | "wsd" | "const"
    warmup_steps: int = 100
    total_steps: int = 1000
    decay_frac: float = 0.1         # WSD: fraction of steps in decay phase
    min_lr_frac: float = 0.1
    master_fp32: bool = True
    trainable: Optional[str] = None  # None = all, "lora" = lora_* leaves only


class OptState(NamedTuple):
    step: jax.Array
    mu: Params
    nu: Params
    master: Optional[Params]


def schedule_lr(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, s / jnp.maximum(cfg.warmup_steps, 1))
    if cfg.schedule == "const":
        return cfg.lr * warm
    if cfg.schedule == "cosine":
        t = jnp.clip((s - cfg.warmup_steps)
                     / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
        return cfg.lr * warm * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)
    if cfg.schedule == "wsd":
        # Warmup-Stable-Decay (MiniCPM, arXiv:2404.06395)
        decay_start = cfg.total_steps * (1 - cfg.decay_frac)
        t = jnp.clip((s - decay_start)
                     / jnp.maximum(cfg.total_steps - decay_start, 1), 0, 1)
        stable = 1.0 - (1 - cfg.min_lr_frac) * t
        return cfg.lr * warm * stable
    raise ValueError(cfg.schedule)


LORA_LEAVES = ("lora_a", "lora_b")


def _is_lora(path) -> bool:
    """The adapter factors; ``lora_scale`` is a constant, not a weight."""
    return bool(path) and getattr(path[-1], "key", None) in LORA_LEAVES


def _trainable_mask(cfg: OptimizerConfig, params: Params) -> Params:
    if cfg.trainable is None:
        return jax.tree_util.tree_map(lambda _: True, params)
    assert cfg.trainable == "lora"
    return jax.tree_util.tree_map_with_path(lambda p, _: _is_lora(p), params)


def freeze_non_lora(params: Params) -> Params:
    """stop_gradient on every leaf but the LoRA factors: the frozen
    leaves' grads are constant zeros that XLA drops, so a PEFT step holds
    no grad buffers for the base weights."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x if _is_lora(p) else jax.lax.stop_gradient(x), params)


def _decay_mask(params: Params) -> Params:
    """No weight decay on norms / biases / scalars."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    vals = []
    for path, leaf in flat:
        name = str(path[-1]) if path else ""
        decay = (hasattr(leaf, "ndim") and leaf.ndim >= 2
                 and "scale" not in name and "bias" not in name)
        vals.append(decay)
    return jax.tree_util.tree_unflatten(treedef, vals)


def init_opt_state(cfg: OptimizerConfig, params: Params) -> OptState:
    mask = _trainable_mask(cfg, params)
    zeros = jax.tree_util.tree_map(
        lambda p, m: jnp.zeros_like(p, jnp.float32) if m else jnp.zeros((), jnp.float32),
        params, mask)
    master = None
    if cfg.master_fp32:
        # copy=True: fp32 params must not alias the master buffer (donation
        # of TrainState would otherwise donate the same buffer twice).
        master = jax.tree_util.tree_map(
            lambda p, m: (jnp.array(p, dtype=jnp.float32, copy=True)
                          if m else jnp.zeros((), jnp.float32)),
            params, mask)
    return OptState(step=jnp.zeros((), jnp.int32), mu=zeros,
                    nu=jax.tree_util.tree_map(jnp.copy, zeros), master=master)


def global_norm(tree: Params) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(sum(leaves))


def trainable_grad_norm(cfg: OptimizerConfig, grads: Params) -> jax.Array:
    """Global norm over the leaves ``cfg`` trains (frozen grads are zero
    by construction and are never read)."""
    mask = jax.tree_util.tree_leaves(_trainable_mask(cfg, grads))
    return global_norm([g for g, m in zip(jax.tree_util.tree_leaves(grads),
                                          mask) if m])


def adamw_update(cfg: OptimizerConfig, grads: Params, state: OptState,
                 params: Params, *, shard_specs=None):
    """Returns (new_params, new_state, stats).

    ``shard_specs`` (pytree of NamedSharding mirroring params): pins the
    freshly-updated bf16 params to the optimizer-shard layout BEFORE they
    are gathered back to the param layout — without it XLA all-gathers the
    fp32 master first and converts after (2x gather traffic and +12 GiB of
    fp32 gather buffers on minicpm-2b/dp)."""
    mask = _trainable_mask(cfg, params)
    dmask = _decay_mask(params)
    gnorm = trainable_grad_norm(cfg, grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) \
        if cfg.grad_clip > 0 else 1.0
    step = state.step + 1
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.betas
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)

    use_master = cfg.master_fp32
    specs = (shard_specs if shard_specs is not None
             else jax.tree_util.tree_map(lambda _: None, params))

    def upd(g, mu, nu, p, master, m, dm, spec):
        if not m:
            return p, mu, nu, master
        g = g.astype(jnp.float32) * clip
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        upd = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
        base = master if use_master else p.astype(jnp.float32)
        if dm:
            upd = upd + cfg.weight_decay * base
        new_master = base - lr * upd
        new_p = new_master.astype(p.dtype)
        if spec is not None and getattr(p, "ndim", 0):
            new_p = jax.lax.with_sharding_constraint(new_p, spec)
        return new_p, mu, nu, new_master

    masters = state.master if state.master is not None else params
    out = jax.tree_util.tree_map(upd, grads, state.mu, state.nu, params,
                                 masters, mask, dmask, specs,
                                 is_leaf=lambda x: x is None)
    new_params = jax.tree_util.tree_map(lambda t: t[0], out,
                                        is_leaf=lambda x: isinstance(x, tuple))
    new_mu = jax.tree_util.tree_map(lambda t: t[1], out,
                                    is_leaf=lambda x: isinstance(x, tuple))
    new_nu = jax.tree_util.tree_map(lambda t: t[2], out,
                                    is_leaf=lambda x: isinstance(x, tuple))
    new_master = None
    if cfg.master_fp32:
        new_master = jax.tree_util.tree_map(
            lambda t: t[3], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_params, OptState(step, new_mu, new_nu, new_master), \
        {"grad_norm": gnorm, "lr": lr}


# ---------------------------------------------------------------------------
# gradient compression (int8 error feedback) — optional DP-collective saver
# ---------------------------------------------------------------------------

def compress_int8(g: jax.Array):
    """Per-tensor symmetric int8 quantisation. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(g)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress_grads(grads: Params, error: Params):
    """Error-feedback compression (1-bit-Adam style, arXiv:2102.02888):
    quantise (g + e), carry the residual e' = (g + e) - dq(q). Cuts DP
    all-reduce bytes 4x; the residual keeps it unbiased over time."""
    def one(g, e):
        t = g.astype(jnp.float32) + e
        q, s = compress_int8(t)
        deq = decompress_int8(q, s)
        return deq, t - deq
    pairs = jax.tree_util.tree_map(one, grads, error)
    deq = jax.tree_util.tree_map(lambda t: t[0], pairs,
                                 is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree_util.tree_map(lambda t: t[1], pairs,
                                     is_leaf=lambda x: isinstance(x, tuple))
    return deq, new_err


__all__ = ["OptimizerConfig", "OptState", "init_opt_state", "adamw_update",
           "freeze_non_lora", "trainable_grad_norm",
           "schedule_lr", "global_norm", "compress_int8", "decompress_int8",
           "ef_compress_grads"]
