"""Random weights for a configuration, made from the seed on the device in
one jitted call, in the parameter layout the program takes.

The layout (leaf names and shapes) is read from the program with
``jax.eval_shape``, which computes nothing; every value is drawn here. The
reference reads the same arrays, so neither side holds weights the other
made. Values by leaf name:

* ``embed``            N(0, 0.02)
* ``w``                N(0, 1/fan_in)
* ``b`` (QKV bias)     N(0, 0.02)
* ``scale`` (norms)    1 + N(0, 0.1)
* ``lora_a``           N(0, 1/fan_in)
* ``lora_b``           N(0, 0.02): an adapter mid-way through training, so
  both factors get a gradient on the first step (a zero B, as at the start
  of fine-tuning, leaves every A with a zero gradient)
* ``lora_scale``       alpha / rank = 16 / r
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def layout(cfg):
    """ShapeDtypeStructs of the program's parameters for ``cfg``."""
    from repro.models.transformer import init_params
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def _leaf(key, name: str, sds, lora_rank: int):
    last = name.rsplit("/", 1)[-1]
    shape, dtype = sds.shape, sds.dtype
    n = lambda s: s * jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if last == "embed":
        x = n(0.02)
    elif last in ("w", "lora_a"):
        x = n(1.0) * jax.lax.rsqrt(jnp.float32(shape[-2]))
    elif last in ("b", "lora_b"):
        x = n(0.02)
    elif last == "scale":
        x = 1.0 + n(0.1)
    elif last == "lora_scale":
        x = jnp.full(shape, 16.0 / lora_rank, jnp.float32)
    else:
        raise KeyError(f"no rule for parameter {name!r}")
    return x.astype(dtype)


def make_params(cfg, seed: int):
    """The weights for ``seed``: one jitted call on the default device."""
    shapes = layout(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]

    def build(root):
        leaves = [_leaf(jax.random.fold_in(root, zlib.crc32(nm.encode())),
                        nm, sds, cfg.lora_rank)
                  for nm, (_, sds) in zip(names, flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    seed = abs(int(seed))
    root = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(build)(root)
