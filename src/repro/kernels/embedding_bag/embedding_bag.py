"""Pallas TPU kernel: EmbeddingBag (gather + weighted segment-reduce).

JAX has no nn.EmbeddingBag; the jnp path (repro.sparse.embedding) lowers to
take + sum. This kernel implements the op the TPU-native way: the bag ids
are **scalar-prefetched** into SMEM so each grid step's BlockSpec index_map
can address the embedding-table row *directly in HBM* — the row DMA
HBM->VMEM is the gather, no (B, H, D) intermediate ever exists.

  grid = (B * H,)   (bag-major; "arbitrary" — out block revisited H times)
  table BlockSpec (1, D): index_map i -> (ids[i], 0)   <- the gather
  out   BlockSpec (1, D): index_map i -> (i // H, 0)   <- the reduce

Weights (per-sample scale, or validity 0/1) ride SMEM alongside the ids.
Modes: sum / mean (mean = sum with 1/n weights, done in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, w_ref, table_ref, o_ref, *, bag: int):
    i = pl.program_id(0)

    @pl.when(i % bag == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[i]
    o_ref[...] += table_ref[...].astype(jnp.float32) * w


def embedding_bag_pallas(table: jax.Array,      # (V, D)
                         ids: jax.Array,        # (B, H) int32
                         weights: jax.Array,    # (B, H) f32 (0 masks)
                         *, interpret: bool = False) -> jax.Array:
    b, bag = ids.shape
    v, d = table.shape
    grid = (b * bag,)
    out = pl.pallas_call(
        functools.partial(_kernel, bag=bag),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, d),
                             lambda i, ids, w: (ids[i], 0)),   # table row
            ],
            out_specs=pl.BlockSpec((1, d), lambda i, ids, w: (i // bag, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="embedding_bag",
    )(ids.reshape(-1).astype(jnp.int32),
      weights.reshape(-1).astype(jnp.float32), table)
    return out


__all__ = ["embedding_bag_pallas"]
