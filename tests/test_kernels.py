"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import reference_embedding_bag
from repro.kernels.windowed_attn.ops import windowed_attention
from repro.kernels.windowed_attn.ref import reference_attention
from repro.kernels.windowed_attn.windowed_attn import (band_steps,
                                                       choose_block,
                                                       n_kv_blocks,
                                                       prepare_inputs)
from repro.core.windowed import ResetConfig
from repro.models.layers import alibi_slopes

KEY = jax.random.PRNGKey(7)


class TestWindowedAttnKernel:
    @pytest.mark.parametrize("B,S,H,Hk,D,W,blk", [
        (1, 128, 2, 1, 8, 32, 32),
        (2, 256, 4, 2, 16, 64, 64),
        (2, 256, 4, 4, 32, 128, 64),
        (1, 512, 8, 2, 64, 128, 128),
        (3, 192, 6, 3, 16, 64, 64),     # non-pow2 batch/heads
        (1, 96, 2, 2, 8, 61, 32),       # n_kv == n_q == 3, dead steps
        (2, 512, 2, 1, 16, 200, 256),   # two SUM gate sub-tiles a block
        (1, 384, 2, 2, 8, 200, 192),    # sub-tiles of 128 and 64 rows
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, S, H, Hk, D, W, blk, dtype):
        r = np.random.default_rng(B * S + H)
        def rand(shape, i):
            return jax.random.normal(jax.random.fold_in(KEY, i), shape,
                                     dtype)
        q, qn = rand((B, S, H, D), 0), rand((B, S, H, D), 3)
        k, kn = rand((B, S, Hk, D), 1), rand((B, S, Hk, D), 4)
        v, v0 = rand((B, S, Hk, D), 2), rand((B, S, Hk, D), 5)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        is_sum = jnp.asarray(r.random((B, S)) < 0.1)
        valid = jnp.asarray(r.random((B, S)) < 0.9)
        kw = dict(pos_q=pos, pos_k=pos, window=W, is_sum_q=is_sum,
                  is_sum_k=is_sum, valid_k=valid, q_nope=qn, k_nope=kn,
                  alibi=alibi_slopes(H), v0=v0,
                  reset=ResetConfig(0.05, 0.3, W / 2))
        o_ref = reference_attention(q, k, v, **kw).astype(jnp.float32)
        o_pl = windowed_attention(q, k, v, **kw,
                                  block_size=blk).astype(jnp.float32)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(o_ref, o_pl, atol=tol, rtol=tol)

    @pytest.mark.parametrize("S,blk,W,counts", [
        (1536, 512, 977, (9, 6)),       # the benchmark cell's geometry
        (96, 32, 61, (9, 6)),
        (256, 32, 64, (24, 21)),        # block-aligned window
        (2048, 256, 977, (40, 30)),
        (64, 64, 16, (1, 1)),           # one block: no dead step
    ])
    def test_band_steps_counts_live_steps(self, S, blk, W, counts):
        blk, s_pad = choose_block(S, blk)
        n_q = s_pad // blk
        n_kv = n_kv_blocks(W, blk, n_q)
        fwd = sum(iq - (n_kv - 1) + ikv >= 0
                  for iq in range(n_q) for ikv in range(n_kv))
        dkv = sum(j + ib <= n_q - 1 for j in range(n_q) for ib in range(n_kv))
        assert band_steps(W, blk, n_q) == (n_q * n_kv, fwd) == counts
        assert dkv == fwd

    @pytest.mark.parametrize("S,block_size,rate", [
        (1536, 512, 0.01),              # the cell's block: 4 sub-tiles
        (1536, 512, 0.0),               # no SUM: every word 0
        (700, 512, 0.02),               # padded tail
        (384, 192, 0.02),               # sub-tiles of 128 and 64 rows
        (128, 32, 0.05),                # blk < 128: one sub-tile a block
    ])
    def test_gate_bits_match_brute_force(self, S, block_size, rate):
        """Bit t of word (b, i) is set iff sub-tile t of q block i of row b
        holds a SUM query; no word is built without NoPE and reset."""
        B, H, D = 3, 2, 8
        r = np.random.default_rng(S + block_size)
        is_sum = r.random((B, S)) < rate
        x = jnp.zeros((B, H, S, D))
        pos = jnp.zeros((B, S), jnp.int32)
        common = dict(window=64, sum_q=jnp.asarray(is_sum), sum_k=None,
                      valid_k=None, seg_q=None, seg_k=None, alibi=None,
                      sum_isolated=True, scale=None, block_size=block_size,
                      interpret=True)
        st, arrays = prepare_inputs(x, x, x, pos, pos, q_nope=x, k_nope=x,
                                    v0=None, reset=None, **common)
        blk, n_q = st.block, arrays[0].shape[2] // st.block
        sub = min(128, blk)
        want = np.zeros((B, n_q), np.int64)
        for b in range(B):
            for i in range(n_q):
                for t in range(-(-blk // sub)):
                    lo = i * blk + t * sub
                    if is_sum[b, lo:min(lo + sub, (i + 1) * blk)].any():
                        want[b, i] |= 1 << t
        np.testing.assert_array_equal(np.asarray(arrays[-1]),
                                      want.reshape(-1))
        _, arrays = prepare_inputs(x, x, x, pos, pos, q_nope=None,
                                   k_nope=None, v0=None, reset=None,
                                   **common)
        assert arrays[-1] is None

    def test_jit_and_grad_through_kernel(self):
        B, S, H, D, W = 1, 128, 2, 16, 32
        q = jax.random.normal(KEY, (B, S, H, D))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, D))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, D))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

        @jax.jit
        def f(q):
            return windowed_attention(q, k, v, pos_q=pos, pos_k=pos,
                                      window=W, block_size=32).sum()
        v1 = f(q)
        assert np.isfinite(float(v1))


class TestEmbeddingBagKernel:
    @pytest.mark.parametrize("V,D,B,H", [
        (64, 8, 4, 3), (512, 32, 16, 8), (1000, 128, 8, 20), (37, 16, 5, 7),
    ])
    @pytest.mark.parametrize("mode", ["sum", "mean"])
    def test_sweep(self, V, D, B, H, mode, rng):
        table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, V, (B, H)), jnp.int32)
        valid = jnp.asarray(rng.random((B, H)) < 0.8)
        o_ref = reference_embedding_bag(table, ids, valid, mode=mode)
        o_pl = embedding_bag(table, ids, valid, mode=mode)
        np.testing.assert_allclose(o_ref, o_pl, atol=1e-5, rtol=1e-5)

    def test_weights(self, rng):
        table = jnp.asarray(rng.normal(size=(100, 16)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 100, (8, 5)), jnp.int32)
        w = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
        o_ref = reference_embedding_bag(table, ids, None, mode="sum",
                                        weights=w)
        o_pl = embedding_bag(table, ids, None, mode="sum", weights=w)
        np.testing.assert_allclose(o_ref, o_pl, atol=1e-5, rtol=1e-5)

    def test_bf16_table(self, rng):
        table = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
        ids = jnp.asarray(rng.integers(0, 64, (4, 6)), jnp.int32)
        o_ref = reference_embedding_bag(table, ids, None).astype(jnp.float32)
        o_pl = embedding_bag(table, ids, None).astype(jnp.float32)
        np.testing.assert_allclose(o_ref, o_pl, atol=2e-2, rtol=2e-2)

    def test_all_invalid_bag_is_zero(self, rng):
        table = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 10, (2, 4)), jnp.int32)
        valid = jnp.zeros((2, 4), bool)
        np.testing.assert_allclose(embedding_bag(table, ids, valid), 0.0)

    @pytest.mark.parametrize("mode", ["sum", "mean"])
    def test_int8_table_scale_fold_is_exact(self, mode, rng):
        """A per-row-quantized table folds its scales into the gather
        weights *exactly* (the bag is a weighted sum), so the int8 path
        must match the reference bag over the dequantized table to fp32
        reduction noise — no quantization tolerance in sight."""
        from repro.core.quant import dequantize_q8, quantize_q8
        V, D, B, H = 200, 16, 8, 6
        table = jnp.asarray(rng.normal(0, 3.0, (V, D)), jnp.float32)
        codes, scale = quantize_q8(table)          # per-row scales (V,)
        ids = jnp.asarray(rng.integers(0, V, (B, H)), jnp.int32)
        valid = jnp.asarray(rng.random((B, H)) < 0.8)
        o_ref = reference_embedding_bag(dequantize_q8(codes, scale),
                                        ids, valid, mode=mode)
        o_q = embedding_bag(codes, ids, valid, mode=mode,
                            table_scale=scale)
        assert o_q.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_q),
                                   atol=1e-5, rtol=1e-5)
