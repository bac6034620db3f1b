"""Gradient equivalence of the Pallas windowed-attention custom VJP.

Three layers of checks, all against ``attention_dense`` (the exact DTI
reference) with the kernel in interpret mode on CPU:

* kernel-level dq/dk/dv (+ dq_nope/dk_nope/dv0) over the DTI feature
  matrix: GQA head grouping, SUM isolation on/off, NoPE+ALiBi SUM rows,
  hidden-state reset, packed ``segment_ids``, key-padding;
* end-to-end ``jax.grad`` of the DTI CTR loss through the full
  transformer (GQA and MLA configs, packed and unpacked batches) with
  ``attn_impl="pallas"`` vs ``attn_impl="dense"``;
* leakage-under-grad: gradients of one packed segment's loss w.r.t.
  another segment's attention inputs are *exactly* zero on the dense,
  blocked and Pallas paths (deterministic case + hypothesis sweep over
  random segment layouts).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro.core.dti import build_streaming_prompts, pack_prompts
from repro.core.windowed import (ResetConfig, attention_blocked,
                                 attention_dense)
from repro.kernels.windowed_attn.ops import windowed_attention
from repro.kernels.windowed_attn.windowed_attn import (Band, band_steps,
                                                       choose_block,
                                                       n_kv_blocks)
from repro.launch.train import make_lm_loss_fn
from repro.models.layers import alibi_slopes
from repro.models.transformer import ModelConfig, init_params
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, init_train_state, make_train_step

KEY = jax.random.PRNGKey(11)
TOL = 1e-4          # acceptance bound: max-abs error vs the dense reference
FWD_TOL = 2e-5      # the forward's bound (tests/test_kernels.py, fp32)


def _rand(shape, i, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, dtype)


def _tree_max_err(a, b):
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(jnp.abs(x.astype(jnp.float32)
                                   - y.astype(jnp.float32)).max()), a, b)
    return max(jax.tree_util.tree_leaves(diffs))


# ---------------------------------------------------------------------------
# kernel-level dq/dk/dv equivalence
# ---------------------------------------------------------------------------

# SUM-row layouts by case name (default: 15 % of rows at random). ``sums``
# lists each batch row's SUM positions; ``packed`` gives segment lengths
# (positions restart per segment, SUMs on each segment's tail); ``dv`` a
# value dim other than D. Blocks of 256 rows hold two 128-row sub-tiles.
_LAYOUTS = {
    "sums_none": dict(sums=[[]]),
    "sums_one_tile": dict(sums=[[261, 300], [10, 100]]),
    "sums_every_tile": dict(sums=[[64, 200, 320, 450], [0, 255, 256, 511]]),
    "sums_tile_edge": dict(sums=[[127, 128]]),
    "sums_packed": dict(packed=(150, 200, 162)),
    "nope_only_tiles": dict(sums=[[261, 300]]),
    "reset_only_tiles": dict(sums=[[261, 300]]),
    "mla_one_kn": dict(dv=8),
    "ragged_sub_tile": dict(sums=[[140, 180]]),     # sub-tiles 128 + 64
    "small_blk_sparse": dict(sums=[[70, 75]]),      # blk 32 < 128
}


def _sum_layout(name, r, B, S):
    """-> (is_sum, pos, seg or None) of the case's SUM-row layout."""
    lay = _LAYOUTS.get(name, {})
    pos = np.broadcast_to(np.arange(S), (B, S))
    seg = None
    if "sums" in lay:
        is_sum = np.zeros((B, S), bool)
        for b, rows in enumerate(lay["sums"]):
            is_sum[b, rows] = True
    elif "packed" in lay:
        lens = lay["packed"]
        seg = np.repeat(np.arange(len(lens)), lens)[None].repeat(B, 0)
        pos = np.concatenate([np.arange(n) for n in lens])[None].repeat(B, 0)
        is_sum = np.zeros((B, S), bool)
        for end in np.cumsum(lens):
            is_sum[:, end - 1 - 3 * np.arange(6)] = True
    else:
        is_sum = r.random((B, S)) < 0.15
    return (jnp.asarray(is_sum), jnp.asarray(pos, jnp.int32),
            None if seg is None else jnp.asarray(seg, jnp.int32))


class TestKernelGrads:
    @pytest.mark.parametrize("name,B,S,H,Hk,D,W,blk,sum_iso,nope,res", [
        ("gqa_full",    2, 128, 4, 2, 16, 32, 32, True,  True,  True),
        ("mla_heads",   1, 128, 4, 4, 16, 32, 32, True,  True,  True),
        ("no_iso",      1,  64, 2, 1,  8, 16, 16, False, True,  True),
        ("no_nope",     1,  64, 2, 2,  8, 16, 16, True,  False, False),
        ("no_reset",    1,  64, 4, 2,  8, 16, 16, True,  True,  False),
        ("reset_only",  1,  64, 2, 2,  8, 16, 16, True,  False, True),
        ("odd_window",  1,  96, 2, 2,  8, 24, 32, True,  True,  True),
        # band over the whole causal triangle, window not block-aligned:
        # n_kv == n_q == 3, dead steps at the start of the fwd and dq walks
        # and at the end of the dk/dv walk (the benchmark cell's schedule)
        ("cell_band",   1,  96, 2, 2,  8, 61, 32, True,  True,  True),
        # SUM gate sub-tiles (_LAYOUTS): which 128-row query sub-tiles of
        # a block run the NoPE and reset streams
        ("sums_none",        1, 512, 2, 1,  8, 200, 256, True, True,  True),
        ("sums_one_tile",    2, 512, 2, 1,  8, 200, 256, True, True,  True),
        ("sums_every_tile",  2, 512, 2, 1,  8, 200, 256, True, True,  True),
        ("sums_tile_edge",   1, 512, 2, 1,  8, 200, 256, True, True,  True),
        ("sums_packed",      1, 512, 2, 1,  8, 200, 256, True, True,  True),
        ("nope_only_tiles",  1, 512, 2, 1,  8, 200, 256, True, True,  False),
        ("reset_only_tiles", 1, 512, 2, 1,  8, 200, 256, True, False, True),
        ("mla_one_kn",       1, 512, 2, 1, 16, 200, 256, True, True,  True),
        ("ragged_sub_tile",  1, 384, 2, 1,  8, 200, 192, True, True,  True),
        ("small_blk_sparse", 1, 128, 2, 1,  8,  32,  32, True, True,  True),
    ])
    def test_dqkv_match_dense(self, name, B, S, H, Hk, D, W, blk,
                              sum_iso, nope, res):
        """Forward output and all six gradients against the dense path."""
        r = np.random.default_rng(len(name))
        dv = _LAYOUTS.get(name, {}).get("dv", D)
        q, qn = _rand((B, S, H, D), 0), _rand((B, S, H, D), 3)
        k, kn = _rand((B, S, Hk, D), 1), _rand((B, S, Hk, D), 4)
        v, v0 = _rand((B, S, Hk, dv), 2), _rand((B, S, Hk, dv), 5)
        w = _rand((B, S, H, dv), 9)
        is_sum, pos, seg = _sum_layout(name, r, B, S)
        valid = jnp.asarray(r.random((B, S)) < 0.9)
        kw = dict(pos_q=pos, pos_k=pos, window=W, is_sum_q=is_sum,
                  is_sum_k=is_sum, valid_k=valid, sum_isolated=sum_iso)
        if seg is not None:
            kw.update(seg_q=seg, seg_k=seg)
        if nope:
            kw.update(q_nope=qn, k_nope=kn, alibi=alibi_slopes(H))
        if res:
            kw.update(v0=v0, reset=ResetConfig(0.05, 0.3, W / 2))

        pallas = lambda *a, **kk: windowed_attention(*a, **kk,
                                                     block_size=blk)
        np.testing.assert_allclose(
            np.asarray(attention_dense(q, k, v, **kw)),
            np.asarray(pallas(q, k, v, **kw)), atol=FWD_TOL, rtol=FWD_TOL)

        def loss(fn, extra=()):
            def f(q, k, v, *rest):
                kw2 = dict(kw)
                for key, val in zip(extra, rest):
                    kw2[key] = val
                return (fn(q, k, v, **kw2) * w).sum()
            return f

        extra = (("q_nope", "k_nope") if nope else ()) + \
                (("v0",) if res else ())
        rest = tuple({"q_nope": qn, "k_nope": kn, "v0": v0}[e] for e in extra)
        argn = tuple(range(3 + len(rest)))
        g_ref = jax.grad(loss(attention_dense, extra), argn)(q, k, v, *rest)
        g_pl = jax.grad(loss(pallas, extra), argn)(q, k, v, *rest)
        for nm, a, b in zip(("dq", "dk", "dv") + extra, g_ref, g_pl):
            err = float(jnp.abs(a - b).max())
            assert err <= TOL, f"{name}/{nm}: {err}"

    def test_packed_segments_grads(self):
        B, H, D, W, blk = 1, 2, 8, 8, 16
        lens = [16, 16, 16, 16]
        S = sum(lens)
        seg = jnp.asarray(np.repeat(np.arange(len(lens)), lens)[None],
                          jnp.int32)
        pos = jnp.asarray(np.concatenate([np.arange(n) for n in lens])[None],
                          jnp.int32)
        q, k, v = (_rand((B, S, H, D), i) for i in range(3))
        w = _rand((B, S, H, D), 9)
        kw = dict(pos_q=pos, pos_k=pos, window=W, seg_q=seg, seg_k=seg)
        g_ref = jax.grad(lambda *a: (attention_dense(*a, **kw) * w).sum(),
                         (0, 1, 2))(q, k, v)
        g_pl = jax.grad(lambda *a: (windowed_attention(
            *a, **kw, block_size=blk) * w).sum(), (0, 1, 2))(q, k, v)
        assert _tree_max_err(g_ref, g_pl) <= TOL

    def test_mla_value_dim(self):
        """Dv != Dqk (MLA heads): fwd and grads on the split value dim."""
        B, S, H, D, DV, W, blk = 1, 64, 2, 16, 8, 16, 16
        q = _rand((B, S, H, D), 0)
        k = _rand((B, S, H, D), 1)
        v = _rand((B, S, H, DV), 2)
        w = _rand((B, S, H, DV), 9)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        kw = dict(pos_q=pos, pos_k=pos, window=W)
        o_ref = attention_dense(q, k, v, **kw)
        o_pl = windowed_attention(q, k, v, **kw, block_size=blk)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pl),
                                   atol=TOL, rtol=TOL)
        g_ref = jax.grad(lambda *a: (attention_dense(*a, **kw) * w).sum(),
                         (0, 1, 2))(q, k, v)
        g_pl = jax.grad(lambda *a: (windowed_attention(
            *a, **kw, block_size=blk) * w).sum(), (0, 1, 2))(q, k, v)
        assert _tree_max_err(g_ref, g_pl) <= TOL

    def test_bf16_grads_finite_and_close(self):
        B, S, H, D, W = 1, 64, 2, 16, 16
        q, k, v = (_rand((B, S, H, D), i, jnp.bfloat16) for i in range(3))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        kw = dict(pos_q=pos, pos_k=pos, window=W)
        f = lambda fn: lambda q: fn(q, k, v, **kw).astype(jnp.float32).sum()
        g_ref = jax.grad(f(attention_dense))(q)
        g_pl = jax.grad(f(lambda *a, **kk: windowed_attention(
            *a, **kk, block_size=16)))(q)
        assert bool(jnp.isfinite(g_pl.astype(jnp.float32)).all())
        np.testing.assert_allclose(np.asarray(g_ref, np.float32),
                                   np.asarray(g_pl, np.float32),
                                   atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# end-to-end: jax.grad of the DTI CTR loss through the transformer
# ---------------------------------------------------------------------------

MAX_LEN = 64


def _gqa_cfg(impl):
    return ModelConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                       d_ff=64, vocab_size=64, window=16, attn_impl=impl,
                       attn_block_size=16, dti_sum_token=True, remat=False)


def _mla_cfg(impl):
    return ModelConfig(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=64, window=16, attn_type="mla",
                       q_lora_rank=0, kv_lora_rank=16, qk_nope_dim=8,
                       qk_rope_dim=8, v_head_dim=8, attn_impl=impl,
                       attn_block_size=16, dti_sum_token=True, remat=False)


def _batch(packed=False, n_users=3):
    prompts = []
    for s in range(n_users):
        r = np.random.default_rng(s)
        toks = [list(map(int, r.integers(8, 60, size=int(r.integers(2, 4)))))
                for _ in range(8)]
        labels = list(map(int, r.integers(0, 2, size=8)))
        prompts += build_streaming_prompts(toks, labels, n_ctx=2, k=3,
                                           max_len=MAX_LEN)
    if packed:
        prompts = pack_prompts(prompts, MAX_LEN)
    return {key: jnp.asarray(np.stack([p[key] for p in prompts]))
            for key in prompts[0]}


class TestEndToEndGrads:
    @pytest.mark.parametrize("make_cfg,packed", [
        (_gqa_cfg, False), (_gqa_cfg, True), (_mla_cfg, False),
    ])
    def test_loss_grads_match_dense(self, make_cfg, packed):
        batch = _batch(packed=packed)
        grads = {}
        for impl in ("dense", "pallas"):
            cfg = make_cfg(impl)
            params = init_params(jax.random.PRNGKey(0), cfg)
            loss_fn = make_lm_loss_fn(cfg, cfg.window)
            loss, _ = loss_fn(params, batch, jax.random.PRNGKey(0))
            grads[impl] = jax.grad(
                lambda p: loss_fn(p, batch, jax.random.PRNGKey(0))[0])(params)
            assert np.isfinite(float(loss))
        err = _tree_max_err(grads["dense"], grads["pallas"])
        assert err <= TOL, f"param-grad mismatch {err}"


@pytest.mark.parametrize("impl", ["pallas", "dense"])
def test_trainer_band_gauges(impl):
    """A Pallas LM's train step sets the trainer's winattn.* gauges to
    ``band_steps`` at the batch's row length; a dense one sets none."""
    cfg = _gqa_cfg(impl)
    ocfg = OptimizerConfig(lr=1e-3)
    step = make_train_step(make_lm_loss_fn(cfg, cfg.window), ocfg)
    trainer = Trainer(step, init_train_state(
        init_params(jax.random.PRNGKey(0), cfg), ocfg), log_every=100)
    trainer.run(iter([_batch()]), n_steps=1)
    snap = trainer.metrics.snapshot("winattn.")
    if impl == "dense":
        assert snap == {}
        return
    n_q = MAX_LEN // cfg.attn_block_size
    assert (snap["winattn.grid_steps"]["value"],
            snap["winattn.live_steps"]["value"]) == band_steps(
                cfg.window, cfg.attn_block_size, n_q) == (8, 7)


def _brute_tile_steps(is_sum, window, blk, sum_stream):
    """Live (q sub-tile, kv step) pairs that run the SUM stream, and all
    live pairs, of a (B, S) batch, walked one by one."""
    b, s = is_sum.shape
    n_q = -(-s // blk)
    n_kv = n_kv_blocks(window, blk, n_q)
    sub = min(128, blk)
    n_sum = n_all = 0
    for r in range(b):
        for i in range(n_q):
            for lo in range(0, blk, sub):
                held = is_sum[r, i * blk + lo:i * blk + min(lo + sub, blk)]
                for ikv in range(n_kv):
                    if i - (n_kv - 1) + ikv >= 0:
                        n_all += 1
                        n_sum += int(sum_stream and held.any())
    return n_sum, n_all


@pytest.mark.parametrize("S,blk,W,rate,sum_stream", [
    (1536, 512, 977, 0.01, True),       # the benchmark cell's geometry
    (1536, 512, 977, 0.01, False),      # no NoPE or reset: no SUM stream
    (700, 512, 300, 0.02, True),        # padded tail
    (384, 192, 200, 0.02, True),        # sub-tiles of 128 and 64 rows
    (128, 32, 32, 0.05, True),          # blk < 128
])
def test_band_tile_steps_match_brute_force(S, blk, W, rate, sum_stream):
    is_sum = np.random.default_rng(S).random((5, S)) < rate
    blk, s_pad = choose_block(S, blk)
    band = Band(W, blk, s_pad // blk, min(128, blk), sum_stream)
    assert band.tile_steps(is_sum) == _brute_tile_steps(is_sum, W, blk,
                                                        sum_stream)


@pytest.mark.parametrize("sum_token", [True, False])
def test_trainer_tile_counters(sum_token):
    """The trainer counts, per batch, the live (sub-tile, kv step) pairs
    that run the SUM stream and all live pairs; with DTI off, none run it."""
    cfg = dataclasses.replace(_gqa_cfg("pallas"), dti_sum_token=sum_token)
    ocfg = OptimizerConfig(lr=1e-3)
    step = make_train_step(make_lm_loss_fn(cfg, cfg.window), ocfg)
    trainer = Trainer(step, init_train_state(
        init_params(jax.random.PRNGKey(0), cfg), ocfg), log_every=100)
    batches = [_batch(), _batch(packed=True)]
    trainer.run(iter([{k: np.asarray(x) for k, x in b.items()}
                      for b in batches]), n_steps=2)
    snap = trainer.metrics.snapshot("winattn.")
    want = np.sum([_brute_tile_steps(np.asarray(b["is_sum"]), cfg.window,
                                     cfg.attn_block_size, sum_token)
                   for b in batches], axis=0)
    got = (snap["winattn.sum_tile_steps"]["value"],
           snap["winattn.tile_steps"]["value"])
    assert got == tuple(want)
    assert (want[0] > 0) == sum_token


# ---------------------------------------------------------------------------
# leakage under grad: packed segments stay isolated in the backward pass
# ---------------------------------------------------------------------------

def _leakage_case(lens, window, seed, with_sum, target_seg):
    """Grads of segment ``target_seg``'s output w.r.t. q/k/v must be
    *exactly* zero at every other segment's positions, on all paths."""
    B, H, D = 1, 2, 8
    blk = 8
    S = ((sum(lens) + blk - 1) // blk) * blk
    n_pad = S - sum(lens)
    seg = np.concatenate([np.repeat(np.arange(len(lens)), lens),
                          np.full(n_pad, -1)])
    pos = np.concatenate([np.concatenate([np.arange(n) for n in lens]),
                          np.zeros(n_pad, np.int64)])
    valid = seg >= 0
    r = np.random.default_rng(seed)
    is_sum = (r.random(S) < 0.25) & valid if with_sum else np.zeros(S, bool)
    seg_j = jnp.asarray(seg[None], jnp.int32)
    pos_j = jnp.asarray(pos[None], jnp.int32)
    q, k, v = (_rand((B, S, H, D), i + seed) for i in range(3))
    qn, kn, v0 = (_rand((B, S, H, D), i + seed + 5) for i in range(3))
    kw = dict(pos_q=pos_j, pos_k=pos_j, window=window, seg_q=seg_j,
              seg_k=seg_j, valid_k=jnp.asarray(valid[None]))
    if with_sum:
        kw.update(is_sum_q=jnp.asarray(is_sum[None]),
                  is_sum_k=jnp.asarray(is_sum[None]), q_nope=qn, k_nope=kn,
                  alibi=alibi_slopes(H), v0=v0,
                  reset=ResetConfig(0.05, 0.3, window / 2))
    sel = jnp.asarray((seg == target_seg)[None, :, None, None])
    others = (seg != target_seg) & valid

    impls = {
        "dense": lambda *a: attention_dense(*a, **kw),
        "blocked": lambda *a: attention_blocked(*a, **kw),
        "pallas": lambda *a: windowed_attention(*a, **kw, block_size=blk),
    }
    for name, fn in impls.items():
        gq, gk, gv = jax.grad(
            lambda q, k, v: jnp.sum(jnp.where(sel, fn(q, k, v), 0.0)),
            (0, 1, 2))(q, k, v)
        for gname, g in (("dq", gq), ("dk", gk), ("dv", gv)):
            leak = float(jnp.abs(g[0, others]).max())
            assert leak == 0.0, f"{name}/{gname} leaks {leak}"


class TestLeakageUnderGrad:
    def test_deterministic_layout(self):
        _leakage_case([12, 9, 7], window=8, seed=0, with_sum=True,
                      target_seg=1)
        _leakage_case([5, 17], window=4, seed=1, with_sum=False,
                      target_seg=0)

    @pytest.mark.hyp
    @settings(max_examples=8, deadline=None)
    @given(st.lists(st.integers(min_value=2, max_value=12), min_size=2,
                    max_size=4),
           st.sampled_from([1, 2, 4, 8]),   # divides padded S (blocked path)
           st.integers(min_value=0, max_value=10 ** 6),
           st.booleans())
    def test_random_layouts(self, lens, window, seed, with_sum):
        _leakage_case(lens, window=window, seed=seed, with_sum=with_sum,
                      target_seg=seed % len(lens))
