"""Int8 quantized KV cache: the quantization-error test harness that
gates the tentpole.

Layers of guarantee, weakest math to strongest system property:

1. ``quantize_q8``/``dequantize_q8`` round-trip error is bounded by half
   a quantization step (scale/2) per element — the symmetric-int8
   contract every downstream tolerance derives from.
2. RoPE commutes with the per-slot scale (rotation never crosses a
   scale group), which is what lets the kernel rope raw codes and
   multiply the scale afterwards.
3. The Pallas kernel's in-VMEM dequant + read-time rope matches the
   dense reference bit-for-bit-ish (fp32 softmax noise only) on raw
   codes, for both the GQA layout (one scale group) and the absorbed-MLA
   layout (two groups split at ``rope_start``).
4. Scale invariance under paging: scales ride the same slot axis as the
   codes, so page adoption, steals and evictions move both together with
   zero requantization — int8 paged-under-pressure scores are *float
   exact* against int8 contiguous, and a cross-row adopted prefix
   reproduces its original scores exactly.
5. End-to-end tolerance: int8 decode sits within a documented bound of
   the fp32 scores on every cell of the GQA/MLA x dense/pallas x
   contiguous/paged/pool-pressure matrix (~1e-3 observed at this scale;
   the 2e-2 gate catches a broken dequant path, not noise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.configs import get_arch
from repro.core.quant import Q8_MAX, dequantize_q8, quantize_q8
from repro.data.requests import make_request_stream
from repro.data.synthetic import make_ctr_dataset
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.models.layers import apply_rope
from repro.models.transformer import init_params
from repro.serve.cache import (cache_shape, init_lm_cache, is_quantized,
                               kv_token_bytes)
from repro.serve.scheduler import ServeScheduler

from test_serve import _cfg

# Documented end-to-end tolerance for int8 KV vs fp32 scores on the
# smoke-scale configs below. Observed |dp| is ~1e-3; anything near the
# gate means a dequant/scale-plumbing bug, not quantization noise.
INT8_SCORE_TOL = 2e-2


# ---------------------------------------------------------------------------
# 1. the quantizer's error bound
# ---------------------------------------------------------------------------

def test_dequant_error_bound(rng):
    """|x - dq(q(x))| <= scale/2 per element, across magnitudes."""
    for mag in (1e-3, 1.0, 37.5, 1e4):
        x = jnp.asarray(rng.normal(0, mag, (5, 7, 16)), jnp.float32)
        q, scale = quantize_q8(x)
        assert q.dtype == jnp.int8
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= int(Q8_MAX)
        err = jnp.abs(x - dequantize_q8(q, scale))
        bound = scale[..., None] / 2 + 1e-6 * mag
        assert bool(jnp.all(err <= bound))


def test_zero_groups_are_safe():
    """An all-zero scale group must not divide by zero: codes come back
    zero and dequantize to finite zeros."""
    x = jnp.zeros((2, 3, 8), jnp.float32)
    q, scale = quantize_q8(x)
    assert bool(jnp.all(q == 0))
    out = dequantize_q8(q, scale)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(out == 0))
    # mixed: one live group next to a dead one
    x = x.at[0, 0].set(jnp.arange(8, dtype=jnp.float32))
    q, scale = quantize_q8(x)
    assert bool(jnp.all(jnp.isfinite(dequantize_q8(q, scale))))


@pytest.mark.hyp
@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                min_size=1, max_size=64))
def test_roundtrip_error_bound_property(xs):
    x = jnp.asarray(xs, jnp.float32)
    q, scale = quantize_q8(x)
    err = jnp.abs(x - dequantize_q8(q, scale))
    assert bool(jnp.all(err <= scale / 2 + 1e-3))


# ---------------------------------------------------------------------------
# 2. RoPE commutes with the scale (the kernel's rope-codes-then-scale)
# ---------------------------------------------------------------------------

def test_rope_commutes_with_per_slot_scale(rng):
    """Rotation mixes dims only *within* one (slot, head) scale group, so
    rope(codes) * scale == rope(codes * scale) — the identity the kernel
    exploits to dequantize after roping raw codes."""
    B, cap, Hk, D = 2, 9, 2, 16
    x = jnp.asarray(rng.normal(0, 2.0, (B, cap, Hk, D)), jnp.float32)
    q, scale = quantize_q8(x)
    pos = jnp.asarray(rng.integers(0, 50, (B, cap)), jnp.int32)
    scale_first = apply_rope(dequantize_q8(q, scale), pos)
    scale_after = apply_rope(q.astype(jnp.float32), pos) * scale[..., None]
    np.testing.assert_allclose(np.asarray(scale_first),
                               np.asarray(scale_after), atol=1e-5)


# ---------------------------------------------------------------------------
# 3. kernel == dense reference on raw int8 codes
# ---------------------------------------------------------------------------

def _quant_operands(rng, *, hk, d, dv, rope_start):
    """Build a quantized decode problem: fp32 truth -> codes + scales in
    the cache layout (G=1 whole-key scales, or G=2 split at rope_start)."""
    B, s, H, cap = 2, 3, 4, 40
    kf = jnp.asarray(rng.normal(0, 1.5, (B, cap, hk, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(0, 1.5, (B, cap, hk, dv)), jnp.float32)
    if rope_start:
        c_q, c_s = quantize_q8(kf[..., :rope_start])
        p_q, p_s = quantize_q8(kf[..., rope_start:])
        k = jnp.concatenate([c_q, p_q], axis=-1)
        k_scale = jnp.stack([c_s, p_s], axis=-1)        # (B, cap, hk, 2)
    else:
        k, k_s = quantize_q8(kf)
        k_scale = k_s[..., None]                        # (B, cap, hk, 1)
    v, v_scale = quantize_q8(vf)
    pos_k = np.broadcast_to(np.arange(cap, dtype=np.int32), (B, cap)).copy()
    pos_k[:, 33:] = -1                                  # empty tail slots
    pos_k[1, 7] = -1                                    # and a hole
    pos_q = np.tile(np.array([[33, 34, 35]], np.int32), (B, 1))
    q = jnp.asarray(rng.normal(0, 1.0, (B, s, H, d)), jnp.float32)
    qn = jnp.asarray(rng.normal(0, 1.0, (B, s, H, d)), jnp.float32)
    sum_q = jnp.asarray(np.array([[0, 1, 0], [1, 0, 1]], bool))
    alibi = jnp.linspace(0.1, 0.4, H, dtype=jnp.float32)
    kw = dict(pos_q=jnp.asarray(pos_q), pos_k=jnp.asarray(pos_k),
              window=0, k_scale=k_scale, v_scale=v_scale,
              rope_start=rope_start)
    return q, k, v, qn, sum_q, alibi, kw


@pytest.mark.parametrize("geom", [
    dict(hk=2, d=16, dv=16, rope_start=0),     # GQA: one scale group
    dict(hk=1, d=12, dv=8, rope_start=8),      # MLA: latent|rope groups
])
def test_kernel_matches_ref_on_int8_codes(rng, geom):
    q, k, v, qn, sum_q, alibi, kw = _quant_operands(rng, **geom)
    want = decode_attention_ref(q, k, v, kw["pos_q"], kw["pos_k"],
                                window=0, sum_q=sum_q, q_nope=qn,
                                alibi=alibi, k_scale=kw["k_scale"],
                                v_scale=kw["v_scale"],
                                rope_start=kw["rope_start"])
    got = decode_attention(q, k, v, kw["pos_q"], kw["pos_k"], window=0,
                           is_sum_q=sum_q, q_nope=qn, alibi=alibi,
                           k_scale=kw["k_scale"], v_scale=kw["v_scale"],
                           rope_start=kw["rope_start"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_kernel_quant_rejects_external_nope_stream(rng):
    """On the quant path the NoPE stream *is* the unroped dequant of the
    codes; passing a separate k_nope would desynchronise them."""
    q, k, v, qn, sum_q, alibi, kw = _quant_operands(
        rng, hk=2, d=16, dv=16, rope_start=0)
    with pytest.raises(AssertionError):
        decode_attention(q, k, v, kw["pos_q"], kw["pos_k"], window=0,
                         is_sum_q=sum_q, q_nope=qn,
                         k_nope=jnp.zeros_like(k, jnp.float32),
                         alibi=alibi, k_scale=kw["k_scale"],
                         v_scale=kw["v_scale"], rope_start=0)


# ---------------------------------------------------------------------------
# 4/5. end to end through the scheduler
# ---------------------------------------------------------------------------

def _stream(params, cfg, reqs, *, kv_dtype, attn_impl="dense",
            layout="contiguous"):
    paged = layout != "contiguous"
    s = ServeScheduler(params, cfg, n_slots=2, capacity=64,
                       buckets=(8, 16), attn_impl=attn_impl,
                       kv_dtype=kv_dtype, paged=paged,
                       page_size=8 if paged else 16,
                       n_pages=10 if layout == "pressure" else None)
    rids = [s.submit(r["context"], r["candidates"]) for r in reqs]
    out = s.run()
    return [out[r].scores for r in rids], s


def _reqs(cfg, *, n=6, seed=3, repeat_frac=0.5):
    ds = make_ctr_dataset(n_users=4, n_items=30, seq_len=10,
                          vocab_size=cfg.vocab_size)
    return make_request_stream(ds, n_requests=n, k=2, n_ctx=3, seed=seed,
                               repeat_frac=repeat_frac)


def test_int8_cache_layout_and_telemetry():
    cfg = _cfg()
    cache = init_lm_cache(cfg, 2, 16, dtype=jnp.float32, kv_dtype="int8")
    assert is_quantized(cache)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].dtype == jnp.float32
    # scale sidecars share the slot axis with the codes
    assert cache["k_scale"].shape[:3] == cache["k"].shape[:3]
    assert kv_token_bytes(cache) < kv_token_bytes(
        init_lm_cache(cfg, 2, 16, dtype=jnp.float32))


def test_int8_paged_pressure_exact_vs_int8_contiguous():
    """Scale invariance under adoption/steal/eviction: the sidecars move
    with the codes, so pool pressure changes *where* KV lives but never
    its dequantized value — scores are float-exact, not merely close."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    reqs = _reqs(cfg, n=10, seed=5, repeat_frac=0.3)
    got, sched = _stream(params, cfg, reqs, kv_dtype="int8",
                         layout="pressure")
    want, _ = _stream(params, cfg, reqs, kv_dtype="int8",
                      layout="contiguous")
    assert got == want                    # float-exact, not allclose
    tel = sched.telemetry()
    assert tel["page_evictions"] > 0      # the reclamation paths ran
    assert tel["kv_dtype"] == "int8"


def test_cross_row_adoption_preserves_scales():
    """A prefix adopted cross-row after its original row was stolen must
    reproduce the original scores exactly — the adopted pages carry their
    scales, nothing requantizes."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    ctx = [list(range(10, 30))]
    s = ServeScheduler(params, cfg, n_slots=2, capacity=64,
                       buckets=(8, 16), kv_dtype="int8",
                       paged=True, page_size=8)
    r0 = s.submit(ctx, [[30]])
    base = s.run()[r0].scores
    for t in range(4):                    # roll both rows over -> steal
        s.submit([[40 + t] * 20], [[31]])
    s.run()
    r1 = s.submit(ctx, [[30]])
    again = s.run()[r1]
    assert again.scores == base           # bit-equal through adoption
    assert again.shared_prefix_tokens == 16
    assert s.telemetry()["cross_row_tokens"] == 16


def test_int8_retains_more_cross_row_prefix_at_equal_pool_bytes():
    """The payoff of int8 KV: on the revisit-heavy mix (24 requests on 2
    slots, repeat fraction 0.3, the smoke model) a pool of int8 pages
    sized to a 16-page bf16 pool's bytes holds ~1.8x the pages, so it
    keeps >= 1.5x the cross-row prefix tokens and a higher prefix hit
    rate than the bf16 pool it replaces."""
    cfg = get_arch("dti-llama").smoke
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_ctr_dataset(n_users=16, n_items=120, seq_len=12,
                          vocab_size=cfg.vocab_size, seed=0)
    reqs = make_request_stream(ds, n_requests=24, k=8, n_ctx=8, seed=0,
                               repeat_frac=0.3)
    capacity = max(1 + sum(len(t) for t in r["context"]) for r in reqs) + 64
    page_size = 16
    base_pages = max(16, -(-capacity // page_size) + 2)
    cap_eff = -(-capacity // page_size) * page_size

    def token_bytes(kv_dtype):
        return kv_token_bytes(cache_shape(
            cfg, 2, cap_eff, dtype=jnp.bfloat16, kv_dtype=kv_dtype,
            page_size=page_size, n_pages=base_pages))

    # the floor keeps the int8 pool at or under the bf16 pool's bytes
    int8_pages = int(base_pages * token_bytes(None) / token_bytes("int8"))
    tel = {}
    for kv_dtype, n_pages in ((None, base_pages), ("int8", int8_pages)):
        s = ServeScheduler(params, cfg, n_slots=2, capacity=capacity,
                           buckets=(16, 32, 64), cache_dtype=jnp.bfloat16,
                           kv_dtype=kv_dtype, page_size=page_size,
                           n_pages=n_pages)
        rids = [s.submit(r["context"], r["candidates"]) for r in reqs]
        out = s.run()
        assert all(np.isfinite(out[r].scores).all() for r in rids)
        tel[kv_dtype] = s.telemetry()
        assert not tel[kv_dtype]["watchdog_fired"]
    bf16, int8 = tel[None], tel["int8"]
    assert bf16["cross_row_tokens"] > 0
    assert int8["cross_row_tokens"] >= 1.5 * bf16["cross_row_tokens"]
    assert int8["prefix_hit_rate"] > bf16["prefix_hit_rate"]


@pytest.mark.parametrize("layout", ["contiguous", "paged", "pressure"])
@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
@pytest.mark.parametrize("attn_type", ["gqa", "mla"])
def test_int8_scores_within_tolerance_of_fp32(attn_type, attn_impl, layout):
    """The acceptance matrix: every (attn x impl x layout) cell's int8
    scores sit within INT8_SCORE_TOL of the fp32 run."""
    cfg = _cfg(attn_type)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _reqs(cfg, n=5, seed=7)
    got, _ = _stream(params, cfg, reqs, kv_dtype="int8",
                     attn_impl=attn_impl, layout=layout)
    want, _ = _stream(params, cfg, reqs, kv_dtype=None,
                      attn_impl=attn_impl, layout=layout)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=INT8_SCORE_TOL)


@pytest.mark.parametrize("attn_type", ["gqa", "mla"])
def test_int8_dense_matches_int8_pallas(attn_type):
    """Dense dequant-then-attend and the kernel's in-VMEM dequant read
    the same codes: their scores differ only by fp32 reduction order."""
    cfg = _cfg(attn_type)
    params = init_params(jax.random.PRNGKey(2), cfg)
    reqs = _reqs(cfg, n=5, seed=9)
    got, _ = _stream(params, cfg, reqs, kv_dtype="int8",
                     attn_impl="pallas")
    want, _ = _stream(params, cfg, reqs, kv_dtype="int8",
                      attn_impl="dense")
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
