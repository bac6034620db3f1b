"""Unit tests for the paper's core machinery: prompts, masks, reset, Eq. 3,
metrics, losses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.dti import (PromptStats, SpecialTokens, batch_prompts,
                            build_sliding_prompts, build_streaming_prompts,
                            window_tokens)
from repro.core.flops import (dti_flops, flops_reduction_approx,
                              flops_reduction_exact, sliding_window_flops)
from repro.core.losses import ctr_logits, ctr_loss
from repro.core.metrics import auc, ctr_metrics, f1, log_loss
from repro.core.windowed import ResetConfig, dti_mask, reset_alpha

SP = SpecialTokens()


def _items(m, tok_len=3, seed=0):
    r = np.random.default_rng(seed)
    toks = [[int(t) for t in r.integers(SP.n_reserved, 100, tok_len)]
            for _ in range(m)]
    labels = r.integers(0, 2, m)
    return toks, labels


# ---------------------------------------------------------------------------
# prompt builders (paper §3.1, §3.2)
# ---------------------------------------------------------------------------

class TestPrompts:
    def test_sliding_window_count(self):
        toks, labels = _items(30)
        prompts = build_sliding_prompts(toks, labels, n_ctx=5, max_len=256)
        assert len(prompts) == 30 - 5          # m - n prompts

    def test_streaming_count(self):
        toks, labels = _items(30)
        prompts = build_streaming_prompts(toks, labels, n_ctx=5, k=5,
                                          max_len=256)
        assert len(prompts) == 5               # ceil((m - n) / k)

    def test_streaming_k_targets_per_prompt(self):
        toks, labels = _items(25)
        prompts = build_streaming_prompts(toks, labels, n_ctx=5, k=4,
                                          max_len=256)
        for p in prompts[:-1]:
            assert int(p["is_sum"].sum()) == 4

    def test_labels_only_at_sum_positions(self):
        toks, labels = _items(20)
        for build, kw in [(build_sliding_prompts, {}),
                          (build_streaming_prompts, {"k": 3})]:
            for p in build(toks, labels, n_ctx=4, max_len=256, **kw):
                assert not np.any(p["labels"][~p["is_sum"]])

    def test_streaming_label_alignment(self):
        toks, labels = _items(20)
        prompts = build_streaming_prompts(toks, labels, n_ctx=4, k=3,
                                          max_len=256)
        got = np.concatenate([p["labels"][p["is_sum"]] for p in prompts])
        np.testing.assert_array_equal(got, labels[4:])

    def test_token_budget_ratio(self):
        """Streaming prompts shrink total tokens ~k/(1 + k/n)-fold — the
        redundancy elimination that drives Eq. 3."""
        toks, labels = _items(200, tok_len=4)
        s_sw, s_dti = PromptStats(), PromptStats()
        build_sliding_prompts(toks, labels, n_ctx=20, max_len=4096,
                              stats=s_sw)
        build_streaming_prompts(toks, labels, n_ctx=20, k=50, max_len=4096,
                                stats=s_dti)
        assert s_sw.n_tokens / s_dti.n_tokens > 5.0
        assert s_dti.n_targets == 180

    def test_batching_shapes(self):
        toks, labels = _items(30)
        prompts = build_streaming_prompts(toks, labels, n_ctx=5, k=5,
                                          max_len=128)
        b = next(batch_prompts(prompts, 4))
        assert b["tokens"].shape == (4, 128)
        assert b["valid"].dtype == bool

    def test_window_tokens_cap(self):
        assert window_tokens(20, 5.0) <= 1024    # the paper's cap
        assert window_tokens(2, 3.0) == 9


# ---------------------------------------------------------------------------
# masks + reset (paper §3.3, §4.1)
# ---------------------------------------------------------------------------

class TestMaskAndReset:
    def test_mask_causal_window(self):
        pos = jnp.arange(16)[None]
        m = np.asarray(dti_mask(pos, pos, window=4))[0]
        for t in range(16):
            for s in range(16):
                expect = 0 <= t - s <= 4
                assert m[t, s] == expect

    def test_mask_sum_isolation(self):
        pos = jnp.arange(8)[None]
        is_sum = jnp.zeros((1, 8), bool).at[0, 3].set(True)
        m = np.asarray(dti_mask(pos, pos, window=8, is_sum_k=is_sum))[0]
        assert m[3, 3]                       # SUM attends itself
        assert not m[4:, 3].any()            # nobody else attends the SUM

    @pytest.mark.hyp
    @given(st.integers(0, 2000))
    @settings(max_examples=30, deadline=None)
    def test_reset_alpha_bounds(self, d):
        cfg = ResetConfig(0.1, 0.4, 512.0)
        a = float(reset_alpha(jnp.asarray(d), cfg))
        assert 0.1 <= a <= 0.4 + 1e-6

    def test_reset_alpha_monotone(self):
        cfg = ResetConfig(0.0, 0.3, 512.0)
        d = jnp.arange(0, 1200, 10)
        a = np.asarray(reset_alpha(d, cfg))
        assert np.all(np.diff(a) >= -1e-9)
        mid = float(reset_alpha(jnp.asarray(512), cfg))
        assert abs(mid - 0.15) < 1e-6        # midpoint -> (ymin+ymax)/2


# ---------------------------------------------------------------------------
# FLOPs model (paper §3.5)
# ---------------------------------------------------------------------------

class TestEq3:
    def test_paper_example(self):
        """n=20 ctx, k=50 targets: the paper quotes 14.28x."""
        c = 10                               # tokens per interaction
        red = flops_reduction_approx(N=20 * c, K=50 * c, k=50)
        assert abs(red - 14.2857) < 1e-3

    def test_exact_matches_ratio(self):
        m, n, k, c, d, L = 5000, 20, 50, 10, 256, 4
        N, K = n * c, k * c
        sw = sliding_window_flops(m, n, N, d, L)
        dt = dti_flops(m, k, N, K, d, L)
        assert abs(sw / dt - flops_reduction_exact(m, n, k, N, K)
                   * (N + d) / (N + d)) / (sw / dt) < 0.35
        # approx converges to exact as m -> inf
        assert abs(flops_reduction_exact(10**7, n, k, N, K)
                   - flops_reduction_approx(N, K, k)) < 0.01

    @pytest.mark.hyp
    @given(st.integers(2, 60))
    @settings(max_examples=20, deadline=None)
    def test_reduction_increases_with_k(self, k):
        assert (flops_reduction_approx(200, (k + 1) * 10, k + 1)
                > flops_reduction_approx(200, k * 10, k))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_auc_perfect_and_random(self):
        y = np.array([0, 0, 1, 1])
        assert auc(y, np.array([.1, .2, .8, .9])) == 1.0
        assert auc(y, np.array([.9, .8, .2, .1])) == 0.0
        assert auc(y, np.array([.5, .5, .5, .5])) == 0.5

    def test_auc_ties_average_rank(self):
        y = np.array([0, 1, 0, 1])
        s = np.array([.3, .3, .1, .9])
        assert abs(auc(y, s) - 0.875) < 1e-9

    @pytest.mark.hyp
    @given(st.lists(st.tuples(st.integers(0, 1),
                              st.floats(0.01, 0.99)), min_size=6,
                    max_size=60))
    @settings(max_examples=30, deadline=None)
    def test_auc_monotonic_invariance(self, pairs):
        y = np.array([p[0] for p in pairs])
        s = np.array([p[1] for p in pairs])
        if y.min() == y.max():
            return
        a1 = auc(y, s)
        # power-of-two scale: strictly monotone AND exact in floats (a shift
        # or a sigmoid-style transform can round near-equal scores into
        # ties, e.g. 4*0.01-1 == 4*0.010000000000000002-1, legitimately
        # changing the tie-averaged AUC)
        a2 = auc(y, 4.0 * s)
        assert abs(a1 - a2) < 1e-9

    def test_log_loss_known(self):
        y = np.array([1, 0])
        p = np.array([0.8, 0.2])
        expect = -np.mean([np.log(0.8), np.log(0.8)])
        assert abs(log_loss(y, p) - expect) < 1e-9

    def test_f1(self):
        y = np.array([1, 1, 0, 0])
        s = np.array([.9, .4, .6, .1])
        # tp=1 fp=1 fn=1 -> f1 = 0.5
        assert abs(f1(y, s) - 0.5) < 1e-9


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

class TestCTRLoss:
    def _setup(self):
        from repro.models.transformer import ModelConfig, init_params
        cfg = ModelConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          d_ff=64, vocab_size=64, head_dim=16, remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        return cfg, params

    def test_loss_only_counts_sum_positions(self):
        cfg, params = self._setup()
        h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        mask = jnp.zeros((2, 8), bool).at[:, 3].set(True)
        labels = jnp.zeros((2, 8), jnp.int32).at[:, 3].set(1)
        l1, _ = ctr_loss(params, cfg, h, mask, labels, yes_id=3, no_id=4)
        # corrupting labels off the SUM positions must not change the loss
        labels2 = labels.at[:, 5].set(1)
        l2, _ = ctr_loss(params, cfg, h, mask, labels2, yes_id=3, no_id=4)
        assert float(l1) == float(l2)

    def test_bidimensional_softmax(self):
        cfg, params = self._setup()
        h = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 32))
        logits = ctr_logits(params, cfg, h, 3, 4)
        assert logits.shape == (1, 4, 2)
        p = jax.nn.softmax(logits, axis=-1)
        np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, rtol=1e-5)
