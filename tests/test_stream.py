"""Streaming continual-training subsystem (repro.stream + satellites).

The acceptance bar: training on incrementally-extended prompts for a user
whose history grows m -> m+Δ yields the same supervised (target, context)
pairs — and grad-identical batches under packing — as rebuilding the full
DTI corpus and keeping only the new targets; plus streaming metrics,
pipeline shape discipline, online-trainer eval/publication, and weight
hot-swap into live serving.
"""
import jax
import numpy as np
import pytest

from repro.core.dti import (build_streaming_prompts, pack_prompts,
                            prompt_length)
from repro.core.metrics import StreamingAUC, StreamingLogLoss, auc, log_loss
from repro.data.requests import make_event_stream, warm_histories
from repro.data.synthetic import make_ctr_dataset
from repro.models.transformer import ModelConfig, init_params
from repro.obs.clock import monotonic
from repro.obs.trace import SpanTracer, validate_chrome_trace
from repro.stream import (IncrementalDTI, OnlineTrainer, ParamPublisher,
                          ParamSubscriber, StreamPipeline,
                          make_stream_loss_fn)
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptimizerConfig

N_CTX, K, MAX_LEN = 4, 3, 128


def _cfg():
    return ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                       d_ff=64, vocab_size=256, head_dim=16,
                       attn_type="gqa", window=0, attn_impl="dense",
                       dti_sum_token=True, remat=False)


def _history(m, seed=0):
    rng = np.random.default_rng(seed)
    items = [[int(x) for x in rng.integers(8, 200, int(rng.integers(2, 5)))]
             for _ in range(m)]
    labels = [int(x) for x in rng.integers(0, 2, m)]
    return items, labels


def _events(items, labels, lo, hi, user=0):
    return [{"user": user, "item_tokens": items[i], "label": labels[i]}
            for i in range(lo, hi)]


def _rebuild_keep_new(items, labels, m_old):
    """Reference: full DTI rebuild over the grown history, keeping only the
    targets that did not exist at m_old (target_mask on their [SUM]s)."""
    rows = []
    for gi, r in enumerate(build_streaming_prompts(
            items, labels, n_ctx=N_CTX, k=K, max_len=MAX_LEN)):
        gs = N_CTX + gi * K
        tm = np.zeros(MAX_LEN, bool)
        for j, p in enumerate(np.flatnonzero(r["is_sum"])):
            if gs + j >= m_old:
                tm[p] = True
        if tm.any():
            r = dict(r)
            r["target_mask"] = tm
            rows.append(r)
    return rows


def _supervised_pairs(rows):
    """(causal token prefix, label) per supervised [SUM] — the pair the
    loss actually trains on."""
    out = []
    for r in rows:
        for p in np.flatnonzero(r["target_mask"]):
            out.append((tuple(r["tokens"][: p + 1].tolist()),
                        int(r["labels"][p])))
    return sorted(out)


class TestStreamingMetrics:
    def test_histogram_auc_close_to_exact_10k(self, rng):
        labels = (rng.random(10_000) < 0.35).astype(int)
        # scores correlated with labels, heavy ties via rounding
        scores = np.clip(0.3 * labels + 0.5 * rng.random(10_000), 0, 1)
        scores = np.round(scores, 3)
        acc = StreamingAUC()
        for lo in range(0, 10_000, 1000):           # streamed in chunks
            acc.update(labels[lo:lo + 1000], scores[lo:lo + 1000])
        assert abs(acc.value() - auc(labels, scores)) <= 1e-3

    def test_merge_equals_single_pass(self, rng):
        labels = (rng.random(4000) < 0.5).astype(int)
        scores = rng.random(4000)
        whole = StreamingAUC().update(labels, scores)
        a = StreamingAUC().update(labels[:1500], scores[:1500])
        b = StreamingAUC().update(labels[1500:], scores[1500:])
        assert a.merge(b).value() == whole.value()
        la = StreamingLogLoss().update(labels[:1500], scores[:1500])
        lb = StreamingLogLoss().update(labels[1500:], scores[1500:])
        assert la.merge(lb).value() == pytest.approx(
            log_loss(labels, scores), abs=1e-12)

    def test_degenerate_one_class(self):
        assert StreamingAUC().update([1, 1], [0.2, 0.9]).value() == 0.5
        assert StreamingAUC().value() == 0.5


class TestIncrementalEquivalence:
    def test_supervised_pairs_match_rebuild(self):
        """m -> m+Δ with Δ delivered in uneven calls: every new target is
        supervised exactly once, against exactly the causal context the
        full rebuild would give it."""
        m0, d = 9, 7
        items, labels = _history(m0 + d)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items[:m0], labels[:m0])
        rows = []
        for lo, hi in ((m0, m0 + 1), (m0 + 1, m0 + 4), (m0 + 4, m0 + d)):
            rows += inc.extend_prompts(_events(items, labels, lo, hi))
        ref = _rebuild_keep_new(items, labels, m0)
        assert _supervised_pairs(rows) == _supervised_pairs(ref)

    def test_single_call_rows_byte_identical(self):
        """Δ in one call: the emitted rows ARE the rebuilt-and-filtered rows."""
        m0, d = 10, 6
        items, labels = _history(m0 + d, seed=1)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items[:m0], labels[:m0])
        rows = inc.extend_prompts(_events(items, labels, m0, m0 + d))
        ref = _rebuild_keep_new(items, labels, m0)
        assert len(rows) == len(ref)
        for r, s in zip(rows, ref):
            assert set(r) == set(s)
            for key in r:
                np.testing.assert_array_equal(r[key], s[key], err_msg=key)

    def test_grad_identical_under_packing(self):
        """Packed incremental batches and packed rebuilt-and-filtered
        batches produce the same gradients: unsupervised suffix targets a
        partial emission lacks are causally invisible to the supervised
        positions, and target_mask zeroes their loss weight."""
        cfg = _cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
        loss_fn = make_stream_loss_fn(cfg, window=0)
        m0, d = 8, 6
        items, labels = _history(m0 + d, seed=2)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items[:m0], labels[:m0])
        rows = []
        for lo, hi in ((m0, m0 + 2), (m0 + 2, m0 + 3), (m0 + 3, m0 + d)):
            rows += inc.extend_prompts(_events(items, labels, lo, hi))
        ref = _rebuild_keep_new(items, labels, m0)
        assert len(rows) > len(ref)          # partial emissions happened

        def grads(rs):
            batch = {k: np.stack([r[k] for r in pack_prompts(rs, MAX_LEN)])
                     for k in rs[0]}
            g, _ = jax.grad(lambda p: loss_fn(p, batch,
                                              jax.random.PRNGKey(0)),
                            has_aux=True)(params)
            return g

        ga, gb = grads(rows), grads(ref)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                    np.asarray(b), atol=1e-6),
            ga, gb)

    def test_event_cost_is_group_local(self):
        """One new event touches O(n_ctx + k) interactions' tokens, never
        the full history — the incremental cost claim."""
        items, labels = _history(60, seed=3)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items[:59], labels[:59])
        rows = inc.extend_prompts(_events(items, labels, 59, 60))
        assert len(rows) == 1
        bound = sum(len(t) + 2 for t in items[-(N_CTX + K):]) + 1
        assert prompt_length(rows[0]) <= bound
        assert inc.buffered_interactions(0) <= N_CTX + K

    def test_unsupervised_seed_keeps_pending_history(self):
        """seed_history(supervised=False) must not trim interactions its
        first emission still needs: the whole backlog is supervised against
        exactly the full-rebuild corpus."""
        items, labels = _history(20, seed=5)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items, labels, supervised=False)
        assert inc.extend_prompts([]) == []            # nothing new arrived
        more_items, more_labels = _history(1, seed=6)
        items, labels = items + more_items, labels + more_labels
        rows = inc.extend_prompts(_events(items, labels, 20, 21))
        ref = _rebuild_keep_new(items, labels, 0)      # everything is new
        assert _supervised_pairs(rows) == _supervised_pairs(ref)
        assert inc.buffered_interactions(0) <= N_CTX + K

    def test_pack_rejects_mixed_target_mask(self):
        items, labels = _history(12, seed=7)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        inc.seed_history(0, items[:8], labels[:8])
        masked = inc.extend_prompts(_events(items, labels, 8, 12))
        plain = build_streaming_prompts(items, labels, n_ctx=N_CTX, k=K,
                                        max_len=MAX_LEN)
        with pytest.raises(AssertionError):
            pack_prompts(masked + plain, MAX_LEN)
        with pytest.raises(AssertionError):
            pack_prompts(plain + masked, MAX_LEN)

    def test_unseen_user_and_short_history_emit_nothing_until_ready(self):
        items, labels = _history(N_CTX + 1, seed=4)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        assert inc.extend_prompts(_events(items, labels, 0, N_CTX)) == []
        rows = inc.extend_prompts(_events(items, labels, N_CTX, N_CTX + 1))
        assert len(rows) == 1
        assert int(rows[0]["target_mask"].sum()) == 1


class TestPipeline:
    def _setup(self, n_ticks=3, users=4):
        ds = make_ctr_dataset(n_users=users, n_items=50, seq_len=16,
                              vocab_size=256, seed=0)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        for u, (toks, labels) in enumerate(warm_histories(ds,
                                                          start_frac=0.5)):
            inc.seed_history(u, toks, labels)
        ticks = make_event_stream(ds, n_ticks=n_ticks, start_frac=0.5,
                                  seed=0)
        return inc, ticks

    def test_fixed_shapes_and_exactly_once_supervision(self):
        inc, ticks = self._setup()
        n_events = sum(len(t) for t in ticks)
        pipe = StreamPipeline(iter(ticks), inc, batch_size=3)
        targets = 0
        for batch in pipe.batches():
            assert batch["tokens"].shape == (3, MAX_LEN)
            assert set(batch) >= {"tokens", "positions", "segment_ids",
                                  "is_sum", "labels", "valid", "target_mask"}
            targets += int(batch["target_mask"].sum())
        assert targets == n_events          # every event supervised once
        assert pipe.stats.n_targets == n_events
        assert 0.0 < pipe.stats.pad_fraction < 1.0

    def test_buckets_bound_sequence_dim(self):
        inc, ticks = self._setup()
        pipe = StreamPipeline(iter(ticks), inc, batch_size=2,
                              buckets=(64, MAX_LEN))
        shapes = {b["tokens"].shape[1] for b in pipe.batches()}
        assert shapes <= {64, MAX_LEN}

    def test_stop_releases_put_blocked_worker(self):
        """An abandoned consumer + stop() must not leak a worker thread
        blocked on the bounded queue."""
        inc, ticks = self._setup(n_ticks=8)
        pipe = StreamPipeline(iter(ticks), inc, batch_size=1, queue_size=1)
        gen = pipe.batches()
        next(gen)                        # worker now blocked on a full queue
        pipe.stop()
        assert not pipe._thread.is_alive()

    def test_worker_errors_surface(self):
        inc, _ = self._setup()

        def bad_source():
            yield [{"user": 0}]              # malformed event

        pipe = StreamPipeline(bad_source(), inc, batch_size=2)
        with pytest.raises(KeyError):
            list(pipe.batches())


class TestOnlineTrainer:
    def _trainer(self, tmp_path=None, **kw):
        cfg = _cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
        ocfg = OptimizerConfig(lr=1e-3, schedule="const", warmup_steps=1,
                               total_steps=1000)
        ckpt = (CheckpointManager(str(tmp_path), save_interval=1,
                                  async_write=False)
                if tmp_path is not None else None)
        kw.setdefault("window_targets", 8)
        return OnlineTrainer(make_stream_loss_fn(cfg, window=0), params,
                             ocfg, ckpt=ckpt, **kw), cfg

    def _stream(self, n_ticks=3):
        ds = make_ctr_dataset(n_users=4, n_items=50, seq_len=16,
                              vocab_size=256, seed=0)
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        for u, (toks, labels) in enumerate(warm_histories(ds,
                                                          start_frac=0.5)):
            inc.seed_history(u, toks, labels)
        return StreamPipeline(
            iter(make_event_stream(ds, n_ticks=n_ticks, start_frac=0.5,
                                   seed=0)),
            inc, batch_size=2)

    def test_trains_evaluates_and_windows(self):
        ot, _ = self._trainer()
        ot.run(self._stream().batches())
        assert ot.step > 0
        assert all(np.isfinite(r["loss"]) for r in ot.history)
        assert len(ot.eval_windows) >= 1    # full windows rolled on their own
        assert all(w.n_targets >= ot.window_targets
                   for w in ot.eval_windows)
        ot.flush_windows()                  # close the partial tail window
        assert ot.lifetime_auc.n == sum(w.n_targets for w in ot.eval_windows)
        assert 0.0 <= ot.lifetime_auc.value() <= 1.0
        if len(ot.eval_windows) >= 2:
            assert set(ot.drift()) == {"d_auc", "d_log_loss"}

    def test_checkpoint_warm_start(self, tmp_path):
        ot, _ = self._trainer(tmp_path)
        ot.run(self._stream().batches())
        resumed, _ = self._trainer(tmp_path)
        assert resumed.resume_if_possible()
        assert resumed.step == ot.step
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            resumed.state.params, ot.state.params)
        # optimizer moments came back too (warm start, not params-only)
        assert int(resumed.state.opt.step) == int(ot.state.opt.step)

    def test_publishes_versions(self, tmp_path):
        pub = ParamPublisher(str(tmp_path))
        ot, _ = self._trainer(publisher=pub, publish_every=2)
        ot.run(self._stream().batches())
        assert ot.published_version == ot.step
        assert pub.latest_version() == ot.step


class TestPublishHotSwap:
    def test_publisher_subscriber_roundtrip(self, tmp_path):
        cfg = _cfg()
        p0 = init_params(jax.random.PRNGKey(0), cfg)
        p1 = jax.tree_util.tree_map(lambda x: x + 1.0, p0)
        pub = ParamPublisher(str(tmp_path))
        sub = ParamSubscriber(str(tmp_path), p0)
        assert sub.poll() is None            # nothing published yet
        pub.publish(1, p1)
        version, got = sub.poll()
        assert version == 1
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            got, p1)
        assert sub.poll() is None            # no re-delivery

    def test_scheduler_hot_swap_keeps_inflight_slots(self, tmp_path):
        """Weights published mid-request land between decode steps; the
        in-flight request finishes on its slot (never evicted) and later
        requests score under the new weights."""
        from repro.serve.scheduler import ServeScheduler
        cfg = _cfg()
        p_old = init_params(jax.random.PRNGKey(0), cfg)
        p_new = init_params(jax.random.PRNGKey(1), cfg)
        ctx = [[10 + i] for i in range(4)]
        cands = [[30 + j, 40 + j] for j in range(8)]  # several bursts

        swaps = {"n": 0}

        def source():
            swaps["n"] += 1
            return (7, p_new) if swaps["n"] == 2 else None

        sched = ServeScheduler(p_old, cfg, n_slots=2, capacity=64,
                               buckets=(8,))
        sched.attach_param_source(source, poll_every=1)
        rid = sched.submit(ctx, cands)
        res = sched.run()[rid]
        assert len(res.scores) == len(cands)
        assert all(0.0 <= s <= 1.0 for s in res.scores)
        assert sched.params_version == 7
        assert sched.params is p_new

        # post-swap requests match a scheduler born with the new weights
        rid2 = sched.submit(ctx, cands)
        after = sched.run()[rid2]
        fresh = ServeScheduler(p_new, cfg, n_slots=2, capacity=64,
                               buckets=(8,))
        want_rid = fresh.submit(ctx, cands)
        np.testing.assert_allclose(after.scores,
                                   fresh.run()[want_rid].scores, atol=1e-6)

    def test_ctr_server_update_params(self):
        from repro.serve.engine import CTRServer
        cfg = _cfg()
        server = CTRServer(init_params(jax.random.PRNGKey(0), cfg), cfg,
                           max_len=64)
        p_new = init_params(jax.random.PRNGKey(1), cfg)
        server.update_params(p_new)
        assert server.params is p_new


def test_stream_run_trace_has_tick_and_step_spans():
    """A streaming run under a ``SpanTracer``, tick by tick as a live
    stream feeds it: the exported trace validates and holds one
    ``stream.tick`` span per tick and one ``online.step`` span per train
    step; every tick's targets are trained after it arrives (freshness > 0)
    and the online AUC is read after every tick."""
    tracer = SpanTracer()
    ds = make_ctr_dataset(n_users=4, n_items=50, seq_len=16,
                          vocab_size=256, seed=0)
    inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
    for u, (toks, labels) in enumerate(warm_histories(ds, start_frac=0.5)):
        inc.seed_history(u, toks, labels, supervised=True)
    ticks = make_event_stream(ds, n_ticks=3, start_frac=0.5, seed=0)
    cfg = _cfg()
    ot = OnlineTrainer(make_stream_loss_fn(cfg, window=0),
                       init_params(jax.random.PRNGKey(0), cfg),
                       OptimizerConfig(lr=1e-3, schedule="const",
                                       warmup_steps=1, total_steps=1000),
                       publish_every=0, window_targets=8, tracer=tracer)
    freshness, auc_over_time = [], []
    for t, tick in enumerate(ticks):
        t0 = monotonic()
        pipe = StreamPipeline(iter([tick]), inc, batch_size=2,
                              tracer=tracer)
        ot.run(pipe.batches(), rng=jax.random.PRNGKey(t))
        jax.block_until_ready(ot.state.params)
        freshness.append(monotonic() - t0)
        auc_over_time.append(ot.lifetime_auc.value())

    doc = tracer.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    spans = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert spans.count("stream.tick") == len(ticks)
    assert spans.count("online.step") == ot.step > 0
    assert np.percentile(freshness, 95) > 0.0
    assert len(auc_over_time) == len(ticks)
    assert all(0.0 <= a <= 1.0 for a in auc_over_time)


class TestPrefixPrewarmer:
    """Stream->serve cache priming: hot-user selection, re-warm gating,
    swap-tick behaviour. The scheduler end of the contract (candidate-less
    admission, radix publication, identical scores) is covered by
    tests/test_paged_cache.py::test_prewarm_primes_the_radix_index."""

    class _Sched:
        def __init__(self):
            self.calls = []

        def prewarm(self, context):
            self.calls.append([list(t) for t in context])
            return len(self.calls)       # a fake rid

    def _dti(self, users):
        inc = IncrementalDTI(n_ctx=N_CTX, k=K, max_len=MAX_LEN)
        for u, m in users.items():
            items, labels = _history(m, seed=u)
            inc.seed_history(u, items, labels)
        return inc

    def test_hot_users_warm_once_until_history_grows(self):
        from repro.stream import PrefixPrewarmer
        inc = self._dti({0: 3, 1: 3, 2: 3})
        sched = self._Sched()
        pw = PrefixPrewarmer(inc, sched, top_k=2, min_events=2.0, decay=0.5)
        pw.observe([{"user": 0}] * 5 + [{"user": 1}] * 4 + [{"user": 2}] * 1)
        rids = pw.tick()
        # top_k=2 by heat: users 0 and 1; user 2 is below min_events
        assert len(rids) == 2 and pw.warmed == 2
        assert sched.calls[0] == [list(t) for t in inc._users[0].items]
        # same heat, same histories -> nothing new to warm
        pw.observe([{"user": 0}] * 5 + [{"user": 1}] * 4)
        assert pw.tick() == []
        # history growth re-arms the user
        inc.extend_prompts(_events(*_history(4, seed=0), 3, 4, user=0))
        pw.observe([{"user": 0}] * 5)
        assert len(pw.tick()) == 1
        assert sched.calls[-1] == [list(t) for t in inc._users[0].items]

    def test_swap_tick_skips_and_rearms(self):
        from repro.stream import PrefixPrewarmer
        inc = self._dti({0: 3})
        sched = self._Sched()
        pw = PrefixPrewarmer(inc, sched, top_k=1, min_events=1.0, decay=1.0)
        pw.observe([{"user": 0}] * 3)
        assert len(pw.tick()) == 1
        # a hot-swap tick warms nothing but drops the warmed markers...
        assert pw.tick(swapped=True) == []
        assert pw.skipped_swap_ticks == 1
        # ...so the unchanged prefix re-warms under the new weights
        assert len(pw.tick()) == 1 and pw.warmed == 2

    def test_heat_decays_cold_users_out(self):
        from repro.stream import PrefixPrewarmer
        inc = self._dti({0: 3})
        sched = self._Sched()
        pw = PrefixPrewarmer(inc, sched, top_k=4, min_events=2.0, decay=0.5)
        pw.observe([{"user": 0}] * 4)
        assert len(pw.tick()) == 1       # heat 4 -> 2.0, still hot
        assert pw.tick() == []           # 1.0: below the gate (and warmed)
        for _ in range(12):
            pw.tick()
        assert pw._heat == {}            # decayed out entirely
