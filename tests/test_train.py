"""Training runtime: optimizer math, schedules, LoRA masking, grad accum,
compression, checkpointing (atomic/keep-k/elastic), fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   compress_int8, decompress_int8,
                                   ef_compress_grads, init_opt_state,
                                   schedule_lr)
from repro.train.resilience import FailureSupervisor, StragglerMonitor
from repro.train.trainer import (TrainOptions, Trainer, init_train_state,
                                 make_train_step)


class TestOptimizer:
    def test_adamw_converges_quadratic(self):
        cfg = OptimizerConfig(lr=0.1, schedule="const", warmup_steps=1,
                              total_steps=100, weight_decay=0.0,
                              grad_clip=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = init_opt_state(cfg, params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}          # d/dw w^2
            params, state, _ = adamw_update(cfg, grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_schedules(self):
        for sched in ("cosine", "wsd", "const"):
            cfg = OptimizerConfig(lr=1.0, schedule=sched, warmup_steps=10,
                                  total_steps=100, min_lr_frac=0.1)
            lrs = [float(schedule_lr(cfg, jnp.asarray(s)))
                   for s in range(100)]
            assert lrs[0] < lrs[9]                  # warmup
            assert max(lrs) <= 1.0 + 1e-6
        # WSD holds stable then decays
        cfg = OptimizerConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                              total_steps=100, decay_frac=0.2)
        mid = float(schedule_lr(cfg, jnp.asarray(50)))
        end = float(schedule_lr(cfg, jnp.asarray(99)))
        assert abs(mid - 1.0) < 1e-5 and end < 0.2

    def test_lora_trainable_mask_freezes_base(self):
        cfg = OptimizerConfig(lr=0.1, schedule="const", trainable="lora")
        params = {"w": jnp.ones((4, 4)),
                  "lora_a": jnp.ones((4, 2)), "lora_b": jnp.zeros((2, 4))}
        state = init_opt_state(cfg, params)
        grads = {k: jnp.ones_like(v) for k, v in params.items()}
        new, _, _ = adamw_update(cfg, grads, state, params)
        np.testing.assert_array_equal(new["w"], params["w"])       # frozen
        assert float(jnp.abs(new["lora_a"] - params["lora_a"]).max()) > 0

    def test_grad_clip(self):
        cfg = OptimizerConfig(lr=1e-3, grad_clip=1.0, schedule="const")
        params = {"w": jnp.zeros(3)}
        state = init_opt_state(cfg, params)
        _, _, stats = adamw_update(cfg, {"w": jnp.asarray([1e3, 0, 0])},
                                   state, params)
        assert float(stats["grad_norm"]) > 100     # reported pre-clip

    @pytest.mark.hyp
    @given(st.lists(st.floats(-100, 100), min_size=2, max_size=40))
    @settings(max_examples=30, deadline=None)
    def test_int8_roundtrip_error_bound(self, xs):
        g = jnp.asarray(xs, jnp.float32)
        q, s = compress_int8(g)
        err = jnp.abs(decompress_int8(q, s) - g)
        assert float(err.max()) <= float(s) * 0.5 + 1e-6

    def test_error_feedback_preserves_sum(self):
        """Over many steps the EF residual keeps the compressed stream
        unbiased: sum(deq) -> sum(g)."""
        r = np.random.default_rng(0)
        g = jnp.asarray(r.normal(size=(64,)), jnp.float32)
        err = jnp.zeros_like(g)
        total = jnp.zeros_like(g)
        for _ in range(50):
            deq, err = ef_compress_grads(g, err)
            total = total + deq
        np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g),
                                   atol=float(jnp.abs(g).max()) / 50)


class TestTrainStep:
    def _loss(self, params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    def _batch(self, n=32, seed=0):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, 4)).astype(np.float32)
        w_true = np.asarray([1.0, -2.0, 3.0, 0.5], np.float32)
        return {"x": x, "y": x @ w_true}

    def test_grad_accum_matches_full_batch(self):
        cfg = OptimizerConfig(lr=1e-2, schedule="const")
        params = {"w": jnp.zeros(4)}
        b = self._batch()
        s1 = init_train_state(params, cfg, TrainOptions(donate=False))
        s2 = init_train_state(params, cfg,
                              TrainOptions(grad_accum=4, donate=False))
        f1 = make_train_step(self._loss, cfg, TrainOptions(donate=False))
        f4 = make_train_step(self._loss, cfg,
                             TrainOptions(grad_accum=4, donate=False))
        s1, m1 = f1(s1, b, jax.random.PRNGKey(0))
        s2, m2 = f4(s2, b, jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(s1.params["w"]),
                                   np.asarray(s2.params["w"]), atol=1e-6)

    def test_training_reduces_loss(self):
        cfg = OptimizerConfig(lr=5e-2, schedule="const", warmup_steps=1,
                              weight_decay=0.0)
        state = init_train_state({"w": jnp.zeros(4)}, cfg)
        step = make_train_step(self._loss, cfg)
        losses = []
        for i in range(200):
            state, m = step(state, self._batch(seed=i),
                            jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.05 * losses[0]

    def test_lora_steps_touch_only_the_adapter_factors(self):
        """trainable="lora": three steps move lora_a/lora_b and nothing
        else — base weights, biases, norms and the constant lora_scale stay
        bit-identical and carry only scalar optimizer state."""
        import dataclasses
        from repro.configs.qwen2_1_5b import SMOKE
        from repro.launch.train import make_lm_loss_fn
        from repro.models.transformer import init_params

        cfg = dataclasses.replace(SMOKE, lora_rank=4, attn_impl="dense")
        params = init_params(jax.random.PRNGKey(0), cfg)
        before = jax.tree_util.tree_map(np.asarray, params)
        ocfg = OptimizerConfig(lr=1e-2, schedule="const", warmup_steps=1,
                               trainable="lora")
        r = np.random.default_rng(0)
        b, s = 2, 32
        batch = {"tokens": r.integers(8, cfg.vocab_size, (b, s)),
                 "positions": np.tile(np.arange(s), (b, 1)),
                 "is_sum": r.random((b, s)) < 0.2,
                 "labels": r.integers(0, 2, (b, s)),
                 "valid": np.ones((b, s), bool)}
        state = init_train_state(params, ocfg)
        step = make_train_step(make_lm_loss_fn(cfg, 16), ocfg)
        for i in range(3):
            state, m = step(state, batch, jax.random.PRNGKey(i))
            assert float(m["grad_norm"]) > 0

        flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
        mu = jax.tree_util.tree_leaves(state.opt.mu)
        master = jax.tree_util.tree_leaves(state.opt.master)
        old = jax.tree_util.tree_leaves(before)
        n_lora = 0
        for (path, new), m_, ma, o in zip(flat, mu, master, old):
            if path[-1].key in ("lora_a", "lora_b"):
                n_lora += 1
                assert m_.shape == new.shape and ma.shape == new.shape
            else:
                np.testing.assert_array_equal(np.asarray(new), o,
                                              err_msg=str(path))
                assert m_.shape == () and ma.shape == ()
        # a/b on q, k, v, o, gate, up, down (layers stacked on axis 0)
        assert n_lora == 2 * 7 and n_lora < len(flat)
        lora_b = [np.asarray(x) for p, x in flat if p[-1].key == "lora_b"]
        assert any(np.abs(x).max() > 0 for x in lora_b)


class TestCheckpoint:
    def test_roundtrip_atomic_keepk(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, save_interval=1,
                                async_write=False)
        state = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                 "b": jnp.asarray(1.5, jnp.bfloat16)}
        for step in (1, 2, 3):
            mgr.save(step, state, meta={"step": step})
        assert mgr.all_steps() == [2, 3]              # keep-k gc
        target = {"w": jnp.zeros((2, 3)), "b": jnp.asarray(0, jnp.bfloat16)}
        restored = mgr.restore(target)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert restored["b"].dtype == jnp.bfloat16
        assert mgr.restore_meta()["meta"]["step"] == 3

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, {"w": jnp.zeros((2, 3))})
        with pytest.raises(ValueError):
            mgr.restore({"w": jnp.zeros((3, 3))})

    def test_elastic_restore_onto_new_sharding(self, tmp_path):
        """Save unsharded, restore with explicit shardings (the lose-a-pod
        path: restore is mesh-agnostic)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_cpu_mesh
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        state = {"w": jnp.arange(8, dtype=jnp.float32)}
        mgr.save(1, state)
        mesh = make_cpu_mesh()
        shardings = {"w": NamedSharding(mesh, P("data"))}
        restored = mgr.restore({"w": jnp.zeros(8)}, shardings=shardings)
        assert restored["w"].sharding == shardings["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8))

    def test_crash_resume_full_state_with_compression(self, tmp_path):
        """Crash-resume of a FULL TrainState — params, AdamW moments
        (incl. fp32 master), and the error-feedback residual with gradient
        compression on. A mid-run failure (via FailureSupervisor) restores
        from ``latest_step`` and the resumed run reproduces the
        uninterrupted one bit-for-bit."""
        opts = TrainOptions(compress_grads=True, donate=False)
        cfg = OptimizerConfig(lr=1e-2, schedule="const", warmup_steps=1)

        def loss(params, batch, rng):
            return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2), {}

        def batch(i):
            r = np.random.default_rng(i)
            x = r.normal(size=(8, 4)).astype(np.float32)
            return {"x": x, "y": x @ np.asarray([1.0, -2.0, 3.0, 0.5],
                                                np.float32)}

        step = make_train_step(loss, cfg, opts)
        params = {"w": jnp.zeros(4)}

        def run_steps(state, lo, hi):
            for i in range(lo, hi):
                state, _ = step(state, batch(i), jax.random.PRNGKey(i))
            return state

        # uninterrupted reference over 6 steps
        ref = run_steps(init_train_state(params, cfg, opts), 0, 6)
        assert ref.ef_error is not None            # compression engaged

        mgr = CheckpointManager(str(tmp_path), save_interval=1,
                                async_write=False)
        state = run_steps(init_train_state(params, cfg, opts), 0, 4)
        mgr.save(4, state, meta={"step": 4})

        calls = {"n": 0}

        def attempt():
            calls["n"] += 1
            if calls["n"] == 1:                    # simulated mid-run failure
                raise RuntimeError("pod lost at step 5")
            restored = mgr.restore(
                init_train_state(params, cfg, opts),
                step=mgr.latest_step())
            # the round-trip is exact: every leaf incl. moments + residual
            for a, b in zip(jax.tree_util.tree_leaves(restored),
                            jax.tree_util.tree_leaves(state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            start = mgr.restore_meta()["meta"]["step"]
            return run_steps(restored, start, 6)

        from repro.train.resilience import FailureSupervisor
        final = FailureSupervisor(lambda: None, max_failures=2).attempt(attempt)
        for a, b in zip(jax.tree_util.tree_leaves(final),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_trainer_resume(self, tmp_path):
        cfg = OptimizerConfig(lr=1e-2, schedule="const")

        def loss(params, batch, rng):
            return jnp.mean((params["w"] - 1.0) ** 2), {}

        def batches():
            while True:
                yield {}

        state = init_train_state({"w": jnp.zeros(2)}, cfg)
        step = make_train_step(loss, cfg, TrainOptions(donate=False))
        mgr = CheckpointManager(str(tmp_path), save_interval=5,
                                async_write=False)
        t1 = Trainer(step, state, ckpt=mgr, log_fn=lambda *_: None)
        t1.run(batches(), n_steps=7)
        assert t1.step == 7
        t2 = Trainer(step, init_train_state({"w": jnp.zeros(2)}, cfg),
                     ckpt=mgr, log_fn=lambda *_: None)
        t2.resume_if_possible()
        assert t2.step == 7
        np.testing.assert_allclose(np.asarray(t2.state.params["w"]),
                                   np.asarray(t1.state.params["w"]))


class TestResilience:
    def test_straggler_flagging(self):
        mon = StragglerMonitor(4, threshold=1.5, patience=2)
        for step in range(5):
            times = {0: 1.0, 1: 1.0, 2: 1.0, 3: 5.0}
            rep = mon.update(step, times)
        assert rep.stragglers == [3]
        assert rep.worst_ratio > 1.5

    def test_no_false_positives(self):
        mon = StragglerMonitor(4)
        for step in range(10):
            rep = mon.update(step, {h: 1.0 + 0.01 * h for h in range(4)})
        assert rep.stragglers == []

    def test_failure_supervisor_recovers(self):
        calls = {"n": 0, "recovered": 0}

        def recover():
            calls["recovered"] += 1

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("pod lost")
            return "done"

        sup = FailureSupervisor(recover, max_failures=5)
        assert sup.attempt(flaky) == "done"
        assert calls["recovered"] == 2

    def test_failure_supervisor_budget(self):
        sup = FailureSupervisor(lambda: None, max_failures=2)
        with pytest.raises(RuntimeError):
            sup.attempt(lambda: (_ for _ in ()).throw(RuntimeError("x")))
