"""Training loop: jitted step factory + orchestration (checkpoint, straggler
monitoring, failure recovery, grad accumulation, gradient compression).

``make_train_step`` builds one jitted function from any
``loss_fn(params, batch, rng) -> (loss, metrics)``; the same factory serves
the DTI LM, the sliding-window baseline, recsys and GNN archs (they differ
only in loss_fn), so every paradigm shares one runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.clock import monotonic
from repro.obs.trace import NULL_TRACER
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import (OptimizerConfig, OptState, adamw_update,
                                   ef_compress_grads, freeze_non_lora,
                                   init_opt_state)
from repro.train.resilience import StragglerMonitor


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    ef_error: Optional[Any]      # error-feedback residual (compression on)


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    grad_accum: int = 1
    compress_grads: bool = False
    donate: bool = True


def init_train_state(params, opt_cfg: OptimizerConfig,
                     options: TrainOptions = TrainOptions()) -> TrainState:
    ef = None
    if options.compress_grads:
        ef = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
    return TrainState(params, init_opt_state(opt_cfg, params), ef)


def make_train_step(loss_fn: Callable, opt_cfg: OptimizerConfig,
                    options: TrainOptions = TrainOptions(),
                    in_shardings=None, out_shardings=None, jit: bool = True):
    """loss_fn(params, batch, rng) -> (loss, metrics-dict).

    With ``opt_cfg.trainable == "lora"`` the loss sees the base weights
    through ``freeze_non_lora``, so only the adapter factors get grads."""
    if opt_cfg.trainable == "lora":
        base_loss = loss_fn

        def loss_fn(params, batch, rng):
            return base_loss(freeze_non_lora(params), batch, rng)

    def step(state: TrainState, batch, rng):
        if options.grad_accum > 1:
            def micro(carry, mb):
                g_acc, l_acc, rng = carry
                rng, sub = jax.random.split(rng)
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb, sub)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss, rng), None

            mb = jax.tree_util.tree_map(
                lambda x: x.reshape(options.grad_accum,
                                    x.shape[0] // options.grad_accum,
                                    *x.shape[1:]), batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss, _), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32), rng), mb)
            n = float(options.grad_accum)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            loss = loss / n
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch, rng)

        ef_error = state.ef_error
        if options.compress_grads:
            grads, ef_error = ef_compress_grads(grads, ef_error)

        params, opt, stats = adamw_update(opt_cfg, grads, state.opt,
                                          state.params)
        metrics = dict(metrics or {})
        metrics.update(loss=loss, **stats)
        return TrainState(params, opt, ef_error), metrics

    if not jit:
        return step
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return jax.jit(step, donate_argnums=(0,) if options.donate else (), **kw)


@dataclasses.dataclass
class Trainer:
    """Step-loop orchestration with checkpoint/restart + straggler signals.

    Timing discipline: the first executed step pays XLA compilation, so
    folding it into throughput makes tok/s lie on short runs. The loop
    records it separately (``compile_s``) from the steady-state
    accumulators (``steady_s`` / ``steady_steps``); ``timing()`` reports
    both, and ``launch.train`` derives steady tokens/s from the steady
    half only. Per-step ``sec`` entries in ``history`` are unchanged
    (the first record still carries its compile-inclusive duration).
    """
    step_fn: Callable
    state: TrainState
    ckpt: Optional[CheckpointManager] = None
    monitor: Optional[StragglerMonitor] = None
    log_every: int = 10
    log_fn: Callable[[str], None] = print
    tracer: Any = None                 # repro.obs.trace.SpanTracer or None

    step: int = 0
    history: list = dataclasses.field(default_factory=list)
    compile_s: Optional[float] = None  # first executed step (compile+run)
    steady_s: float = 0.0              # sum of post-compile step times
    steady_steps: int = 0

    def timing(self) -> Dict[str, float]:
        """Compile-vs-steady split of this trainer's executed steps:
        ``compile_s`` (first step, XLA compile included), ``step_s``
        (mean steady-state step) and ``steady_steps`` (how many steps
        back that mean)."""
        step_s = self.steady_s / self.steady_steps if self.steady_steps \
            else 0.0
        return {"compile_s": float(self.compile_s or 0.0),
                "step_s": step_s, "steady_steps": self.steady_steps}

    def resume_if_possible(self):
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            self.state = self.ckpt.restore(self.state)
            self.step = self.ckpt.restore_meta()["step"]
            self.log_fn(f"[trainer] resumed from step {self.step}")

    def run(self, batches: Iterator, *, n_steps: int, rng=None,
            host_time_fn: Optional[Callable[[int, float], Dict[int, float]]] = None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        tracer = self.tracer if self.tracer is not None else NULL_TRACER
        target = self.step + n_steps
        for batch in batches:
            if self.step >= target:
                break
            rng, sub = jax.random.split(rng)
            t0 = monotonic()
            with tracer.span("train.step", step=self.step + 1):
                self.state, metrics = self.step_fn(self.state, batch, sub)
                jax.block_until_ready(metrics["loss"])
            dt = monotonic() - t0
            if self.compile_s is None:
                self.compile_s = dt
            else:
                self.steady_s += dt
                self.steady_steps += 1
            self.step += 1
            rec = {k: float(v) for k, v in metrics.items()}
            rec.update(step=self.step, sec=dt)
            self.history.append(rec)
            if self.monitor is not None:
                times = (host_time_fn(self.step, dt) if host_time_fn
                         else {0: dt})
                report = self.monitor.update(self.step, times)
                if report.stragglers:
                    self.log_fn(f"[straggler] step {self.step}: "
                                f"hosts {report.stragglers} "
                                f"worst/median={report.worst_ratio:.2f}")
            if self.ckpt is not None:
                self.ckpt.maybe_save(self.step, self.state,
                                     meta={"step": self.step})
            if self.step % self.log_every == 0:
                self.log_fn(f"[step {self.step}] loss={rec['loss']:.4f} "
                            f"lr={rec.get('lr', 0):.2e} {dt*1e3:.0f}ms")
        if self.ckpt is not None:
            self.ckpt.save(self.step, self.state, meta={"step": self.step},
                           block=True)
        return self.history


__all__ = ["TrainState", "TrainOptions", "init_train_state",
           "make_train_step", "Trainer"]
