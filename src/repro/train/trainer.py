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

from repro.obs import compiles
from repro.obs.clock import monotonic
from repro.obs.metrics import MetricsRegistry
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
    through ``freeze_non_lora``, so only the adapter factors get grads.
    The returned step carries ``loss_fn.attn_band`` (or None) as its own
    ``attn_band``, which ``Trainer`` reads for its ``winattn.*`` metrics."""
    attn_band = getattr(loss_fn, "attn_band", None)
    if opt_cfg.trainable == "lora":
        base_loss = loss_fn

        def loss_fn(params, batch, rng):
            return base_loss(freeze_non_lora(params), batch, rng)

    def step(state: TrainState, batch, rng):
        # named scopes mark each op's phase in the compiled program's
        # op_name metadata (train.grad: forward and backward; their
        # transpose(...) half is the backward), for device traces
        if options.grad_accum > 1:
            def micro(carry, mb):
                g_acc, l_acc, rng = carry
                rng, sub = jax.random.split(rng)
                with jax.named_scope("train.grad"):
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
            with jax.named_scope("train.grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, batch, rng)

        with jax.named_scope("train.optimizer"):
            ef_error = state.ef_error
            if options.compress_grads:
                grads, ef_error = ef_compress_grads(grads, ef_error)
            params, opt, stats = adamw_update(opt_cfg, grads, state.opt,
                                              state.params)
        metrics = dict(metrics or {})
        metrics.update(loss=loss, **stats)
        return TrainState(params, opt, ef_error), metrics

    if jit:
        kw = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        step = jax.jit(step, donate_argnums=(0,) if options.donate else (),
                       **kw)
    step.attn_band = attn_band
    return step


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

    Observability: each step is a ``train.step`` span (the rng split and
    the call, ``train.dispatch``, then the wait for its loss,
    ``train.wait``) with siblings ``train.feed`` (the next batch),
    ``train.fetch`` (the metrics read to the host and recorded) and
    ``train.checkpoint``, each carrying ``step``; no host work between
    steps falls outside them. ``metrics`` counts ``train.steps`` and,
    from batches of NumPy arrays (no device read), ``train.targets``
    (``is_sum``), ``train.tokens`` (row tokens, pad included) and
    ``train.pad_tokens`` (``~valid``); the compile watch adds
    ``jit.compiles`` / ``jit.compile_s`` and ``jit.compile`` spans. A step
    with an ``attn_band`` (a Pallas-attention LM's, ``make_train_step``)
    sets gauges ``winattn.grid_steps`` / ``winattn.live_steps`` once, for
    the first batch's row length: the windowed kernels' grid steps and
    the steps that run their body, per (row, head) and call. From each
    NumPy batch's ``is_sum`` it counts ``winattn.tile_steps``, the live
    (query sub-tile, kv step) pairs of each call per head, and
    ``winattn.sum_tile_steps``, those of them that run the SUM stream.
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
    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)

    def __post_init__(self):
        compiles.watch(self)

    def _count(self, batch) -> None:
        m = self.metrics
        m.counter("train.steps").inc()
        is_sum = batch.get("is_sum") if isinstance(batch, dict) else None
        valid = batch.get("valid") if isinstance(batch, dict) else None
        if isinstance(is_sum, np.ndarray):
            m.counter("train.targets").inc(int(is_sum.sum()))
        if isinstance(valid, np.ndarray):
            m.counter("train.tokens").inc(int(valid.size))
            m.counter("train.pad_tokens").inc(int(valid.size - valid.sum()))
        band = getattr(self.step_fn, "attn_band", None)
        tokens = batch.get("tokens") if isinstance(batch, dict) else None
        if band is None or tokens is None:
            return
        geo = band(tokens.shape[1])
        if not m.names("winattn.grid_steps"):
            grid, live = geo.steps()
            m.gauge("winattn.grid_steps").set(grid)
            m.gauge("winattn.live_steps").set(live)
        if isinstance(is_sum, np.ndarray):
            n_sum, n_all = geo.tile_steps(is_sum)
            m.counter("winattn.sum_tile_steps").inc(n_sum)
            m.counter("winattn.tile_steps").inc(n_all)

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
        tracer = self.tracer if self.tracer is not None else NULL_TRACER
        target = self.step + n_steps
        batches = iter(batches)
        while self.step < target:
            n = self.step + 1
            with tracer.span("train.feed", step=n):
                batch = next(batches, None)
            if batch is None:
                break
            t0 = monotonic()
            with tracer.span("train.step", step=n):
                with tracer.span("train.dispatch", step=n):
                    if rng is None:
                        rng = jax.random.PRNGKey(0)
                    rng, sub = jax.random.split(rng)
                    self.state, metrics = self.step_fn(self.state, batch, sub)
                with tracer.span("train.wait", step=n):
                    jax.block_until_ready(metrics["loss"])
            dt = monotonic() - t0
            if self.compile_s is None:
                self.compile_s = dt
            else:
                self.steady_s += dt
                self.steady_steps += 1
            self.step = n
            with tracer.span("train.fetch", step=n):
                self._count(batch)
                rec = {k: float(v) for k, v in metrics.items()}
                rec.update(step=n, sec=dt)
                self.history.append(rec)
                if self.monitor is not None:
                    times = host_time_fn(n, dt) if host_time_fn else {0: dt}
                    report = self.monitor.update(n, times)
                    if report.stragglers:
                        self.log_fn(f"[straggler] step {n}: "
                                    f"hosts {report.stragglers} "
                                    f"worst/median={report.worst_ratio:.2f}")
                if n % self.log_every == 0:
                    self.log_fn(f"[step {n}] loss={rec['loss']:.4f} "
                                f"lr={rec.get('lr', 0):.2e} {dt*1e3:.0f}ms")
            if self.ckpt is not None:
                with tracer.span("train.checkpoint", step=n):
                    self.ckpt.maybe_save(n, self.state, meta={"step": n})
        if self.ckpt is not None:
            self.ckpt.save(self.step, self.state, meta={"step": self.step},
                           block=True)
        return self.history


__all__ = ["TrainState", "TrainOptions", "init_train_state",
           "make_train_step", "Trainer"]
