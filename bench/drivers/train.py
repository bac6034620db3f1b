"""A training cell: ``Trainer.run`` over the LoRA DTI step of
``repro.launch.train`` (``make_train_step(make_lm_loss_fn(cfg, window))``
with ``trainable="lora"``), fed the batches of the mix's generator.

Set-up builds the one trainer, warms it through its first three steps
(the first compiles) on the window's own call and feed, and keeps host
copies of the adapters' optimizer state after steps 1 and 3 for the
comparison. The window then runs the same trainer on an iterator that
stops yielding batches once ``--seconds`` have passed.
``train_targets_per_s`` is the supervised [SUM] targets of every step the
window ran over the window's wall time, from its start to the end of its
last step.

A generator of a training mix (``bench/traffic/<generator>.py``) gives
``geometry(mix) -> {"max_len", "window"}`` and
``batches(mix, vocab, rows, seed) -> [batch]``, a batch being a dict of
``(rows, max_len)`` arrays: tokens, positions, segment_ids, is_sum,
labels, valid.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import harness, weights
from bench.harness import log
from bench.reference import check

WARM_STEPS = 3          # set-up's steps; the reference follows them


def _lora_host(tree) -> Dict:
    """Host float32 copies of the adapter leaves of ``tree``."""
    from bench.reference.lm import split_lora
    lo, _ = split_lora(tree)
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), lo)


class Feed:
    """Cycles the corpus; records which batch each yield was."""

    def __init__(self, batches):
        self.batches = batches
        self.i = 0
        self.used = []

    def _next(self):
        b = self.batches[self.i % len(self.batches)]
        self.used.append(self.i % len(self.batches))
        self.i += 1
        return b

    def take(self, n):
        for _ in range(n):
            yield self._next()

    def until(self, deadline: float):
        while time.perf_counter() < deadline:
            yield self._next()


def _planted(step, fault):
    """A broken step for the tests of ``correct``."""
    if fault == "state_unchanged":
        def broken(state, batch, rng):
            _, metrics = step(jax.tree_util.tree_map(jax.numpy.copy, state),
                              batch, rng)
            return state, metrics
        return broken
    if fault == "half_batch":
        def broken(state, batch, rng):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half, rng)
        return broken
    raise ValueError(f"unknown fault {fault!r}")


@dataclasses.dataclass
class Warm:
    """The trainer after set-up, and what the comparison needs of it."""
    trainer: object
    step: object
    feed: Feed
    batches: List[dict]
    geometry: dict
    prog: dict                  # check.program_readings of the warm steps
    tracer: Optional[object]

    @property
    def warm_batches(self) -> List[dict]:
        return [self.batches[i] for i in self.feed.used[:WARM_STEPS]]


def warm_up(cell: harness.Cell, mcfg, device, tracer=None) -> Warm:
    """Build the cell's trainer from its seed and run its first steps."""
    from repro.launch.train import make_lm_loss_fn
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import (TrainOptions, Trainer, init_train_state,
                                     make_train_step)

    gen = harness.generator(cell)
    rows = cell.config["train"]["rows_per_step"]
    geo = gen.geometry(cell.mix)
    batches = gen.batches(cell.mix, mcfg.vocab_size, rows, cell.seed)
    targets = [int(b["is_sum"].sum()) for b in batches]
    pads = [1.0 - float(b["valid"].mean()) for b in batches]
    log(f"[train] {cell.name}: {len(batches)} batches x {rows} rows x "
        f"{geo['max_len']} tokens, window {geo['window']}, targets/batch "
        f"{min(targets)}..{max(targets)}, pad share "
        f"{min(pads):.4f}..{max(pads):.4f}")

    params = weights.make_params(mcfg, cell.seed)
    opt = cell.config["optimizer"]
    ocfg = OptimizerConfig(lr=opt["lr"], betas=tuple(opt["betas"]),
                           eps=opt["eps"], weight_decay=opt["weight_decay"],
                           grad_clip=opt["grad_clip"],
                           schedule=opt["schedule"],
                           warmup_steps=opt["warmup_steps"],
                           total_steps=opt["total_steps"], trainable="lora")
    master_0 = _lora_host(params)
    donate = cell.fault != "state_unchanged"
    step = make_train_step(make_lm_loss_fn(mcfg, geo["window"]), ocfg,
                           TrainOptions(donate=donate))
    timed = _planted(step, cell.fault) if cell.fault else step
    state = init_train_state(params, ocfg)
    del params
    harness.log_memory(device, "weights and optimizer state made")
    trainer = Trainer(timed, state, log_every=10 ** 9, log_fn=log,
                      tracer=tracer)
    feed = Feed(batches)
    trainer.run(feed.take(1), n_steps=1)
    mu_1 = _lora_host(trainer.state.opt.mu)
    trainer.run(feed.take(WARM_STEPS - 1), n_steps=WARM_STEPS - 1)
    master_3 = _lora_host(trainer.state.opt.master)
    first = [h["loss"] for h in trainer.history[:WARM_STEPS]]
    harness.log_memory(device, f"after {WARM_STEPS} steps")
    prog = check.program_readings(first, mu_1, master_0, master_3,
                                  opt["betas"][0])
    return Warm(trainer, step, feed, batches, geo, prog, tracer)


def follow_reference(cell: harness.Cell, mcfg, warm_batches, **kw) -> dict:
    """The plain reference over the warm steps' batches, from the seed's
    weights made anew (``kw``: the control or a fault in its place)."""
    params = weights.make_params(mcfg, cell.seed)
    follow = check.make_train_ref(
        harness.ref_config(cell), cell.config["optimizer"],
        harness.generator(cell).geometry(cell.mix)["window"],
        rows_per_block=cell.config["train"].get("ref_rows_per_block", 1),
        **kw)
    return follow(params, warm_batches)


def _log_memory_analysis(step, state_sds, batch) -> None:
    """The compiled step's own account of its memory (a cache hit)."""
    if not hasattr(step, "lower"):
        return
    m = step.lower(state_sds, batch, jax.random.PRNGKey(0)).compile() \
        .memory_analysis()
    if m is None:
        return
    log(f"[memory] compiled step: arguments {m.argument_size_in_bytes}, "
        f"outputs {m.output_size_in_bytes}, aliased {m.alias_size_in_bytes}, "
        f"temporaries {m.temp_size_in_bytes}, code "
        f"{m.generated_code_size_in_bytes} bytes")


def run(cell: harness.Cell, devices, t_start: float, counter) -> dict:
    from repro.obs.trace import SpanTracer

    mcfg = harness.model_config(cell)
    tracer = SpanTracer(jax_annotate=True) if cell.trace else None
    w = warm_up(cell, mcfg, devices[0], tracer)
    trainer, feed, batches = w.trainer, w.feed, w.batches
    setup_s = time.perf_counter() - t_start
    log(f"[train] set-up {setup_s:.3f}s; first step "
        f"{trainer.history[0]['sec']:.3f}s, then "
        f"{[round(h['sec'], 3) for h in trainer.history[1:]]}; losses "
        f"{w.prog['losses']}")

    # -- the window ---------------------------------------------------------
    n0 = trainer.step
    if tracer is not None:
        tracer.clear()
        jax.profiler.start_trace(cell.out_dir + "/trace")
    counter.armed = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        trainer.run(feed.until(t0 + cell.seconds), n_steps=10 ** 9)
        t1 = time.perf_counter()
    counter.armed = False
    if tracer is not None:
        jax.profiler.stop_trace()
    ran = feed.used[WARM_STEPS:]
    n_steps = trainer.step - n0
    assert n_steps == len(ran)
    window_s = t1 - t0
    done = sum(int(batches[i]["is_sum"].sum()) for i in ran)
    log(f"[train] window {window_s:.4f}s, {n_steps} steps "
        f"(mean {window_s / max(n_steps, 1):.4f}s), {done} targets, "
        f"{counter.count} compiles inside")
    peak = harness.peak_bytes(devices[0])
    harness.log_memory(devices[0], "after the window")
    state_sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state)
    spans = tracer.events() if tracer is not None else []
    epoch = tracer._epoch if tracer is not None else 0.0
    trainer.state = None
    w.trainer = trainer = None
    gc.collect()
    _log_memory_analysis(w.step, state_sds, batches[0])

    # -- correct --------------------------------------------------------------
    t_ref = time.perf_counter()
    ref = follow_reference(cell, mcfg, w.warm_batches)
    numbers = check.train_numbers(w.prog, ref)
    checks = harness.compared(numbers, cell.limits)
    log(f"[train] reference {time.perf_counter() - t_ref:.1f}s; losses "
        f"program {w.prog['losses']} reference {ref['losses']}; gaps by step "
        f"{check.later_loss_gaps(w.prog, ref)}")
    result = {
        "correct": checks["ok"], "attempted": n_steps, "failed": 0,
        "device": harness.device_info(devices, cell.workload["chips"], peak),
    }
    ctx = {"cell": cell, "kind": "train", "batches": batches,
           "window_batches": [batches[i] for i in ran],
           "window": w.geometry["window"], "window_s": window_s,
           "geometry": w.geometry, "spans": spans, "span_epoch": epoch,
           "device_kind": devices[0].device_kind, "log": log}
    if cell.trace:
        from bench import tracing
        tr = tracing.load(cell.out_dir + "/trace")
        ctx["trace"] = tr
        result["metrics"] = harness.run_readers(cell, ctx)
        result["device"]["busy_s"] = tracing.busy_s(tr)
        result["device"]["window_s"] = tracing.window_s(tr)
        on = tracing.spans_on_trace(spans, tr, t0 - epoch)
        result["breakdown"] = {"device_ops": tracing.top_ops(tr),
                               "idle_gaps": tracing.idle_gaps(tr, on)}
    else:
        result["metrics"] = {
            "train_targets_per_s": harness.metric(done / window_s, "targets/s"),
            "setup_s": harness.metric(setup_s, "s")}
    return result, checks
