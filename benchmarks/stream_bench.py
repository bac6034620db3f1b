"""Continual-training benchmark: periodic full retrain vs incremental DTI.

    PYTHONPATH=src python -m benchmarks.stream_bench [--smoke] \
        [--json BENCH_stream.json] [--trace trace_stream.json]

Production histories never stop growing, so the paper's O(m·n²)-vs-O(m·n)
training-cost argument is really about *retraining*. This bench replays
one interaction event stream (``repro.data.requests.make_event_stream``)
three ways, all starting from the same warm-corpus base model and all
measured on the same held-out chronological tail:

  * ``full_sw``    — periodic full retrain, sliding-window prompts: at each
    retrain point, rebuild one-prompt-per-target over the ENTIRE history so
    far and train an epoch from the base params. O(m·n²) per retrain.
  * ``full_dti``   — periodic full retrain, batch DTI (k-target streaming
    prompts, packed): the paper's training paradigm, applied the only way
    the pre-stream repo could — from scratch over the full corpus.
  * ``stream_dti`` — incremental streaming DTI (``repro.stream``): per tick,
    ``IncrementalDTI.extend_prompts`` emits rows supervising only the newly
    arrived targets, the async ``StreamPipeline`` packs them into fixed-
    shape batches, and the ``OnlineTrainer`` fine-tunes in place. O(Δm·(n+k))
    per tick.

Reported per mode: supervised tokens pushed through train steps (the cost
axis), time-to-freshness per tick (simulated clock advanced only by
measured build+train time: seconds from a tick's arrival until all its
targets are trained), and AUC/logloss over time on the holdout (the
quality axis). The headline ``token_reduction`` (full retrain tokens /
streaming tokens) is the continual-setting analog of the paper's 92%
single-pass reduction; the acceptance bar is ≥5x (tests/test_stream.py).

All three modes train the same DTI model config — this compares
*retraining strategies*, not attention paradigms (that is Table 3 /
``benchmarks.table3_training_time``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.dti import (PromptStats, batch_prompts, build_sliding_prompts,
                            build_streaming_prompts, pack_prompts,
                            train_max_len)
from repro.core.metrics import ctr_metrics
from repro.data.requests import make_event_stream, warm_histories
from repro.data.synthetic import make_ctr_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.obs.trace import SpanTracer, validate_chrome_trace
from repro.serve.engine import make_prefill_fn
from repro.stream import (IncrementalDTI, OnlineTrainer, StreamPipeline,
                          make_stream_loss_fn)
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import init_train_state, make_train_step


class Evaluator:
    """One jitted prefill reused for every eval point of every mode."""

    def __init__(self, cfg, window, ds, *, n_ctx, end_frac, max_len,
                 batch: int = 32):
        self._prefill = jax.jit(make_prefill_fn(cfg, window=window))
        prompts, labels = [], []
        for u in range(len(ds.sequences)):
            toks, labs = ds.user_prompt_material(u)
            for i in range(max(int(len(toks) * end_frac), n_ctx), len(toks)):
                prompts += build_sliding_prompts(
                    toks[i - n_ctx: i + 1], labs[i - n_ctx: i + 1],
                    n_ctx=n_ctx, max_len=max_len)
                labels.append(int(labs[i]))
        self.labels = np.asarray(labels)
        self.batches = [
            {k: b[k] for k in ("tokens", "positions", "is_sum", "valid")}
            for b in batch_prompts(prompts, batch)]
        self._sums = [np.asarray([np.flatnonzero(b["is_sum"][i])[-1]
                                  for i in range(b["is_sum"].shape[0])])
                      for b in self.batches]

    def __call__(self, params) -> Dict[str, float]:
        scores = []
        for b, sums in zip(self.batches, self._sums):
            p = np.asarray(self._prefill(params, b))
            scores += [p[i, s] for i, s in enumerate(sums)]
        m = ctr_metrics(self.labels, np.asarray(scores[: len(self.labels)]))
        return {"auc": m["auc"], "log_loss": m["log_loss"]}


def _build_corpus(histories, visible: List[int], *, paradigm, n_ctx, k,
                  max_len, pack):
    prompts, stats = [], PromptStats()
    for (toks, labels), m in zip(histories, visible):
        if m <= n_ctx:
            continue
        build = (build_sliding_prompts if paradigm == "sw"
                 else build_streaming_prompts)
        kw = {} if paradigm == "sw" else {"k": k}
        prompts += build(toks[:m], labels[:m], n_ctx=n_ctx, max_len=max_len,
                         stats=stats, **kw)
    if pack and prompts:
        prompts = pack_prompts(prompts, max_len)
    return prompts, stats


def run_full_retrain(base_params, cfg, window, ds, ticks, *, paradigm,
                     n_ctx, k, max_len, batch, lr, retrain_every,
                     evaluator, seed):
    """Periodic full retrain: at every ``retrain_every``-th tick, rebuild
    the whole corpus seen so far and train one epoch from the base params.
    The simulated clock advances only by measured build+train time, so
    time-to-freshness is compute lag, not replay-harness overhead."""
    loss_fn = make_stream_loss_fn(cfg, window)
    histories = [ds.user_prompt_material(u)
                 for u in range(len(ds.sequences))]
    visible = [int(len(t) * _START_FRAC) for t, _ in histories]
    rng = np.random.default_rng(seed)
    # one jitted step reused across retrains: retraining from scratch means
    # fresh optimizer *state*, not a fresh compile
    ocfg = OptimizerConfig(lr=lr, schedule="const", warmup_steps=1,
                           total_steps=10_000)
    step_fn = make_train_step(loss_fn, ocfg)
    clock = 0.0
    arrivals, pending = [], []
    tokens = steps = retrains = 0
    freshness, auc_t = [], []
    state = None
    for t, tick in enumerate(ticks):
        arrivals.append(clock)
        pending.append(t)
        for ev in tick:
            visible[ev["user"]] = max(visible[ev["user"]], ev["index"] + 1)
        if (t + 1) % retrain_every and t + 1 != len(ticks):
            continue
        t0 = time.perf_counter()
        prompts, _ = _build_corpus(histories, visible, paradigm=paradigm,
                                   n_ctx=n_ctx, k=k, max_len=max_len,
                                   pack=paradigm != "sw")
        state = init_train_state(base_params, ocfg)
        for b in batch_prompts(prompts, batch, rng=rng):
            state, m = step_fn(state, b, jax.random.PRNGKey(steps))
            tokens += int(b["valid"].sum())
            steps += 1
        jax.block_until_ready(state.params)
        clock += time.perf_counter() - t0
        retrains += 1
        freshness += [clock - arrivals[p] for p in pending]
        pending = []
        auc_t.append({"tick": t, "clock_s": clock,
                      **evaluator(state.params)})
    return _mode_result(tokens, steps, clock, freshness, auc_t,
                        retrains=retrains)


def run_stream(base_params, cfg, window, ds, ticks, *, n_ctx, k, max_len,
               batch, lr, evaluator, seed, eval_every=1, tracer=None):
    # Smaller fixed batches than the offline epochs: a tick's rows rarely
    # fill an offline-sized batch, and padding-by-duplication is real
    # compute — the per-tick batch is the pipeline's freshness/efficiency
    # knob (docs/streaming.md).
    """Incremental streaming DTI through the real subsystem: per-tick
    StreamPipeline (async packing) feeding one persistent OnlineTrainer."""
    inc = IncrementalDTI(n_ctx=n_ctx, k=k, max_len=max_len)
    for u, (toks, labels) in enumerate(warm_histories(ds,
                                                      start_frac=_START_FRAC)):
        inc.seed_history(u, toks, labels, supervised=True)
    ocfg = OptimizerConfig(lr=lr, schedule="const", warmup_steps=1,
                           total_steps=10_000)
    ot = OnlineTrainer(make_stream_loss_fn(cfg, window), base_params, ocfg,
                       publish_every=0, window_targets=128, tracer=tracer)
    clock = 0.0
    tokens = slots = 0
    freshness, auc_t = [], []
    for t, tick in enumerate(ticks):
        arrival = clock
        t0 = time.perf_counter()
        pipe = StreamPipeline(iter([tick]), inc, batch_size=batch,
                              tracer=tracer)
        ot.run(pipe.batches(), rng=jax.random.PRNGKey(seed + t))
        jax.block_until_ready(ot.state.params)
        clock += time.perf_counter() - t0
        freshness.append(clock - arrival)
        tokens += pipe.stats.n_tokens
        slots += pipe.stats.n_slots
        if (t + 1) % eval_every == 0 or t + 1 == len(ticks):
            auc_t.append({"tick": t, "clock_s": clock,
                          **evaluator(ot.state.params)})
    ot.flush_windows()
    out = _mode_result(tokens, ot.step, clock, freshness, auc_t)
    out["pad_fraction"] = 1.0 - tokens / max(slots, 1)
    out["drift_windows"] = len(ot.eval_windows)
    out["progressive_auc"] = ot.lifetime_auc.value()
    return out


def _mode_result(tokens, steps, clock, freshness, auc_t, **extra) -> Dict:
    f = np.asarray(freshness) if freshness else np.zeros(1)
    last = auc_t[-1] if auc_t else {"auc": 0.5, "log_loss": 0.0}
    return {"trained_tokens": int(tokens), "steps": int(steps),
            "train_time_s": clock,
            "freshness_mean_s": float(f.mean()),
            "freshness_p95_s": float(np.percentile(f, 95)),
            "auc_over_time": auc_t,
            "final_auc": last["auc"], "final_log_loss": last["log_loss"],
            **extra}


_START_FRAC = 0.5
_END_FRAC = 0.9


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small stream, same code paths)")
    ap.add_argument("--json", default=None, help="write results to this path")
    ap.add_argument("--users", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--n-ctx", type=int, default=6, dest="n_ctx")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--stream-batch", type=int, default=None,
                    dest="stream_batch",
                    help="per-tick batch size for the streaming mode "
                         "(default batch // 2)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--stream-lr", type=float, default=None, dest="stream_lr",
                    help="online fine-tune LR (default lr / 4: the stream "
                         "sees each target once, so continual updates run "
                         "gentler than from-scratch retrains)")
    ap.add_argument("--retrain-every", type=int, default=1,
                    dest="retrain_every",
                    help="full-retrain cadence in ticks; 1 = retrain on "
                         "every tick (freshness policy matched to streaming)")
    ap.add_argument("--warm-epochs", type=int, default=2, dest="warm_epochs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the stream_dti mode as a Chrome-trace "
                         "JSON (stream.tick packing spans from the worker "
                         "thread interleaved with online.step train "
                         "spans; see docs/observability.md); exits "
                         "nonzero on a schema-invalid or span-less trace")
    args = ap.parse_args(argv)

    users = args.users or (10 if args.smoke else 24)
    seq = args.seq or (40 if args.smoke else 60)
    ticks_n = args.ticks or (10 if args.smoke else 12)
    stream_batch = args.stream_batch or max(2, args.batch // 2)
    stream_lr = args.stream_lr or args.lr / 4

    cfg = get_arch("dti-llama").smoke
    ds = make_ctr_dataset(n_users=users, n_items=200, seq_len=seq,
                          vocab_size=cfg.vocab_size, seed=args.seed,
                          label_scale=5.0)
    window = 0                                  # dense full causal at scale
    sw_len = train_max_len(args.n_ctx, 1, ds.avg_item_tokens)
    dti_len = train_max_len(args.n_ctx, args.k, ds.avg_item_tokens)
    ticks = make_event_stream(ds, n_ticks=ticks_n, start_frac=_START_FRAC,
                              end_frac=_END_FRAC, seed=args.seed)
    evaluator = Evaluator(cfg, window, ds, n_ctx=args.n_ctx,
                          end_frac=_END_FRAC, max_len=sw_len)
    n_events = sum(len(t) for t in ticks)
    print(f"[stream_bench] {users} users, {n_events} events over {ticks_n} "
          f"ticks, k={args.k}, n_ctx={args.n_ctx}, "
          f"{len(evaluator.labels)} holdout targets")

    # one warm base model shared by every mode
    params0 = init_params(jax.random.PRNGKey(args.seed), cfg)
    histories = [ds.user_prompt_material(u) for u in range(users)]
    warm_vis = [int(len(t) * _START_FRAC) for t, _ in histories]
    warm, _ = _build_corpus(histories, warm_vis, paradigm="dti",
                            n_ctx=args.n_ctx, k=args.k, max_len=dti_len,
                            pack=True)
    wcfg = OptimizerConfig(lr=args.lr, schedule="const", warmup_steps=1,
                           total_steps=10_000)
    wstate = init_train_state(params0, wcfg)
    wstep = make_train_step(make_stream_loss_fn(cfg, window), wcfg)
    rng = np.random.default_rng(args.seed)
    for e in range(args.warm_epochs):
        for b in batch_prompts(warm, args.batch, rng=rng):
            wstate, _ = wstep(wstate, b, jax.random.PRNGKey(e))
    base_params = jax.device_get(wstate.params)
    print(f"[warm] base model: {evaluator(base_params)}")

    common = dict(n_ctx=args.n_ctx, k=args.k, batch=args.batch, lr=args.lr,
                  evaluator=evaluator, seed=args.seed)
    # tracer for the streaming mode only: its per-tick pipeline + online
    # steps are the subsystem under observation; the full-retrain modes
    # are cost references
    tracer = SpanTracer() if args.trace else None
    modes = {
        "full_sw": run_full_retrain(
            base_params, cfg, window, ds, ticks, paradigm="sw",
            max_len=sw_len, retrain_every=args.retrain_every, **common),
        "full_dti": run_full_retrain(
            base_params, cfg, window, ds, ticks, paradigm="dti",
            max_len=dti_len, retrain_every=args.retrain_every, **common),
        "stream_dti": run_stream(
            base_params, cfg, window, ds, ticks, max_len=dti_len,
            tracer=tracer,
            **dict(common, batch=stream_batch, lr=stream_lr)),
    }
    for name, m in modes.items():
        print(f"  {name:10s} {m['trained_tokens']:9d} tok  "
              f"{m['steps']:4d} steps  train {m['train_time_s']:6.1f}s  "
              f"fresh p95 {m['freshness_p95_s']:6.2f}s  "
              f"AUC {m['final_auc']:.4f}")

    reduction = {
        name: modes[name]["trained_tokens"]
        / max(modes["stream_dti"]["trained_tokens"], 1)
        for name in ("full_sw", "full_dti")}
    print(f"  token reduction (full / streaming): {reduction}")

    result = {
        "config": {"arch": cfg.name, "users": users, "seq": seq,
                   "ticks": ticks_n, "events": n_events, "k": args.k,
                   "n_ctx": args.n_ctx, "batch": args.batch,
                   "stream_batch": stream_batch, "lr": args.lr,
                   "stream_lr": stream_lr,
                   "retrain_every": args.retrain_every,
                   "start_frac": _START_FRAC, "end_frac": _END_FRAC,
                   "smoke": bool(args.smoke)},
        "modes": modes,
        "token_reduction_vs_full_retrain": reduction,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"[stream_bench] wrote {args.json}")

    if args.trace:
        # export first, then gate: a trace missing the pipeline's packing
        # spans or the trainer's step spans means the streaming
        # instrumentation regressed, and CI must notice
        tracer.save(args.trace)
        doc = tracer.to_chrome_trace()
        problems = validate_chrome_trace(doc)
        names_x = {e["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "X"}
        if "stream.tick" not in names_x:
            problems.append("no stream.tick span")
        if "online.step" not in names_x:
            problems.append("no online.step span")
        print(f"[stream_bench] wrote {args.trace} "
              f"({len(tracer)} events, {len(problems)} problems)")
        if problems:
            print(f"[stream_bench] INVALID TRACE: {'; '.join(problems)}",
                  file=sys.stderr)
            sys.exit(1)
    return result


if __name__ == "__main__":
    # the CLI run only: tests call main() in-process with the cache off
    enable_compile_cache()
    main()
