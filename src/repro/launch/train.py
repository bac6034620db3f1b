"""End-to-end training driver (CLI).

    PYTHONPATH=src python -m repro.launch.train \
        --arch dti-llama --paradigm dti --k 10 --steps 200

Trains the paper's CTR LLM (the CPU-scale REPRO config by default) on the
synthetic MovieLens-like corpus with either training paradigm:

  * ``--paradigm sw``   — sliding-window baseline (1 target / prompt)
  * ``--paradigm dti``  — streaming prompts with k targets (+ windowed
    causal attention, [SUM] loss, hidden-state reset, SUM NoPE+ALiBi)
  * ``--paradigm dti-`` — DTI without the two bottleneck fixes (ablation)

``--pack`` bin-packs prompts into shared segment-isolated rows (fewer,
denser rows per epoch; docs/batch_schema.md). ``--attn-impl pallas``
trains through the fused windowed-attention kernel's custom VJP
(docs/kernels.md); banded impls get a finite window automatically when
the config's is 0 (``effective_window``).

Non-LM archs (--arch gin-tu / din / ...) train their smoke config on the
matching synthetic generator — every assigned architecture is runnable
end-to-end from this one driver.

Checkpointing (atomic, keep-k, resumable), straggler monitoring and the
full evaluation (AUC / LogLoss / F1) are always on; this is the same
runtime the production mesh would run, minus the mesh.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.dti import (PromptStats, SpecialTokens, batch_prompts,
                            build_sliding_prompts, build_streaming_prompts,
                            effective_window, pack_prompts, train_max_len,
                            window_tokens)
from repro.core.losses import ctr_loss
from repro.core.metrics import ctr_metrics
from repro.data.synthetic import make_ctr_dataset, split_users
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import (ModelConfig, attn_band, forward,
                                      init_params)
from repro.obs.clock import monotonic
from repro.serve.engine import make_prefill_fn
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptimizerConfig
from repro.train.resilience import StragglerMonitor
from repro.train.trainer import (TrainOptions, Trainer, init_train_state,
                                 make_train_step)

SP = SpecialTokens()


# ---------------------------------------------------------------------------
# LM CTR training (the paper)
# ---------------------------------------------------------------------------

def build_prompt_sets(ds, splits, *, paradigm: str, n_ctx: int, k: int,
                      max_len: int):
    """-> (train_prompts, stats), eval prompt builder uses SW always."""
    train, _, test = splits
    stats = PromptStats()
    train_prompts: List[Dict[str, np.ndarray]] = []
    for toks, labels in train:
        if len(toks) <= n_ctx:
            continue
        if paradigm == "sw":
            train_prompts += build_sliding_prompts(
                toks, labels, n_ctx=n_ctx, max_len=max_len, stats=stats)
        else:
            train_prompts += build_streaming_prompts(
                toks, labels, n_ctx=n_ctx, k=k, max_len=max_len, stats=stats)
    test_prompts, test_labels = [], []
    for toks, labels, start in test:
        for i in range(max(start, n_ctx), len(toks)):
            p = build_sliding_prompts(toks[i - n_ctx:i + 1],
                                      labels[i - n_ctx:i + 1],
                                      n_ctx=n_ctx, max_len=max_len)
            test_prompts += p
            test_labels.append(int(labels[i]))
    return train_prompts, test_prompts, np.asarray(test_labels), stats


def make_lm_loss_fn(cfg: ModelConfig, window: int):
    """Loss over the canonical batch schema; consumes packed rows whenever
    the batch carries ``segment_ids`` (cross-segment isolation happens in
    the attention mask, the [SUM] loss itself is position-local)."""
    def loss_fn(params, batch, rng):
        out = forward(params, cfg, batch["tokens"],
                      positions=batch["positions"], is_sum=batch["is_sum"],
                      valid=batch["valid"],
                      segment_ids=batch.get("segment_ids"),
                      dti_enabled=cfg.dti_sum_token, window=window)
        with jax.named_scope("lm.loss"):
            loss, _ = ctr_loss(params, cfg, out["hidden"], batch["is_sum"],
                               batch["labels"], yes_id=SP.yes, no_id=SP.no)
        return loss + out["aux_loss"], {}
    if cfg.attn_impl == "pallas":
        # the windowed kernels' band at a row length, for the trainer's
        # winattn.* metrics (make_train_step carries it to the step); the
        # trainer asks for it every batch, and rows keep a few lengths
        loss_fn.attn_band = functools.lru_cache(maxsize=8)(
            functools.partial(attn_band, cfg, window))
    return loss_fn


def evaluate_lm(params, cfg: ModelConfig, window: int, test_prompts,
                test_labels, *, batch_size: int = 32) -> Dict[str, float]:
    prefill = jax.jit(make_prefill_fn(cfg, yes_id=SP.yes, no_id=SP.no,
                                      window=window))
    scores = []
    for batch in batch_prompts(test_prompts, batch_size):
        p = np.asarray(prefill(params, {k: batch[k] for k in
                                        ("tokens", "positions", "is_sum",
                                         "valid")}))
        for i in range(p.shape[0]):
            sums = np.flatnonzero(batch["is_sum"][i])
            scores.append(p[i, sums[-1]] if len(sums) else 0.5)
    scores = np.asarray(scores[: len(test_labels)])
    return ctr_metrics(test_labels, scores)


def run_lm(args) -> Dict:
    arch = get_arch(args.arch)
    cfg = arch.smoke if args.size == "smoke" else arch.config
    if args.paradigm == "sw":
        cfg = dataclasses.replace(cfg, dti_reset=False, dti_sum_alibi=False)
    elif args.paradigm == "dti-":
        cfg = dataclasses.replace(cfg, dti_reset=False, dti_sum_alibi=False)
    if args.attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)

    ds = make_ctr_dataset(n_users=args.users, n_items=args.items,
                          seq_len=args.seq, vocab_size=cfg.vocab_size,
                          seed=args.seed)
    splits = split_users(ds)
    n_tok = window_tokens(args.n_ctx, ds.avg_item_tokens)
    window = 0 if cfg.window == 0 else n_tok
    eff = effective_window(cfg.attn_impl, window, args.n_ctx,
                           ds.avg_item_tokens)
    if eff != window:
        print(f"[attn] {cfg.attn_impl} path: window 0 -> {eff} tokens")
        window = eff
    max_len = train_max_len(args.n_ctx,
                            1 if args.paradigm == "sw" else args.k,
                            ds.avg_item_tokens)
    train_prompts, test_prompts, test_labels, stats = build_prompt_sets(
        ds, splits, paradigm=args.paradigm, n_ctx=args.n_ctx, k=args.k,
        max_len=max_len)
    print(f"[data] {stats.n_prompts} train prompts, {stats.n_tokens} tokens, "
          f"{stats.n_targets} targets; window={window} max_len={max_len} "
          f"pad_fraction={stats.pad_fraction:.3f}")
    if args.pack:
        pstats = PromptStats()
        train_prompts = pack_prompts(train_prompts, max_len, stats=pstats)
        print(f"[pack] {pstats.n_prompts} prompts -> {pstats.n_rows} rows, "
              f"pad_fraction {stats.pad_fraction:.3f} -> "
              f"{pstats.pad_fraction:.3f}")
        stats = pstats

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    # the arch's PEFT recipe applies where the config carries adapters; a
    # config without them (dti-llama's CPU REPRO) is a full fine-tune
    ocfg = OptimizerConfig(lr=args.lr, schedule="cosine",
                           warmup_steps=max(10, args.steps // 10),
                           total_steps=args.steps,
                           trainable=arch.trainable if cfg.lora_rank else None)
    loss_fn = make_lm_loss_fn(cfg, window)
    state = init_train_state(params, ocfg)
    step_fn = make_train_step(loss_fn, ocfg)

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=2,
                                 save_interval=max(50, args.steps // 4))
    trainer = Trainer(step_fn, state, ckpt=ckpt,
                      monitor=StragglerMonitor(1), log_every=args.log_every)
    trainer.resume_if_possible()

    rng = np.random.default_rng(args.seed)

    def batches():
        while True:
            yield from batch_prompts(train_prompts, args.batch, rng=rng,
                                     drop_remainder=False)

    t0 = monotonic()
    trainer.run(batches(), n_steps=args.steps)
    train_time = monotonic() - t0

    metrics = evaluate_lm(trainer.state.params, cfg, window, test_prompts,
                          test_labels)
    # compile-vs-steady split (repro.obs / Trainer.timing): short runs
    # fold the first step's XLA compile into wall time, so headline
    # tok/s comes from the steady half only
    timing = trainer.timing()
    steady_tok_s = (args.batch * max_len * (1 - stats.pad_fraction)
                    / timing["step_s"] if timing["step_s"] else 0.0)
    result = {"paradigm": args.paradigm, "k": args.k,
              "train_time_s": train_time, "steps": trainer.step,
              "compile_s": timing["compile_s"],
              "steady_step_s": timing["step_s"],
              "steady_tokens_per_s": steady_tok_s,
              "prompts": stats.n_prompts, "train_tokens": stats.n_tokens,
              "packed": bool(args.pack),
              "pad_fraction": stats.pad_fraction,
              **metrics}
    print(f"[timing] compile {timing['compile_s']:.2f}s, steady step "
          f"{timing['step_s']*1e3:.0f}ms x {timing['steady_steps']} "
          f"({steady_tok_s:.0f} tok/s)")
    print(f"[result] {result}")
    return result


# ---------------------------------------------------------------------------
# non-LM archs: train the smoke config on synthetic data
# ---------------------------------------------------------------------------

def run_other(args) -> Dict:
    from repro.launch.smoke import train_smoke
    result = train_smoke(args.arch, steps=args.steps, batch=args.batch,
                         seed=args.seed, lr=args.lr)
    print(f"[result] {result}")
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dti-llama")
    ap.add_argument("--paradigm", default="dti",
                    choices=["sw", "dti", "dti-"])
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--pack", action="store_true",
                    help="bin-pack prompts into shared rows (segment-aware)")
    ap.add_argument("--attn-impl", default=None, dest="attn_impl",
                    choices=["dense", "blocked", "pallas"],
                    help="override the config's attention path (pallas = "
                         "fused kernel, fwd AND bwd via its custom VJP)")
    ap.add_argument("--n-ctx", type=int, default=10, dest="n_ctx")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--users", type=int, default=48)
    ap.add_argument("--items", type=int, default=300)
    ap.add_argument("--seq", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=20)
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    if get_arch(args.arch).family == "lm":
        run_lm(args)
    else:
        run_other(args)


if __name__ == "__main__":
    main()
