"""Bring-up check on a TPU: the DTI trainer and the serving scheduler at
Qwen2-1.5B widths, through the entry points a user calls.

    python3 chip_smoke.py              # one chip: train phase, serve phase
    python3 chip_smoke.py --chips 4    # four chips: mesh-sharded serving only

The model is Qwen2-1.5B (``repro.configs.qwen2_1_5b.FULL``: all 28 layers at
the published widths) with the paper's LoRA rank-8 recipe on q, k, v, o,
gate, up and down, random weights from ``--seed``, and the fused Pallas
attention kernels compiled by Mosaic.

* train phase — ``repro.launch.train``'s loss / state / step / ``Trainer``
  at the trainer CLI's default k, n_ctx and batch on a small synthetic
  corpus. The first step's loss and LoRA grad-norm are checked against the
  blocked XLA attention path on the same params and batch.
* serve phase — a ``ServeScheduler`` on the Pallas decode kernel with a
  paged bf16 KV cache answers multi-target requests; every score is checked
  against the ``CTRServer.score`` oracle over sliding-window prompts.
* ``--chips 4`` — the same requests through a scheduler on a (2, 2)
  ``(data, model)`` mesh and through an unsharded one; scores must agree.

Everything runs in this one process. Any failed check raises, so the exit
code is nonzero. The last line of stdout is one JSON object naming the
device; without a TPU (or with the kernels set to interpret) the script
exits nonzero before printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.qwen2_1_5b import FULL  # noqa: E402
from repro.core.dti import (batch_prompts, build_sliding_prompts,  # noqa: E402
                            train_max_len, window_tokens)
from repro.data.requests import make_request_stream  # noqa: E402
from repro.data.synthetic import make_ctr_dataset, split_users  # noqa: E402
from repro.kernels import default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serve_mesh  # noqa: E402
from repro.launch.train import (build_parser, build_prompt_sets,  # noqa: E402
                                make_lm_loss_fn)
from repro.models.transformer import ModelConfig, init_params  # noqa: E402
from repro.serve.engine import CTRServer  # noqa: E402
from repro.serve.scheduler import ServeScheduler  # noqa: E402
from repro.train.optimizer import (OptimizerConfig,  # noqa: E402
                                   freeze_non_lora, trainable_grad_norm)
from repro.train.trainer import (Trainer, init_train_state,  # noqa: E402
                                 make_train_step)

# Relative |pallas - blocked| bounds (first-step loss, LoRA grad-norm) by
# compute dtype. fp32: the two paths differ only in summation order (online
# vs full softmax), ~1e-7 per op. bf16: the blocked path rounds its
# probabilities and outputs to bf16 (2^-9 relative) where the kernel keeps
# fp32. Over 28 layers at these widths that put the two 3.3e-3 apart on the
# loss and 7e-4 on the grad-norm (a sum over 56 adapters' backward passes)
# on a TPU v5e at seed 0, and 7e-4 / 3e-3 on another batch in a CPU run.
# The loss bound must also stay below what a dropped DTI term does: the
# run re-measures the loss without SUM isolation and without the
# hidden-state reset (1.0e-1 and 1.1e-2 on the v5e) and fails unless both
# exceed it. 6e-3 sits ~2x from the measured delta and ~2x from the
# smaller ablation; the grad-norm bound leaves ~3x over the CPU reading.
TRAIN_TOL = {"float32": (1e-4, 1e-4), "bfloat16": (6e-3, 1e-2)}
# |p - oracle| bound on a click probability, by compute dtype. The scheduler
# reads context K/V from a bf16 cache in chunks; the oracle re-encodes each
# sliding-window prompt in one dense prefill. Both round to bf16 (2^-9
# relative) at different points, compounding over the layers: 1.2e-2 apart
# at 28 layers in a CPU run at these widths and 7.8e-3 on a TPU v5e; the
# bound leaves ~4x over the larger.
# fp32 compute keeps only the bf16 cache's rounding.
SERVE_TOL = {"float32": 5e-3, "bfloat16": 5e-2}
# |sharded - unsharded| bound: the same math, with the model-axis all-reduce
# summing bf16 partials of the attention/FFN outputs in another order; over
# 28 layers that put them 1.2e-2 apart on 4 CPU devices at these widths.
# fp32 keeps only the summation order (1.5e-7 at SMOKE size).
MESH_TOL = {"float32": 1e-4, "bfloat16": 5e-2}


def lora_config(cfg: ModelConfig = FULL) -> ModelConfig:
    """The paper's recipe: LoRA rank 8 on every projection, Pallas path."""
    return dataclasses.replace(cfg, lora_rank=8, attn_impl="pallas")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def peak_hbm_bytes():
    """``peak_bytes_in_use`` of device 0 (None where the backend keeps no
    memory statistics)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def train_phase(cfg: ModelConfig, *, seed: int = 0, steps: int = 5,
                corpus=None, log=print) -> dict:
    """A few DTI train steps with LoRA, plus the pallas-vs-blocked check.
    ``corpus`` overrides the trainer CLI's defaults (users, items, seq,
    k, n_ctx, batch)."""
    d = vars(build_parser().parse_args([]))
    d.update(corpus or {})
    ds = make_ctr_dataset(n_users=d["users"], n_items=d["items"],
                          seq_len=d["seq"], vocab_size=cfg.vocab_size,
                          seed=seed)
    window = window_tokens(d["n_ctx"], ds.avg_item_tokens)
    max_len = train_max_len(d["n_ctx"], d["k"], ds.avg_item_tokens)
    prompts, _, _, _ = build_prompt_sets(
        ds, split_users(ds), paradigm="dti", n_ctx=d["n_ctx"], k=d["k"],
        max_len=max_len)
    gen = batch_prompts(prompts * steps, d["batch"],
                        rng=np.random.default_rng(seed))
    batches = [next(gen) for _ in range(steps)]

    ocfg = OptimizerConfig(lr=d["lr"], schedule="const", warmup_steps=1,
                           total_steps=steps, trainable="lora")
    params = init_params(jax.random.PRNGKey(seed), cfg)

    # the blocked XLA path on the same params and first batch, before the
    # donating train step consumes the params
    def loss_and_norm(c):
        loss_fn = make_lm_loss_fn(c, window)

        def f(p, batch):
            loss, g = jax.value_and_grad(
                lambda q: loss_fn(freeze_non_lora(q), batch, None)[0])(p)
            return loss, trainable_grad_norm(ocfg, g)
        return jax.jit(f)

    def loss_only(c):
        loss_fn = make_lm_loss_fn(c, window)
        return jax.jit(lambda p, batch: loss_fn(p, batch, None)[0])

    blocked = dataclasses.replace(cfg, attn_impl="blocked")
    ref_loss, ref_norm = (float(x) for x in
                          loss_and_norm(blocked)(params, batches[0]))
    ablation = {
        name: _rel(float(loss_only(dataclasses.replace(blocked, **kw))(
            params, batches[0])), ref_loss)
        for name, kw in (("no_sum_isolation", {"dti_sum_isolated": False}),
                         ("no_reset", {"dti_reset": False}))}

    loss_fn = make_lm_loss_fn(cfg, window)
    step_fn = make_train_step(loss_fn, ocfg)
    state = init_train_state(params, ocfg)
    lowered = step_fn.lower(state, batches[0], jax.random.PRNGKey(0))
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    trainer = Trainer(step_fn, state, log_every=max(steps, 1), log_fn=log)
    trainer.run(iter(batches), n_steps=steps)
    first = trainer.history[0]
    timing = trainer.timing()

    tol, tol_norm = TRAIN_TOL[cfg.compute_dtype]
    out = {
        "layers": cfg.n_layers, "d_model": cfg.d_model,
        "tokens_per_step": d["batch"] * max_len, "window": window,
        "compile_s": timing["compile_s"],
        "step_ms": timing["step_s"] * 1e3,
        "peak_hbm_bytes": peak_hbm_bytes(),
        "mosaic_calls": mosaic_calls,
        "loss": first["loss"], "loss_blocked": ref_loss,
        "loss_rel_delta": _rel(first["loss"], ref_loss),
        "lora_grad_norm": first["grad_norm"], "lora_grad_norm_blocked": ref_norm,
        "grad_norm_rel_delta": _rel(first["grad_norm"], ref_norm),
        "ablation_loss_rel_delta": ablation, "tol": tol,
        "tol_grad_norm": tol_norm,
        "final_loss": trainer.history[-1]["loss"],
    }
    log(f"[train] {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"lora_rank={cfg.lora_rank} attn={cfg.attn_impl} "
        f"mosaic_calls={mosaic_calls} compile {out['compile_s']:.1f}s "
        f"steady step {out['step_ms']:.1f}ms x{timing['steady_steps']} "
        f"tokens/step {out['tokens_per_step']} "
        f"peak_bytes_in_use {out['peak_hbm_bytes']}")
    log(f"[train] pallas vs blocked: loss {first['loss']:.6f} vs "
        f"{ref_loss:.6f} (rel {out['loss_rel_delta']:.2e}), lora grad-norm "
        f"{first['grad_norm']:.6e} vs {ref_norm:.6e} "
        f"(rel {out['grad_norm_rel_delta']:.2e}); tol {tol:g} / {tol_norm:g}; "
        "ablation "
        + " ".join(f"{k} {v:.2e}" for k, v in ablation.items()))
    for name in ("loss", "lora_grad_norm"):
        if not np.isfinite(out[name]):
            raise AssertionError(f"non-finite {name}: {out[name]}")
    if out["loss_rel_delta"] > tol or out["grad_norm_rel_delta"] > tol_norm:
        raise AssertionError(f"pallas and blocked disagree past "
                             f"{tol:g} / {tol_norm:g}")
    weak = {k: v for k, v in ablation.items() if v <= tol}
    if weak:
        raise AssertionError(f"tolerance {tol:g} cannot see {weak}")
    out["params"] = trainer.state.params
    return out


def _requests(cfg: ModelConfig, seed: int, n_requests: int, k: int,
              n_ctx: int):
    ds = make_ctr_dataset(n_users=16, n_items=120, seq_len=max(n_ctx, 12),
                          vocab_size=cfg.vocab_size, seed=seed)
    return make_request_stream(ds, n_requests=n_requests, k=k, n_ctx=n_ctx,
                               seed=seed)


def _drain(sched: ServeScheduler, requests):
    rids = [sched.submit(r["context"], r["candidates"]) for r in requests]
    results = sched.run()
    return np.asarray([results[r].scores for r in rids], np.float64)


def _scheduler(params, cfg, requests, buckets, mesh=None):
    ctx_len = max(1 + sum(len(t) for t in r["context"]) for r in requests)
    return ServeScheduler(params, cfg, n_slots=4,
                          capacity=ctx_len + max(buckets), window=cfg.window,
                          buckets=buckets, attn_impl="pallas",
                          cache_dtype=jnp.bfloat16, paged=True, mesh=mesh)


def serve_phase(cfg: ModelConfig, params, *, seed: int = 0,
                n_requests: int = 8, k: int = 4, n_ctx: int = 6,
                buckets=(16, 32), log=print) -> dict:
    """Scheduler scores vs the sliding-window ``CTRServer`` oracle."""
    requests = _requests(cfg, seed, n_requests, k, n_ctx)
    sched = _scheduler(params, cfg, requests, buckets)
    sched.warmup()
    scores = _drain(sched, requests)

    ctx_len = max(1 + sum(len(t) for t in r["context"]) for r in requests)
    cand_len = max(len(c) + 1 for r in requests for c in r["candidates"])
    max_len = -(-(ctx_len + cand_len) // 64) * 64
    oracle = CTRServer(params, dataclasses.replace(cfg, attn_impl="dense"),
                       max_len=max_len)
    ref = []
    for r in requests:
        prompts = []
        for cand in r["candidates"]:
            prompts += build_sliding_prompts(
                r["context"] + [cand], [0] * (len(r["context"]) + 1),
                n_ctx=len(r["context"]), max_len=max_len)
        ref.append(oracle.score(prompts))
    ref = np.asarray(ref, np.float64)

    tol = SERVE_TOL[cfg.compute_dtype]
    delta = float(np.max(np.abs(scores - ref)))
    compile_s = {b: round(v["compile_s"], 3)
                 for b, v in sched.jit_stats().items()}
    log(f"[serve] {len(requests)} requests x {k} candidates, buckets "
        f"{tuple(buckets)}, paged bf16 KV, decode attn=pallas; bucket "
        f"compile_s {compile_s}; max |p - oracle| {delta:.3e} (tol {tol:g})")
    if not np.all(np.isfinite(scores)):
        raise AssertionError("non-finite scheduler scores")
    if delta > tol:
        raise AssertionError(f"scheduler scores drift {delta:.3e} > {tol:g} "
                             "from the oracle")
    return {"requests": len(requests), "max_abs_delta": delta, "tol": tol,
            "bucket_compile_s": compile_s}


def mesh_phase(cfg: ModelConfig, *, seed: int = 0, dp: int = 2, mp: int = 2,
               n_requests: int = 8, k: int = 4, n_ctx: int = 6,
               buckets=(16, 32), log=print) -> dict:
    """The same requests through a (dp, mp)-mesh scheduler and an
    unsharded one."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    requests = _requests(cfg, seed, n_requests, k, n_ctx)
    mesh = make_serve_mesh(dp, mp)
    sharded = _scheduler(params, cfg, requests, buckets, mesh=mesh)
    n_dev = {len(x.sharding.device_set) for tree in (sharded.cache,
                                                     sharded.params)
             for x in jax.tree_util.tree_leaves(tree)}
    tp_leaves = sum("model" in str(x.sharding.spec)
                    for x in jax.tree_util.tree_leaves(sharded.params))
    sharded.warmup()
    got = _drain(sharded, requests)
    plain = _scheduler(params, cfg, requests, buckets)
    plain.warmup()
    want = _drain(plain, requests)

    tol = MESH_TOL[cfg.compute_dtype]
    delta = float(np.max(np.abs(got - want)))
    log(f"[mesh] ({dp}, {mp}) mesh: cache+param leaves span {sorted(n_dev)} "
        f"devices, {tp_leaves} param leaves split over 'model'; "
        f"{len(requests)} requests; max |sharded - unsharded| {delta:.3e} "
        f"(tol {tol:g})")
    if n_dev != {dp * mp}:
        raise AssertionError(f"sharded state spans {n_dev} devices, "
                             f"not {dp * mp}")
    if not tp_leaves:
        raise AssertionError("no parameter is split over the model axis")
    if not np.all(np.isfinite(got)) or delta > tol:
        raise AssertionError(f"sharded scores drift {delta:.3e} > {tol:g}")
    return {"requests": len(requests), "max_abs_delta": delta, "tol": tol,
            "devices": dp * mp}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh-sharded serving check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (default backend {backend!r})")
    if default_interpret():
        sys.exit("chip_smoke: the Pallas kernels would run interpreted")
    devices = jax.devices()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    print(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {enable_compile_cache()}", flush=True)

    cfg = lora_config()
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    if args.chips == 4:
        mesh_phase(cfg, seed=args.seed, log=log)
    else:
        res = train_phase(cfg, seed=args.seed, log=log)
        if res["mosaic_calls"] == 0:
            raise AssertionError("the train step holds no Mosaic kernel")
        serve_phase(cfg, res.pop("params"), seed=args.seed, log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
