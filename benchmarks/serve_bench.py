"""Serving throughput benchmark: per-candidate re-prefill vs shared context.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] \
        [--attn-impl {dense,pallas}] [--repeat-frac F] \
        [--ctx-heavy-tail] [--dump-scores] [--json BENCH_serve.json] \
        [--trace trace_serve.json] [--jax-profile DIR]

Ways to score the same request stream (one user context, k candidate items
per request), all producing the same p(click) per candidate:

  * ``naive``         — the paper's inference procedure taken literally: one
    sliding-window prompt per candidate, k prefills per request (the context
    is re-encoded k times). Baseline.
  * ``multi_target``  — one prefill per request over a multi-target row:
    context segment + k isolated [SUM]-terminated candidate segments
    (``repro.serve.engine.make_multi_target_prefill_fn``).
  * ``scheduler``     — continuous batching with decode-side shared-context
    KV reuse and cross-request prefix sharing
    (``repro.serve.scheduler.ServeScheduler``): context prefilled once into
    the batched cache, candidates scored as non-committing bursts, contexts
    retained/refcounted so later requests reuse matching prefixes. Runs the
    current scheduling policy: token-budgeted chunked prefill +
    one-step-ahead overlap.
  * ``scheduler_per_slot`` — the same scheduler on the per-slot contiguous
    cache layout (``paged=False``): prefix reuse works only while the
    owning row survives, so its ``cross_row_hits`` are 0 by construction.
    The side-by-side baseline for the paged layout's radix page index
    (``paged_vs_per_slot`` in the artifact: cross-row hits, prefix hit
    rate, pages in use, evictions); on a revisit-heavy stream the run
    exits nonzero if the paged scheduler serves no cross-row hits.
  * ``scheduler_monolithic`` — the same scheduler with the pre-budget
    policy (``monolithic_prefill=True``, no overlap): prefill chunks cut at
    the largest bucket, inflating every co-batched burst's jit shape, and a
    device sync per step. Kept as the side-by-side reference for the
    chunked-prefill p99 win.
  * ``scheduler_pallas`` (with ``--attn-impl pallas``) — the budgeted +
    overlap scheduler run through the fused Pallas decode-attention kernel
    (``repro.kernels.decode_attn``; interpret mode off-TPU) instead of the
    dense decode einsums, so the perf trajectory records dense vs kernel
    side by side.

``--kv-dtype int8`` appends a ``quantized_vs_bf16`` block: the revisit
drain re-run twice — int8 KV pages vs bf16 — at an *equal pool byte*
budget (``--quant-pages`` bf16 pages; int8 gets the same bytes, ~1.8x the
pages). The run exits nonzero unless int8 retains >= 1.5x the cross-row
prefix tokens and a strictly higher prefix hit rate than bf16, and both
runs' scores stay within 0.05 of the fp32 naive oracle.

``--mesh DP,MP`` appends a ``sharded`` block: the same stream drained by a
fleet of 2 mesh-sharded schedulers (user-routed; KV page pool sharded over
the ``data`` axis, KV heads over ``model`` — docs/sharding.md) with the
per-shard ``serve.*`` registries merged into one fleet telemetry snapshot.
The run exits nonzero if the fleet's scores drift more than 1e-4 from the
unsharded scheduler drain. It raises when the runtime has fewer than
DP*MP devices; the ``tier1-multidevice`` CI lane forces 8 host devices
for the (2, 4) placement.

``--trace PATH`` exports the scheduler mode's final drain as a
Chrome-trace-event JSON (``repro.obs.trace``): nested scheduler-step ->
prefill-chunk / burst / dispatch spans plus admission / hot-swap /
finish / watchdog instants — loadable in Perfetto or chrome://tracing,
summarized by ``python -m repro.launch.obs_report``. The run exits
nonzero if the trace fails schema validation or lost the expected span
shapes. ``--jax-profile DIR`` additionally captures a ``jax.profiler``
device trace of the same drain, with decode dispatches annotated per
jit bucket.

``--repeat-frac`` makes that fraction of requests revisit an earlier
context with a fresh slate (``repro.data.requests.make_request_stream``),
the traffic shape prefix sharing exploits. ``--ctx-heavy-tail`` switches
the stream to Pareto-tailed context lengths (clamped at ``--n-ctx-tail``,
default 4x ``--n-ctx``) — the mixed-length traffic where monolithic
prefill's tail inflation shows up in p99.

Reports requests/sec, candidates/sec, p50/p99 request latency with its
queue/service split, the cache-hit token fraction (share of logical prompt
tokens never recomputed) and the share of prefix-shared admissions, plus
the max |score delta| of each shared mode vs naive. Scheduler entries
carry ``decode_impl`` and the scheduler's ``telemetry()`` block (bucket
histogram, queue depth, budget utilization, watchdog). Raw scores are
embedded only under ``--dump-scores``; percentile fields always carry
``n_samples``. The process exits nonzero if any mode reports a non-finite
score or a scheduler watchdog fires, so CI catches a silently-wrong run.
JSON output feeds the CI artifact next to BENCH_kernels.json.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.dti import build_sliding_prompts
from repro.data.requests import make_request_stream
from repro.data.synthetic import make_ctr_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.models.transformer import init_params
from repro.obs import profile as obs_profile
from repro.obs.trace import SpanTracer, validate_chrome_trace
from repro.serve.engine import CTRServer
from repro.serve.scheduler import ServeScheduler
from repro.stream.shard import fleet_serve_snapshot, shard_key


def _round64(n: int) -> int:
    return ((n + 63) // 64) * 64


def _summary(latencies, scores, t_total, n_requests, k, hit_fraction=0.0,
             queue=None, service=None):
    lat = np.asarray(latencies) * 1e3
    out = {
        "requests_per_s": n_requests / t_total,
        "candidates_per_s": n_requests * k / t_total,
        "latency_p50_ms": float(np.percentile(lat, 50)),
        "latency_p99_ms": float(np.percentile(lat, 99)),
        "n_samples": int(len(lat)),
        "cache_hit_token_fraction": hit_fraction,
        "total_s": t_total,
        "scores": scores,
    }
    if queue is not None:
        q, s = np.asarray(queue) * 1e3, np.asarray(service) * 1e3
        out["queue_p50_ms"] = float(np.percentile(q, 50))
        out["queue_p99_ms"] = float(np.percentile(q, 99))
        out["service_p50_ms"] = float(np.percentile(s, 50))
        out["service_p99_ms"] = float(np.percentile(s, 99))
    return out


def run_naive(params, cfg, requests, max_len):
    """k sliding-window prefills per request (context re-encoded k times)."""
    server = CTRServer(params, cfg, max_len=max_len)

    def score_one(req):
        prompts = []
        for cand in req["candidates"]:
            prompts += build_sliding_prompts(
                req["context"] + [cand], [0] * (len(req["context"]) + 1),
                n_ctx=len(req["context"]), max_len=max_len)
        return server.score(prompts)

    score_one(requests[0])                               # compile
    lat, scores = [], []
    t0 = time.perf_counter()
    for req in requests:
        t1 = time.perf_counter()
        scores.append(score_one(req))
        lat.append(time.perf_counter() - t1)
    return _summary(lat, scores, time.perf_counter() - t0,
                    len(requests), len(requests[0]["candidates"]))


def run_multi_target(params, cfg, requests, max_len):
    """One prefill per request: shared context + k isolated segments."""
    server = CTRServer(params, cfg, max_len=max_len)

    def score_one(req):
        return server.score_multi_target(
            [(req["context"], req["candidates"])])[0]

    score_one(requests[0])                               # compile
    lat, scores = [], []
    t0 = time.perf_counter()
    for req in requests:
        t1 = time.perf_counter()
        scores.append(score_one(req))
        lat.append(time.perf_counter() - t1)
    k = len(requests[0]["candidates"])
    hits = logical = 0
    for req in requests:                     # stream-wide, like the scheduler
        ctx = 1 + sum(len(t) for t in req["context"])
        hits += (k - 1) * ctx
        logical += k * ctx + sum(len(c) + 1 for c in req["candidates"])
    return _summary(lat, scores, time.perf_counter() - t0, len(requests), k,
                    hit_fraction=hits / max(logical, 1))


def run_scheduler(params, cfg, requests, *, n_slots, capacity, buckets,
                  attn_impl="dense", monolithic=False, overlap=True,
                  arrival_s=0.0, reps=1, paged=True,
                  cache_dtype=None, kv_dtype=None, n_pages=None,
                  tracer=None):
    """Continuous batching: shared-context cache + non-committing bursts +
    cross-request prefix sharing, on the dense or Pallas decode path.
    ``monolithic=True`` runs the pre-budget chunking (+ per-step sync) as
    the reference policy. ``paged=False`` runs the per-slot contiguous
    cache layout (no page pool, no radix page index) — the baseline the
    paged layout's cross-row prefix hits are measured against; scores are
    identical either way. ``arrival_s`` > 0 paces submissions at that
    inter-arrival gap (open-loop traffic: per-request latency measures the
    requests actually in flight together, not the whole drain's makespan);
    0 submits everything up front (batch drain). ``reps`` repeats the
    measured drain on a fresh scheduler each time and keeps the rep with
    the lowest p99 — scores are deterministic across reps, only wall time
    moves, so best-of-N strips scheduler-external timing noise from the
    policy comparison. ``tracer`` (a ``repro.obs.trace.SpanTracer``) is
    cleared at the start of each rep, so it ends up holding the final
    rep's span stream — enough for the trace artifact, without the
    cross-rep interleaving a shared buffer would record."""
    best = None
    for _ in range(max(1, reps)):
        # fresh scheduler per rep: retained (refcounted) contexts from a
        # prior rep would hand later reps free prefix hits and collapse
        # the policy difference under test
        if tracer is not None:
            tracer.clear()
        sched = ServeScheduler(params, cfg, n_slots=n_slots,
                               capacity=capacity, window=cfg.window,
                               buckets=buckets, attn_impl=attn_impl,
                               monolithic_prefill=monolithic,
                               overlap=overlap, paged=paged,
                               cache_dtype=(cache_dtype if cache_dtype
                                            is not None else jnp.float32),
                               kv_dtype=kv_dtype, n_pages=n_pages,
                               tracer=tracer)
        sched.warmup()                       # compile every bucket shape
        sched.reset_stats()
        t0 = time.perf_counter()
        if arrival_s > 0.0:
            rids, i = [], 0
            while True:
                while (i < len(requests)
                       and time.perf_counter() >= t0 + i * arrival_s):
                    rids.append(sched.submit(requests[i]["context"],
                                             requests[i]["candidates"]))
                    i += 1
                if not sched.step():
                    if i >= len(requests):
                        break
                    time.sleep(max(0.0, t0 + i * arrival_s
                                   - time.perf_counter()))
            results = sched.run()            # no-op drain: collect results
        else:
            rids = [sched.submit(r["context"], r["candidates"])
                    for r in requests]
            results = sched.run()
        t_total = time.perf_counter() - t0
        lat = [results[r].latency_s for r in rids]
        scores = [results[r].scores for r in rids]
        hits = sum(results[r].cached_tokens for r in rids)
        logical = sum(results[r].logical_tokens for r in rids)
        out = _summary(lat, scores, t_total, len(requests),
                       len(requests[0]["candidates"]),
                       hit_fraction=hits / max(logical, 1),
                       queue=[results[r].queue_s for r in rids],
                       service=[results[r].service_s for r in rids])
        out["steps"] = sched.n_steps
        out["decode_impl"] = attn_impl
        out["reps"] = max(1, reps)
        out["shared_admission_fraction"] = (sched.shared_admissions
                                            / len(rids))
        out["shared_prefix_tokens"] = sum(
            results[r].shared_prefix_tokens for r in rids)
        out["telemetry"] = sched.telemetry()
        out["jit_stats"] = sched.jit_stats()
        if best is None or out["latency_p99_ms"] < best["latency_p99_ms"]:
            best = out
    return best


def run_sharded_fleet(params, cfg, requests, *, n_slots, capacity, buckets,
                      dp, mp, fleet=2):
    """The scale-out drain (docs/sharding.md): a fleet of mesh-sharded
    schedulers splitting the request stream by user, each with its KV page
    pool sharded over ``data`` and KV heads over ``model``
    (``ServeScheduler(mesh=...)``). ``make_serve_mesh`` raises when the
    runtime has fewer than ``dp * mp`` devices. Scores come back in submission
    order so the caller can diff them against the unsharded drain;
    telemetry is the per-shard ``serve.*`` registries merged into one
    fleet snapshot (``fleet_serve_snapshot``)."""
    mesh = make_serve_mesh(dp, mp)
    scheds = [ServeScheduler(params, cfg, n_slots=n_slots,
                             capacity=capacity, window=cfg.window,
                             buckets=buckets, mesh=mesh)
              for _ in range(fleet)]
    for s in scheds:
        s.warmup()
        s.reset_stats()
    parts = [[] for _ in range(fleet)]
    for i, r in enumerate(requests):
        parts[shard_key(r, fleet)].append((i, r))
    k = len(requests[0]["candidates"])
    scores = [None] * len(requests)
    lat, hits, logical = [], 0, 0
    t0 = time.perf_counter()
    for s, part in zip(scheds, parts):
        rids = [s.submit(r["context"], r["candidates"]) for _, r in part]
        results = s.run()
        for (i, _), rid in zip(part, rids):
            scores[i] = results[rid].scores
            lat.append(results[rid].latency_s)
            hits += results[rid].cached_tokens
            logical += results[rid].logical_tokens
    t_total = time.perf_counter() - t0
    out = _summary(lat, scores, t_total, len(requests), k,
                   hit_fraction=hits / max(logical, 1))
    out["requested_mesh"] = [dp, mp]
    out["mesh"] = {str(a): int(n) for a, n in mesh.shape.items()}
    out["devices"] = len(jax.devices())
    out["fleet"] = fleet
    out["requests_per_shard"] = [len(p) for p in parts]
    out["steps"] = sum(s.n_steps for s in scheds)
    out["decode_impl"] = "dense"
    out["merged_telemetry"] = fleet_serve_snapshot(scheds)
    return out


def run_quant_compare(params, cfg, requests, *, n_slots, capacity, buckets,
                      arrival_s=0.0, base_pages=16, page_size=16):
    """int8 vs bf16 KV on the revisit drain at an *equal pool byte* budget.

    The bf16 scheduler gets ``base_pages`` pages; the int8 scheduler gets
    however many pages the same HBM bytes buy (per-token cost from
    ``repro.serve.cache.kv_token_bytes``, scale sidecar included — with
    the smoke config int8 is ~1.8x denser). Same stream, same slots, same
    capacity: the only free variable is what the byte budget retains, so
    int8's extra pages should show up directly as cross-row prefix hits
    the bf16 pool had to evict.
    """
    from repro.serve.cache import cache_shape, kv_token_bytes

    cap_eff = -(-capacity // page_size) * page_size   # scheduler's rounding
    tb = {}
    for label, kvd in (("bf16", None), ("int8", "int8")):
        spec = cache_shape(cfg, n_slots, cap_eff, dtype=jnp.bfloat16,
                           kv_dtype=kvd, page_size=page_size,
                           n_pages=base_pages)
        tb[label] = kv_token_bytes(spec)
    # floor keeps the int8 pool at-or-under the bf16 byte budget
    int8_pages = max(base_pages, int(base_pages * tb["bf16"] / tb["int8"]))
    out = {}
    for label, kvd, n_pages in (("bf16", None, base_pages),
                                ("int8", "int8", int8_pages)):
        m = run_scheduler(params, cfg, requests, n_slots=n_slots,
                          capacity=capacity, buckets=buckets,
                          arrival_s=arrival_s, cache_dtype=jnp.bfloat16,
                          kv_dtype=kvd, n_pages=n_pages)
        m["n_pages"] = n_pages
        m["kv_token_bytes"] = tb[label]
        m["pool_bytes"] = int(n_pages * page_size * tb[label])
        out[label] = m
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small stream, same code path)")
    ap.add_argument("--json", default=None, help="write results to this path")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--k", type=int, default=None,
                    help="slate size (default 8; 2 under --ctx-heavy-tail, "
                         "whose point is long contexts vs small bursts)")
    ap.add_argument("--n-ctx", type=int, default=None, dest="n_ctx",
                    help="context interactions per request (default 8; "
                         "6 under --ctx-heavy-tail)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-impl", default="dense", dest="attn_impl",
                    choices=("dense", "pallas"),
                    help="decode path for the scheduler; 'pallas' also "
                         "runs a scheduler_pallas mode through the fused "
                         "decode-attention kernel")
    ap.add_argument("--repeat-frac", type=float, default=0.25,
                    dest="repeat_frac",
                    help="fraction of requests revisiting an earlier "
                         "context (exercises cross-request prefix sharing)")
    ap.add_argument("--ctx-heavy-tail", action="store_true",
                    dest="ctx_heavy_tail",
                    help="Pareto-tailed per-request context lengths "
                         "(n_ctx .. n_ctx_tail interactions) — the "
                         "mixed-length workload the chunked-prefill "
                         "scheduler targets")
    ap.add_argument("--n-ctx-tail", type=int, default=None,
                    dest="n_ctx_tail",
                    help="context length clamp under --ctx-heavy-tail "
                         "(default 8x --n-ctx)")
    ap.add_argument("--arrival-ms", type=float, default=None,
                    dest="arrival_ms",
                    help="inter-arrival gap for the scheduler modes "
                         "(default 0 = submit all up front / batch "
                         "drain; set >0 for open-loop paced traffic)")
    ap.add_argument("--reps", type=int, default=None,
                    help="repeat each scheduler-mode drain N times on a "
                         "fresh scheduler and keep the best-p99 rep "
                         "(default 3 under --ctx-heavy-tail, else 1) — "
                         "container timing noise otherwise swamps the "
                         "policy delta")
    ap.add_argument("--kv-dtype", default="native", dest="kv_dtype",
                    choices=("native", "int8"),
                    help="'int8' adds a quantized_vs_bf16 block: the "
                         "revisit drain re-run with int8 KV pages vs bf16 "
                         "at an equal pool byte budget, gated on int8 "
                         "retaining strictly more cross-row prefix")
    ap.add_argument("--quant-pages", type=int, default=16,
                    dest="quant_pages",
                    help="bf16-page budget of the quantized_vs_bf16 "
                         "compare (int8 gets the same bytes; default 16, "
                         "raised automatically if one row's capacity "
                         "needs more)")
    ap.add_argument("--dump-scores", action="store_true", dest="dump_scores",
                    help="embed every mode's raw per-candidate scores in "
                         "the JSON artifact (large; off by default)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the scheduler mode's final drain as a "
                         "Chrome-trace JSON (load in Perfetto / "
                         "chrome://tracing, or summarize with "
                         "python -m repro.launch.obs_report PATH); the "
                         "run exits nonzero if the trace fails schema "
                         "validation or misses the expected span shapes")
    ap.add_argument("--mesh", default=None, metavar="DP,MP",
                    help="also run the scale-out drain: a fleet of 2 "
                         "schedulers splitting the stream by user, each "
                         "mesh-sharded (KV page pool over 'data', KV heads "
                         "over 'model') on a (DP, MP) device mesh; emits a "
                         "'sharded' block with per-shard-merged serve.* "
                         "telemetry and the max |score delta| vs the "
                         "unsharded scheduler drain. Raises when the "
                         "runtime has < DP*MP devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 on CPU)")
    ap.add_argument("--jax-profile", default=None, dest="jax_profile",
                    metavar="DIR",
                    help="also capture a jax.profiler device trace of the "
                         "scheduler-mode drain into DIR (spans annotate "
                         "decode dispatches; no-op if the profiler is "
                         "unavailable)")
    args = ap.parse_args()

    n_requests = args.requests or (8 if args.smoke else 32)
    k = args.k or 8
    n_ctx = args.n_ctx or 8
    n_ctx_tail = None
    arrival_s = (args.arrival_ms or 0.0) * 1e-3
    reps = args.reps or 1
    if args.ctx_heavy_tail:
        # the heavy-tail workload: long mixed-length contexts, small
        # slates (bursts fit the smallest bucket — what monolithic
        # prefill needlessly inflates), drained as a batch so the tail
        # measures how fast the backlog behind a long prefill clears
        k = args.k or 2
        n_ctx = args.n_ctx or 6
        n_ctx_tail = args.n_ctx_tail or 8 * n_ctx
        reps = args.reps or 3
        # heavy tails need enough requests for p99 to mean anything beyond
        # the max; keep smoke runs CI-sized but not degenerate
        n_requests = args.requests or (16 if args.smoke else 48)
    cfg = get_arch("dti-llama").smoke
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    seq_len = max(n_ctx_tail or n_ctx, 12)
    ds = make_ctr_dataset(n_users=16, n_items=120, seq_len=seq_len,
                          vocab_size=cfg.vocab_size, seed=args.seed)
    requests = make_request_stream(ds, n_requests=n_requests, k=k,
                                   n_ctx=n_ctx, seed=args.seed,
                                   repeat_frac=args.repeat_frac,
                                   n_ctx_tail=n_ctx_tail)

    ctx_len = max(1 + sum(len(t) for t in r["context"]) for r in requests)
    cand_max = max(len(c) + 1 for r in requests for c in r["candidates"])
    sw_len = _round64(ctx_len + cand_max)
    mt_len = _round64(ctx_len + k * cand_max)
    buckets = (16, 32, 64)
    capacity = ctx_len + max(buckets)

    print(f"[serve_bench] {n_requests} requests, k={k}, "
          f"ctx<={ctx_len} tok, candidate burst<={cand_max} tok, "
          f"repeat_frac={args.repeat_frac}"
          + (f", heavy-tail ctx (clamp {n_ctx_tail})"
             if args.ctx_heavy_tail else ""))
    # host-side span tracer for the headline scheduler mode only: the
    # other modes are references, and one mode's trace is what the
    # viewer/summarizer consumes
    tracer = (SpanTracer(jax_annotate=bool(args.jax_profile))
              if (args.trace or args.jax_profile) else None)
    prof = (obs_profile.trace(args.jax_profile) if args.jax_profile
            else contextlib.nullcontext())
    modes = {
        "naive": run_naive(params, cfg, requests, sw_len),
        "multi_target": run_multi_target(params, cfg, requests, mt_len),
    }
    with prof:
        modes["scheduler"] = run_scheduler(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, arrival_s=arrival_s, reps=reps, tracer=tracer)
    modes.update({
        # the per-slot contiguous cache, recorded side by side: its
        # prefix reuse dies with the row (cross_row_hits == 0 by
        # construction), which is exactly what the paged radix index is
        # measured against on revisit-heavy streams
        "scheduler_per_slot": run_scheduler(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, arrival_s=arrival_s, reps=reps, paged=False),
        # the pre-change policy, recorded side by side so the budgeted +
        # overlap p99 win is measured, not asserted
        "scheduler_monolithic": run_scheduler(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, monolithic=True, overlap=False,
            arrival_s=arrival_s, reps=reps),
    })
    shared_modes = ["multi_target", "scheduler", "scheduler_per_slot",
                    "scheduler_monolithic"]
    if args.attn_impl == "pallas":
        # single rep: interpret-mode wall time tracks correctness, not the
        # policy comparison (excluded from p99_improvement below), so
        # best-of-N would only burn CI minutes
        modes["scheduler_pallas"] = run_scheduler(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, attn_impl="pallas", arrival_s=arrival_s)
        shared_modes.append("scheduler_pallas")

    all_scores = {name: modes[name].pop("scores") for name in modes}
    ref = np.asarray(all_scores["naive"])
    deltas = {}
    for name in shared_modes:
        sc = np.asarray(all_scores[name])
        deltas[name] = float(np.max(np.abs(sc - ref)))
    if args.dump_scores:
        for name in modes:
            modes[name]["scores"] = all_scores[name]
    for name, m in modes.items():
        print(f"  {name:20s} {m['candidates_per_s']:8.1f} cand/s  "
              f"{m['requests_per_s']:6.1f} req/s  "
              f"p50 {m['latency_p50_ms']:7.1f} ms  "
              f"p99 {m['latency_p99_ms']:7.1f} ms  "
              f"cache-hit {m['cache_hit_token_fraction']:.2f}"
              + (f"  shared-adm {m['shared_admission_fraction']:.2f}"
                 if "shared_admission_fraction" in m else ""))
    print(f"  max |p - naive|: {deltas}")

    result = {
        "config": {"arch": cfg.name, "n_requests": n_requests, "k": k,
                   "n_ctx": n_ctx, "n_ctx_tail": n_ctx_tail,
                   "arrival_ms": arrival_s * 1e3, "reps": reps,
                   "slots": args.slots,
                   "smoke": bool(args.smoke),
                   "decode_impl": args.attn_impl,
                   "repeat_frac": args.repeat_frac},
        "modes": modes,
        "score_max_abs_delta_vs_naive": deltas,
        "speedup_candidates_per_s": {
            name: modes[name]["candidates_per_s"]
            / modes["naive"]["candidates_per_s"]
            for name in shared_modes},
        # policy-vs-policy only: compare against the monolithic reference
        # on the same decode impl (pallas runs interpret-mode off-TPU, so
        # its wall time says nothing about the scheduling policy)
        "p99_improvement_vs_monolithic": {
            name: modes["scheduler_monolithic"]["latency_p99_ms"]
            / modes[name]["latency_p99_ms"]
            for name in shared_modes if name.startswith("scheduler")
            and name != "scheduler_monolithic"
            and modes[name]["decode_impl"]
            == modes["scheduler_monolithic"]["decode_impl"]},
        # the tentpole's headline: prefix reuse that survives row
        # eviction. per_slot's cross_row_hits are structurally 0 (its
        # prefixes die with the row); the paged radix index must serve
        # revisits that arrive after their source row was stolen.
        "paged_vs_per_slot": {
            "cross_row_hits": modes["scheduler"]["telemetry"]
                              ["cross_row_hits"],
            "cross_row_tokens": modes["scheduler"]["telemetry"]
                                ["cross_row_tokens"],
            "prefix_hit_rate_paged": modes["scheduler"]["telemetry"]
                                     ["prefix_hit_rate"],
            "prefix_hit_rate_per_slot": modes["scheduler_per_slot"]
                                        ["telemetry"]["prefix_hit_rate"],
            "pages_in_use": modes["scheduler"]["telemetry"]["pages_in_use"],
            "page_evictions": modes["scheduler"]["telemetry"]
                              ["page_evictions"],
        },
    }

    quant = None
    if args.kv_dtype == "int8":
        # a pool that can't hold one fully-occupied row deadlocks
        # admission: lift the page budget to row capacity + slack first
        page_size = 16
        base_pages = max(args.quant_pages,
                         -(-capacity // page_size) + 2)
        quant = run_quant_compare(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, arrival_s=arrival_s, base_pages=base_pages,
            page_size=page_size)
        q_deltas = {}
        for label in quant:
            sc = np.asarray(quant[label].pop("scores"))
            q_deltas[label] = float(np.max(np.abs(sc - ref)))
        qi, qb = quant["int8"]["telemetry"], quant["bf16"]["telemetry"]
        result["quantized_vs_bf16"] = {
            "bf16": quant["bf16"], "int8": quant["int8"],
            "score_max_abs_delta_vs_naive": q_deltas,
            "pool_bytes_bf16": quant["bf16"]["pool_bytes"],
            "pool_bytes_int8": quant["int8"]["pool_bytes"],
            "pages_bf16": quant["bf16"]["n_pages"],
            "pages_int8": quant["int8"]["n_pages"],
            "cross_row_tokens_ratio": (qi["cross_row_tokens"]
                                       / max(qb["cross_row_tokens"], 1)),
        }
        for label in ("bf16", "int8"):
            t = quant[label]["telemetry"]
            print(f"  quant[{label}]: {quant[label]['n_pages']} pages "
                  f"({quant[label]['pool_bytes']} B)  cross-row tokens "
                  f"{t['cross_row_tokens']}  hit-rate "
                  f"{t['prefix_hit_rate']:.3f}  evictions "
                  f"{t['page_evictions']}  |dp| {q_deltas[label]:.2e}")

    sharded = None
    if args.mesh:
        try:
            dp, mp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh expects DP,MP (got {args.mesh!r})")
        sharded = run_sharded_fleet(
            params, cfg, requests, n_slots=args.slots, capacity=capacity,
            buckets=buckets, dp=dp, mp=mp)
        sh_scores = np.asarray(sharded.pop("scores"))
        sharded["score_max_abs_delta_vs_unsharded"] = float(np.max(np.abs(
            sh_scores - np.asarray(all_scores["scheduler"]))))
        sharded["score_max_abs_delta_vs_naive"] = float(
            np.max(np.abs(sh_scores - ref)))
        all_scores["sharded"] = sh_scores
        result["sharded"] = sharded
        mt = sharded["merged_telemetry"]
        print(f"  sharded mesh={sharded['mesh']}"
              f"  fleet={sharded['fleet']} "
              f"{sharded['candidates_per_s']:8.1f} cand/s  "
              f"fleet steps {mt['serve.steps']['value']}  "
              f"|dp vs unsharded| "
              f"{sharded['score_max_abs_delta_vs_unsharded']:.2e}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"[serve_bench] wrote {args.json}")

    # validity gate: a benchmark that silently scored garbage (NaN burst,
    # stalled row) must fail the CI job, not upload a green artifact
    bad = []
    if args.trace:
        # export first (a malformed trace should still land on disk for
        # inspection), then gate: schema-valid AND carrying the span
        # shapes the scheduler is supposed to emit — a drain whose trace
        # lost its step/prefill spans means the instrumentation regressed
        tracer.save(args.trace)
        doc = tracer.to_chrome_trace()
        problems = validate_chrome_trace(doc)
        names_x = {e["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "X"}
        names_i = {e["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "i"}
        if "scheduler.step" not in names_x:
            problems.append("no scheduler.step span")
        if not ({"prefill_chunk", "burst"} & names_x):
            problems.append("no prefill_chunk/burst span")
        if not ({"admission", "hot_swap", "finish"} & names_i):
            problems.append("no admission/hot_swap/finish instant")
        bad += [f"trace: {p}" for p in problems]
        print(f"[serve_bench] wrote {args.trace} "
              f"({len(tracer)} events, {len(problems)} problems)")
    for name, sc in all_scores.items():
        if not all(math.isfinite(float(s)) for req in sc for s in req):
            bad.append(f"{name}: non-finite score")
    for name in modes:
        tel = modes[name].get("telemetry")
        if tel and tel["watchdog_fired"]:
            bad.append(f"{name}: watchdog fired "
                       f"(stuck rids {tel['watchdog_stuck_rids']})")
    # cross-row regression gate: on a revisit-heavy stream with more
    # distinct contexts than rows, some revisits necessarily arrive after
    # their source row was reused — the paged radix index must serve them
    # (per-slot scores 0 here by design; a 0 on the paged path means the
    # index silently stopped working)
    if args.repeat_frac > 0 and n_requests >= 4 * args.slots:
        pvs = result["paged_vs_per_slot"]
        if pvs["cross_row_hits"] <= 0:
            bad.append(
                f"paged scheduler served 0 cross-row prefix hits on a "
                f"revisit-heavy stream (repeat_frac={args.repeat_frac}, "
                f"{n_requests} requests / {args.slots} slots) — per-slot "
                f"baseline hit rate "
                f"{pvs['prefix_hit_rate_per_slot']:.3f}, paged "
                f"{pvs['prefix_hit_rate_paged']:.3f}")
    if quant is not None:
        # int8 scores must stay near the fp32 naive oracle (quantization
        # error on p(click) is ~1e-3 at smoke scale; 0.05 catches a broken
        # dequant path, not noise), and both runs must be watchdog-clean
        qv = result["quantized_vs_bf16"]
        for label in ("bf16", "int8"):
            if qv["score_max_abs_delta_vs_naive"][label] > 0.05:
                bad.append(f"quant[{label}] scores diverged from naive by "
                           f"{qv['score_max_abs_delta_vs_naive'][label]:.3f}"
                           f" (> 0.05)")
            if quant[label]["telemetry"]["watchdog_fired"]:
                bad.append(f"quant[{label}]: watchdog fired")
        if args.repeat_frac > 0 and n_requests >= 4 * args.slots:
            # the tentpole's payoff gate: at equal pool bytes the denser
            # int8 pages must retain strictly more reusable prefix
            if qv["cross_row_tokens_ratio"] < 1.5:
                bad.append(
                    f"int8 cross-row prefix tokens only "
                    f"{qv['cross_row_tokens_ratio']:.2f}x bf16's at equal "
                    f"pool bytes (need >= 1.5x)")
            if (qi["prefix_hit_rate"] <= qb["prefix_hit_rate"]):
                bad.append(
                    f"int8 prefix hit rate {qi['prefix_hit_rate']:.3f} did "
                    f"not beat bf16's {qb['prefix_hit_rate']:.3f} at equal "
                    f"pool bytes")
    if sharded is not None:
        # the scale-out acceptance bound (docs/sharding.md): the sharded
        # fleet's scores must match the single-device scheduler drain —
        # GSPMD may reorder reductions across shards, nothing more
        if sharded["score_max_abs_delta_vs_unsharded"] > 1e-4:
            bad.append(
                f"sharded drain diverged from unsharded by "
                f"{sharded['score_max_abs_delta_vs_unsharded']:.2e} "
                f"(> 1e-4) on mesh {sharded['mesh']}")
        if sharded["merged_telemetry"]["serve.watchdog_fired"]["value"]:
            bad.append("sharded: watchdog fired on some shard")
    if bad:
        print(f"[serve_bench] INVALID RUN: {'; '.join(bad)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
