"""A serving cell: an open loop of ranking requests into
``repro.serve.scheduler.ServeScheduler`` (``submit`` / ``step``) on the
Pallas decode kernel and a paged bfloat16 KV cache.

Set-up builds the scheduler at the configuration's deployment (slots,
buckets, page pool), compiles its buckets (``warmup``) and runs the mix's
warm-up requests through it. In the window each request is submitted once
its due time has passed; between arrivals the loop steps the scheduler.
At the close, requests still queued or in flight are drained and counted
at their full time.

* ``serve_tts_p95_ms``: the 95th percentile of time-to-score over every
  request due in the window, from its due time to the harvest of its last
  candidate's score.
* ``serve_cand_per_s``: candidates of requests finished inside the window,
  over the window.

A generator of a serving mix (``bench/traffic/<generator>.py``) gives
``requests(mix, vocab, seconds, seed, rate_per_s=None) -> (warm, window)``
and ``context_tokens(request)``.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights
from bench.harness import log
from bench.reference import check


def make_scheduler(cell, params, mcfg, tracer=None):
    from repro.serve.scheduler import ServeScheduler
    s = cell.config["serve"]
    return ServeScheduler(
        params, mcfg, n_slots=s["n_slots"], capacity=s["capacity"],
        buckets=tuple(s["buckets"]), attn_impl="pallas",
        cache_dtype=jnp.bfloat16, paged=True, page_size=s["page_size"],
        n_pages=s["n_pages"], prefill_budget=s.get("prefill_budget"),
        tracer=tracer)


def _plant(sched, fault):
    """A broken decode step for the tests of ``correct``."""
    decode = sched._decode
    if fault == "answer_altered":
        def broken(params, cache, tokens, *a):
            p, c = decode(params, cache, tokens, *a)
            return jnp.where(tokens == 2, 1.0 - p, p), c
    elif fault == "state_unchanged":
        def broken(params, cache, *a):
            p, _ = decode(params, jax.tree_util.tree_map(jnp.copy, cache), *a)
            return p, cache
    else:
        raise ValueError(f"unknown fault {fault!r}")
    sched._decode = broken


def open_loop(sched, reqs, seconds, *, on_open=None):
    """Submit each request at its due time, step in between; returns
    (submit clock per request, window start, window end, queue at close)."""
    submitted = []
    rid_of = {}
    t0 = time.perf_counter()
    if on_open:
        on_open()
    i = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        while i < len(reqs) and reqs[i]["due"] <= now - t0:
            r = reqs[i]
            rid_of[sched.submit(r["context"], r["candidates"])] = i
            submitted.append(time.perf_counter())
            i += 1
        if not sched.step():
            nxt = reqs[i]["due"] if i < len(reqs) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - (time.perf_counter() - t0)))
    t1 = time.perf_counter()
    queued = len(sched._queue)
    return submitted, rid_of, t0, t1, queued, i


def run(cell: harness.Cell, devices, t_start: float, counter) -> dict:
    from repro.obs.trace import SpanTracer

    mcfg = harness.model_config(cell)
    window = mcfg.window
    gen = harness.generator(cell)
    warm, reqs = gen.requests(cell.mix, mcfg.vocab_size, cell.seconds,
                              cell.seed)
    params = weights.make_params(mcfg, cell.seed)
    tracer = SpanTracer(jax_annotate=True, capacity=1 << 21) \
        if cell.trace else None
    sched = make_scheduler(cell, params, mcfg, tracer)
    del params
    if cell.fault:
        _plant(sched, cell.fault)
    sched.warmup()
    for r in warm:
        sched.submit(r["context"], r["candidates"])
    sched.run()
    sched.reset_stats()
    setup_s = time.perf_counter() - t_start
    log(f"[serve] {cell.name}: set-up {setup_s:.3f}s, {len(reqs)} requests "
        f"due in {cell.seconds}s ({len(reqs) / cell.seconds:.3f} req/s), bucket "
        f"compile_s {[round(v['compile_s'], 3) for v in sched.jit_stats().values()]}")

    trace_s = cell.mix.get("trace_seconds") or cell.seconds
    ann = {}

    def on_open():
        if tracer is not None:
            tracer.clear()
            jax.profiler.start_trace(cell.out_dir + "/trace")
        ann["t"] = time.perf_counter()
        ann["a"] = jax.profiler.TraceAnnotation("bench.window")
        ann["a"].__enter__()

    counter.armed = True
    if tracer is not None:
        # trace the first ``trace_seconds`` of the window only
        sub, rid_of, t0, t1, queued, n_sub = open_loop(
            sched, reqs, trace_s, on_open=on_open)
        ann["a"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    else:
        sub, rid_of, t0, t1, queued, n_sub = open_loop(
            sched, reqs, cell.seconds, on_open=on_open)
        ann["a"].__exit__(None, None, None)
    counter.armed = False
    tel = sched.telemetry()
    results = sched.run()                  # drain what is left
    t_drain = time.perf_counter() - t1
    peak = harness.peak_bytes(devices[0])
    late = np.asarray([s - t0 - reqs[i]["due"] for i, s in
                       zip(range(n_sub), sub)])
    done_at = {rid_of[rid]: sub[rid_of[rid]] + res.latency_s
               for rid, res in results.items() if rid in rid_of}
    inf = float("inf")
    tts = np.asarray([done_at.get(i, inf) - (t0 + reqs[i]["due"])
                      for i in range(n_sub)]) * 1e3
    in_window = [i for i in range(n_sub) if done_at.get(i, inf) <= t1]
    n_cand = sum(len(reqs[i]["candidates"]) for i in in_window)
    log(f"[serve] window {t1 - t0:.4f}s: {n_sub} submitted, "
        f"{len(in_window)} finished inside, {queued} queued at the close, "
        f"drain {t_drain:.3f}s, {counter.count} compiles inside; generator "
        f"late by mean {late.mean() * 1e3 if len(late) else 0:.3f} ms, "
        f"max {late.max() * 1e3 if len(late) else 0:.3f} ms; tts p50 "
        f"{np.percentile(tts, 50) if len(tts) else 0:.2f} ms p95 "
        f"{np.percentile(tts, 95) if len(tts) else 0:.2f} ms; prefix hit "
        f"{tel['prefix_hit_rate']:.4f}; steps {tel['steps']} "
        f"{tel['bucket_steps']}")
    spans = tracer.events() if tracer is not None else []
    epoch = tracer._epoch if tracer is not None else 0.0
    del sched
    gc.collect()

    # -- correct --------------------------------------------------------------
    t_ref = time.perf_counter()
    got = {rid_of[rid]: res.scores for rid, res in results.items()
           if rid in rid_of}
    missing = n_sub - len(got)
    sample = check.sample_requests(sorted(got), cell.mix["check_requests"],
                                   cell.seed,
                                   key=lambda i: gen.context_tokens(reqs[i]))
    ref_params = weights.make_params(mcfg, cell.seed)
    score = check.make_serve_ref(harness.ref_config(cell), window)
    length = cell.mix["check_row_tokens"]
    gap = max((check.serve_gap(got[i], score(ref_params, reqs[i], length))
               for i in sample), default=float("inf"))
    n_scores = sum(len(got[i]) for i in sample)
    log(f"[serve] reference over {len(sample)} requests ({n_scores} scores, "
        f"longest context {gen.context_tokens(reqs[sample[0]]) if sample else 0}) "
        f"{time.perf_counter() - t_ref:.1f}s; {missing} never answered")
    numbers = {"score_gap": gap, "unanswered": float(missing)}
    checks = harness.compared(numbers, cell.limits)
    result = {"correct": checks["ok"], "attempted": n_sub,
              "failed": missing,
              "device": harness.device_info(devices, cell.workload["chips"],
                                            peak)}
    ctx = {"cell": cell, "kind": "serve", "requests": reqs, "rid_of": rid_of,
           "telemetry": tel, "spans": spans, "window": window,
           "span_window": (0.0, (t1 - epoch) * 1e6),
           "device_kind": devices[0].device_kind, "log": log}
    if cell.trace:
        from bench import tracing
        tr = tracing.load(cell.out_dir + "/trace")
        ctx["trace"] = tr
        result["metrics"] = harness.run_readers(cell, ctx)
        result["device"]["busy_s"] = tracing.busy_s(tr)
        result["device"]["window_s"] = tracing.window_s(tr)
        on = tracing.spans_on_trace(spans, tr, ann["t"] - epoch)
        result["breakdown"] = {"device_ops": tracing.top_ops(tr),
                               "idle_gaps": tracing.idle_gaps(tr, on)}
    else:
        m = {"setup_s": harness.metric(setup_s, "s")}
        names = {e["name"] for e in cell.end_to_end()}
        if "serve_tts_p95_ms" in names:
            m["serve_tts_p95_ms"] = harness.metric(
                float(np.percentile(tts, 95)), "ms")
        if "serve_cand_per_s" in names:
            m["serve_cand_per_s"] = harness.metric(n_cand / (t1 - t0),
                                                   "candidates/s")
        result["metrics"] = m
    return result, checks
