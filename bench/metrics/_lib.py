"""Helpers the per-layer metric readers share. A reader is
``metrics/<metric name>.py`` with ``read(ctx) -> float | None``; ``ctx``
holds the cell, its traffic, the program's spans and counters and, in a
traced run, the profiler trace (``bench.tracing``)."""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import harness, peaks, tracing


def idle_pct(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * (1.0 - tracing.busy_s(tr) / tracing.window_s(tr))


def window_spans(ctx) -> List[dict]:
    """The program's complete ``X`` spans that began inside the window."""
    lo, hi = ctx["span_window"]
    return [s for s in ctx["spans"] if s.get("ph") == "X" and lo <= s["ts"] <= hi]


def sched_host_ms(ctx):
    """Mean ``scheduler.step`` span less the ``harvest`` spans inside it
    (the one place a step waits on the device)."""
    spans = window_spans(ctx)
    steps = [s for s in spans if s["name"] == "scheduler.step"]
    harv = sorted((s["ts"], s["ts"] + s["dur"]) for s in spans
                  if s["name"] == "harvest")
    if not steps:
        return None
    import bisect
    starts = [h[0] for h in harv]
    tot = 0.0
    for s in steps:
        a, b = s["ts"], s["ts"] + s["dur"]
        i = bisect.bisect_left(starts, a)
        inner = 0.0
        while i < len(harv) and harv[i][0] < b:
            inner += min(harv[i][1], b) - harv[i][0]
            i += 1
        tot += s["dur"] - inner
    return tot / len(steps) / 1e3


def serve_steps(ctx) -> List[List[Tuple[int, int]]]:
    """Per ``scheduler.step`` in the window: its units as (tokens, context
    tokens already in the row's cache before the unit)."""
    context_tokens = harness.generator(ctx["cell"]).context_tokens
    reqs, rid_of = ctx["requests"], ctx["rid_of"]
    spans = [s for s in ctx["spans"] if s.get("ph") == "X"]
    chunks: Dict[int, List[int]] = {}
    for s in sorted(spans, key=lambda s: s["ts"]):
        if s["name"] == "prefill_chunk":
            chunks.setdefault(s["args"]["rid"], []).append(s["args"]["tokens"])
    seen: Dict[int, int] = {}
    lo, hi = ctx["span_window"]
    steps = sorted((s for s in spans if s["name"] == "scheduler.step"),
                   key=lambda s: s["ts"])
    units = sorted((s for s in spans if s["name"] in ("burst", "prefill_chunk")),
                   key=lambda s: s["ts"])
    out, j = [], 0
    for st in steps:
        a, b = st["ts"], st["ts"] + st["dur"]
        cur = []
        while j < len(units) and units[j]["ts"] < b:
            u = units[j]
            j += 1
            if u["ts"] < a:
                continue
            rid, t = u["args"]["rid"], u["args"]["tokens"]
            if rid not in rid_of:
                continue
            n = context_tokens(reqs[rid_of[rid]])
            if u["name"] == "burst":
                cur.append((t, n))
            else:
                done = seen.get(rid, 0)
                seen[rid] = done + 1
                rest = sum(chunks[rid][done:])
                cur.append((t, n - rest))
        if lo <= a <= hi and cur:
            out.append(cur)
    return out


def device_kind(ctx) -> str:
    return ctx["device_kind"]


def peak_flops(ctx) -> float:
    return peaks.peaks(device_kind(ctx))["bf16_flops"]


def ref_cfg(ctx) -> dict:
    return harness.ref_config(ctx["cell"])
