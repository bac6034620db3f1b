"""Reduction of a profiler trace (``.xplane.pb``) and of the program's host
spans to what the per-layer metrics read.

Device events are the ``XLA Ops`` line of ``/device:TPU:0`` (chip 0: every
cell runs on one chip). An op that encloses others on that line (a
``while`` loop of the scanned layers) counts once towards busy time and is
left out of the op breakdown. Host spans of the program
(``repro.obs.trace.SpanTracer``, on the ``perf_counter`` clock) are put on
the profiler's clock by the ``bench.window`` annotation that the harness
opens around the traced window.

A Pallas kernel carries no name of its own in the trace: its event is the
HLO text of a ``tpu_custom_call``. ``kernel_kind`` names it from the jitted
module it runs in and from its outputs:

* in a training step: ``winattn_fwd`` (outputs an attention output and a
  float32 ``[B,H,1,S]`` log-sum-exp), ``winattn_dq`` (bfloat16 outputs
  only) and ``winattn_dkv`` (float32 outputs only);
* in the scheduler's ``decode`` step: ``decode_attn``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MARK = "bench.window"
_OUT = re.compile(r"^%\S+ = \(?(.*?)\)? custom-call\(")


class Trace:
    def __init__(self, ops, modules, host):
        self.ops = ops            # [(name, start_ns, end_ns)] device ops
        self.modules = modules    # [(name, start_ns, end_ns)] jitted programs
        self.host = host          # [(name, start_ns, end_ns)] host events

    def mark(self) -> Tuple[float, float]:
        """The traced window: the ``bench.window`` annotation."""
        ms = [h for h in self.host if h[0] == MARK]
        if not ms:
            raise ValueError("trace holds no bench.window annotation")
        return ms[0][1], ms[0][2]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(sorted(files)[-1]))


def from_profile(pd) -> Trace:
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is not None:
                    dst.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if not e.name.startswith("$"))
    ops.sort(key=lambda e: e[1])
    modules.sort(key=lambda e: e[1])
    return Trace(ops, modules, host)


def _clip(events, t0, t1):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def busy_intervals(ops, t0, t1) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, a, b in sorted(_clip(ops, t0, t1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    t0, t1 = trace.mark()
    return sum(b - a for a, b in busy_intervals(trace.ops, t0, t1)) / 1e9


def window_s(trace: Trace) -> float:
    t0, t1 = trace.mark()
    return (t1 - t0) / 1e9


def leaf_ops(ops):
    """Ops that enclose no other op of the line."""
    parents = set()
    stack = []
    for i, ev in enumerate(ops):
        while stack and ops[stack[-1]][2] <= ev[1]:
            stack.pop()
        if stack and ev[2] <= ops[stack[-1]][2]:
            parents.add(stack[-1])
        stack.append(i)
    return [ev for i, ev in enumerate(ops) if i not in parents]


def op_label(trace: Trace, name: str, start: float) -> str:
    """A device op as the breakdown names it: a kernel by ``kernel_kind``,
    any other op by its HLO instruction name without the number
    (``%fusion.1049`` -> ``fusion``), so ops of one kind add up."""
    kind = kernel_kind(name, module_at(trace, start))
    return kind or re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def top_ops(trace: Trace, n: int = 10):
    """The device ops that took most time in the window: [[name, s]]."""
    t0, t1 = trace.mark()
    tot: Dict[str, float] = {}
    for name, a, b in _clip(leaf_ops(trace.ops), t0, t1):
        k = op_label(trace, name, a)
        tot[k] = tot.get(k, 0.0) + (b - a) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def module_at(trace: Trace, t: float) -> Optional[str]:
    """The jitted program running on the device at ``t``."""
    import bisect
    starts = trace.__dict__.setdefault("_starts", [m[1] for m in trace.modules])
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and trace.modules[i][2] >= t:
        return trace.modules[i][0]
    return None


def kernel_kind(name: str, module: Optional[str]) -> Optional[str]:
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    m = _OUT.match(name)
    outs = re.findall(r"(bf16|f32|s32|s8|f16)\[([\d,]*)\]", m.group(1)) if m else []
    mod = (module or "").split("(")[0]
    if mod.startswith("jit_decode"):
        return "decode_attn"
    if not mod.startswith("jit_step") or not outs:
        return None
    dts = {d for d, _ in outs}
    if len(outs) == 2 and outs[1][0] == "f32" and outs[1][1].split(",")[-2] == "1":
        return "winattn_fwd"
    if dts == {"f32"}:
        return "winattn_dkv"
    if dts <= {"bf16", "f16"}:
        return "winattn_dq"
    return None


def kernel_events(trace: Trace) -> Dict[str, List[Tuple[float, float]]]:
    """{kind: [(start_ns, end_ns)]} of the kernels inside the window."""
    t0, t1 = trace.mark()
    out: Dict[str, List[Tuple[float, float]]] = {}
    for name, a, b in trace.ops:
        if b <= t0 or a >= t1:
            continue
        k = kernel_kind(name, module_at(trace, a))
        if k:
            out.setdefault(k, []).append((a, b))
    return out


def spans_on_trace(spans: List[dict], trace: Trace, mark_perf_s: float):
    """Program spans (Chrome ``X`` events, µs since the tracer's epoch) on
    the profiler's clock. ``mark_perf_s``: ``perf_counter`` at the
    ``bench.window`` annotation's start, minus the tracer's epoch."""
    off = trace.mark()[0] - mark_perf_s * 1e9
    return [(s["name"], s["ts"] * 1e3 + off, (s["ts"] + s["dur"]) * 1e3 + off)
            for s in spans if s.get("ph") == "X"]


def idle_gaps(trace: Trace, spans, n: int = 10, min_s: float = 0.0):
    """The longest gaps between device ops in the window, each named by
    the innermost host span open at its middle (``waiting`` if none)."""
    t0, t1 = trace.mark()
    busy = busy_intervals(trace.ops, t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > min_s * 1e9]
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(open_, key=lambda s: s[2] - s[1])[0] if open_ else "waiting"
        tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]
