"""HLO text analysis: per-device collective-byte accounting for §Roofline.

``cost_analysis`` has no collective term, so we parse the post-SPMD (= per
device) HLO and sum output-shape bytes of every collective op, weighted by
a ring-algorithm traffic model:

    op                  per-device traffic (output bytes O, group size g)
    all-gather          O * (g-1)/g            (~O)
    all-reduce          2 * O * (g-1)/g        (~2O)
    reduce-scatter      O * (g-1)                (input is O*g)
    all-to-all          O * (g-1)/g            (~O)
    collective-permute  O

Group size comes from ``replica_groups`` when parseable (both the explicit
``{{0,1,..},..}`` and the iota ``[groups,size]<=[n]`` forms), else from the
device count.
"""
from __future__ import annotations

import math
import re
from typing import Dict, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """'f32[16,128]' or '(f32[4], bf16[8,8])' -> total bytes."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)     # iota form
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)      # explicit
    if m:
        return len(m.group(1).split(","))
    return default


def _line_traffic(s: str, n_devices: int):
    """One HLO line -> (kind, modeled per-device bytes) or None."""
    m = re.match(r"%?[\w.\-]+\s*=\s*((?:\([^=]*?\))|\S+)\s+([\w\-]+)\(", s)
    if not m:
        return None
    op = m.group(2)
    base = next((c for c in _COLLECTIVES
                 if op == c or op == c + "-start"), None)
    if base is None:
        return None
    o = _shape_bytes(m.group(1))
    g = _group_size(s, n_devices)
    if g <= 1:
        return None
    if base == "all-gather":
        traffic = o * (g - 1) / g
    elif base == "all-reduce":
        traffic = 2 * o * (g - 1) / g
    elif base == "reduce-scatter":
        traffic = o * (g - 1)
    elif base == "all-to-all":
        traffic = o * (g - 1) / g
    else:                                   # collective-permute
        traffic = o
    return base, traffic


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{")
_CALL_SINGLE_RE = re.compile(
    r"(?:condition|body|calls|to_apply)=%([\w.\-]+)")
_CALL_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')


def collective_bytes(hlo_text: str, *, n_devices: int = 1) -> Dict[str, float]:
    """Sum modeled per-device collective traffic by op kind (bytes).

    Collectives inside while bodies (lax.scan / lax.map -- grad accumulation,
    layer scans, chunked prefill) execute once per iteration: the walk below
    multiplies each computation's direct traffic by the product of enclosing
    whiles' ``known_trip_count`` annotations (XLA stamps these for
    statically-counted loops; unannotated loops conservatively count 1)."""
    comps = {}
    entry = None
    current = None
    for raw in hlo_text.splitlines():
        s = raw.strip()
        m = _COMP_RE.match(s)
        if m and s.endswith("{"):
            current = m.group(1)
            comps[current] = []
            if s.startswith("ENTRY"):
                entry = current
            continue
        if s == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(s)
    if not comps:                       # bare op list (tests, fragments)
        comps = {"__flat__": [l.strip() for l in hlo_text.splitlines()]}
        entry = "__flat__"

    direct = {}
    calls = {}
    counts = {}
    for name, lines in comps.items():
        d = {c: 0.0 for c in _COLLECTIVES}
        n = 0
        edges = []
        for s in lines:
            t = _line_traffic(s, n_devices)
            if t is not None:
                d[t[0]] += t[1]
                n += 1
            trip = 1
            if " while(" in s:
                tm = _TRIP_RE.search(s)
                trip = int(tm.group(1)) if tm else 1
            for cm in _CALL_SINGLE_RE.finditer(s):
                edges.append((cm.group(1), trip))
            for cm in _CALL_LIST_RE.finditer(s):
                for callee in re.split(r",\s*", cm.group(1)):
                    edges.append((callee.lstrip("%"), trip))
        direct[name] = d
        counts[name] = n
        calls[name] = edges

    out = {c: 0.0 for c in _COLLECTIVES}
    out["count"] = 0
    stack = set()

    def walk(name, mult):
        if name not in comps or name in stack:
            return
        stack.add(name)
        for c in _COLLECTIVES:
            out[c] += direct[name][c] * mult
        out["count"] += counts[name]
        for callee, trip in calls[name]:
            walk(callee, mult * trip)
        stack.discard(name)

    if entry is None and comps:
        entry = next(iter(comps))
    if entry is not None:
        walk(entry, 1.0)
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"=\s*(?:\([^)]*\)|\S+)\s+{re.escape(opname)}\(",
                          hlo_text))


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` (a dict, or None when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}


__all__ = ["collective_bytes", "analyze_hlo", "count_op",
           "xla_cost_analysis"]


# ===========================================================================
# Full-module analysis: trip-count-aware FLOPs + HBM bytes + collectives
# ===========================================================================
#
# XLA's HloCostAnalysis (what compiled.cost_analysis() exposes) visits each
# while BODY ONCE — a 40-layer lax.scan with 4-way grad accumulation under-
# counts flops/bytes 160x. analyze_hlo() re-derives all three roofline
# inputs from the post-SPMD text with the call-graph walk multiplying by
# known_trip_count:
#   * flops  — 2 * |out| * contracted_size per dot/convolution line
#              (elementwise flops ignored: matmuls dominate every cell)
#   * bytes  — per instruction: output + operand bytes, fusions counted as
#              ONE op (their internals live in registers/VMEM), free ops
#              (parameter/tuple/gte/bitcast/constant) skipped
#   * collective traffic — ring model, as collective_bytes()

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^=]*?\))|\S+)\s+([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CDIM_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "after-all", "iota"}


def _shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def analyze_hlo(hlo_text: str, *, n_devices: int = 1) -> Dict[str, float]:
    comps: Dict[str, list] = {}
    entry = None
    current = None
    symbols: Dict[str, str] = {}           # instr name -> type string
    for raw in hlo_text.splitlines():
        s = raw.strip()
        m = _COMP_RE.match(s)
        if m and s.endswith("{"):
            current = m.group(1)
            comps[current] = []
            if s.startswith("ENTRY"):
                entry = current
            continue
        if s == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(s)
            dm = _DEF_RE.match(s)
            if dm:
                symbols[dm.group(1)] = dm.group(2)
    if not comps:
        comps = {"__flat__": [l.strip() for l in hlo_text.splitlines()]}
        entry = "__flat__"
        for s in comps["__flat__"]:
            dm = _DEF_RE.match(s)
            if dm:
                symbols[dm.group(1)] = dm.group(2)

    # which computations are fusion bodies / scalar appliers (skip bytes)
    fused: set = set()
    for lines in comps.values():
        for s in lines:
            if re.search(r"\bfusion\(", s):
                for cm in re.finditer(r"calls=%([\w.\-]+)", s):
                    fused.add(cm.group(1))
            for cm in re.finditer(r"to_apply=%([\w.\-]+)", s):
                fused.add(cm.group(1))

    per: Dict[str, Dict[str, float]] = {}
    calls: Dict[str, list] = {}
    for name, lines in comps.items():
        flops = bytes_ = coll = 0.0
        ckinds = {c: 0.0 for c in _COLLECTIVES}
        edges = []
        for s in lines:
            dm = _DEF_RE.match(s)
            op = dm.group(3) if dm else ""
            out_type = dm.group(2) if dm else ""
            if op in ("dot", "convolution"):
                out_elems = 1
                dims = _shape_dims(out_type) or []
                for d in dims:
                    out_elems *= d
                contracted = 1
                ops_ = _OPERAND_RE.findall(s[s.index("("):])
                cd = _CDIM_RE.search(s)
                if cd and ops_:
                    lhs_type = symbols.get(ops_[0], "")
                    lhs_dims = _shape_dims(lhs_type)
                    if lhs_dims:
                        for di in cd.group(1).split(","):
                            if di:
                                contracted *= lhs_dims[int(di)]
                flops += 2.0 * out_elems * max(contracted, 1)
            if dm and op not in _FREE_OPS and name not in fused:
                b = _shape_bytes(out_type)
                for oname in _OPERAND_RE.findall(s[s.index("("):])[:8]:
                    b += _shape_bytes(symbols.get(oname, ""))
                bytes_ += b
            t = _line_traffic(s, n_devices)
            if t is not None:
                ckinds[t[0]] += t[1]
                coll += t[1]
            trip = 1
            if " while(" in s:
                tm = _TRIP_RE.search(s)
                trip = int(tm.group(1)) if tm else 1
            for cm in _CALL_SINGLE_RE.finditer(s):
                edges.append((cm.group(1), trip))
            for cm in _CALL_LIST_RE.finditer(s):
                for callee in re.split(r",\s*", cm.group(1)):
                    edges.append((callee.lstrip("%"), trip))
        per[name] = {"flops": flops, "bytes": bytes_, "coll": coll,
                     **ckinds}
        calls[name] = edges

    out = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
           **{c: 0.0 for c in _COLLECTIVES}}
    stack = set()

    def walk(name, mult):
        if name not in comps or name in stack:
            return
        stack.add(name)
        out["flops"] += per[name]["flops"] * mult
        out["bytes"] += per[name]["bytes"] * mult
        out["collective_bytes"] += per[name]["coll"] * mult
        for c in _COLLECTIVES:
            out[c] += per[name][c] * mult
        for callee, trip in calls[name]:
            walk(callee, mult * trip)
        stack.discard(name)

    if entry is not None:
        walk(entry, 1.0)
    return out
