"""What the program records of its training step, against the readers that
use it: the phase rule on the compiled text of a tiny DTI LoRA step, the
per-phase readers and the host-gap reader on a synthetic trace, and the
trainer's pad counter against ``train_pad_frac``."""
from __future__ import annotations

import collections
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, REPO, os.path.join(REPO, "src")]

import bench_tiny  # noqa: E402
from bench import harness, tracing  # noqa: E402
from bench.metrics import _phase  # noqa: E402

_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_CALLED = re.compile(r"(?:body|condition|true_computation|false_computation)"
                     r"=%([\w.\-]+)")
_NOT_LEAF = ("parameter(", "constant(", "tuple(", "get-tuple-element(",
             "bitcast(", " while(", " conditional(", " call(")


def leaf_op_names(text):
    """op_name of every op the compiled module runs as one device op: the
    instructions of the entry computation and of the loop bodies and
    branches it runs, less parameters, constants, tuples and the control
    flow that encloses other ops; fused computations are not walked."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur and line.strip():
            comps[cur].append(line.strip())
    run, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in run:
            continue
        run.add(c)
        for ln in comps[c]:
            todo += _CALLED.findall(ln)
            b = re.search(r"branch_computations=\{([^}]*)\}", ln)
            if b:
                todo += re.findall(r"%([\w.\-]+)", b.group(1))
            if " call(" in ln:
                todo += re.findall(r"to_apply=%([\w.\-]+)", ln)
    out = []
    for c in run:
        for ln in comps[c]:
            if any(s in ln for s in _NOT_LEAF):
                continue
            m = re.search(r'op_name="([^"]*)"', ln)
            if m:
                out.append(m.group(1))
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("phase_root")))


@pytest.fixture(scope="module")
def tiny_ctx(root):
    cell = harness.load_cell(root, "tiny.train", seed=7, seconds=1.0,
                             trace=True)
    gen = harness.generator(cell)
    batches = gen.batches(cell.mix, cell.config["model"]["vocab_size"],
                          cell.config["train"]["rows_per_step"], cell.seed)
    return {"cell": cell, "kind": "train", "window_batches": batches[:1],
            "window": gen.geometry(cell.mix)["window"], "log": lambda *a: None}


@pytest.fixture(scope="module")
def tiny_step_text(tiny_ctx):
    """The compiled text of the tiny cell's step (remat on), as the
    readers lower it again."""
    return _phase._step_text(tiny_ctx)


def test_every_leaf_op_of_the_step_has_one_phase(tiny_step_text):
    ops = leaf_op_names(tiny_step_text)
    assert len(ops) > 100
    by = collections.Counter(_phase.phase(op) for op in ops)
    assert None not in by, [op for op in ops if _phase.phase(op) is None][:5]
    assert set(by) == set(_phase.PHASES)
    lora = {_phase.phase(op) for op in ops if _phase.is_lora(op)}
    assert lora == {"fwd", "remat", "bwd"}
    # the kernels' names reach op_name: the forward kernel in the forward
    # and in remat's recompute, dq and dk/dv in the backward
    kern = {(k, _phase.phase(op)) for op in ops
            for k in ("winattn_fwd", "winattn_dq", "winattn_dkv")
            if f"/{k}/" in op}
    assert kern == {("winattn_fwd", "fwd"), ("winattn_fwd", "remat"),
                    ("winattn_dq", "bwd"), ("winattn_dkv", "bwd")}


def test_phase_rule_is_exclusive_and_reads_the_scopes():
    cases = {
        "jit(step)/train.grad/jvp(lm.forward)/while/body/closed_call/"
        "lm.mlp/lora/dot_general": "fwd",
        "jit(step)/train.grad/transpose(jvp(lm.forward))/while/body/"
        "closed_call/checkpoint/lora/dot_general": "bwd",
        "jit(step)/train.grad/transpose(jvp(lm.forward))/while/body/"
        "closed_call/checkpoint/rematted_computation/lm.mlp/dot_general":
            "remat",
        "jit(step)/train.optimizer/sub": "opt",
        "jit(step)/lm.attn/cos": "fwd",               # hoisted out of scan
        "jit(step)/train.grad/jvp(lm.loss)/log_softmax": "fwd",
        "jit(step)/transpose(jvp(closed_call))/checkpoint/"
        "rematted_computation/dot_general": "remat",  # a program unscoped
        "jit(step)/add": None,
        "": None,
        None: None,
    }
    for op, want in cases.items():
        assert _phase.phase(op) == want, op
    assert _phase.is_lora("a/lora/dot_general") and _phase.is_lora("a/lora")
    assert not _phase.is_lora("a/lora_b/dot") and not _phase.is_lora(None)
    text = ('%fc.7 (p: bf16[2]) -> (bf16[2], bf16[2]) {\n'
            '  %sub.1 = bf16[2]{0} subtract(%p, %p), metadata={op_name='
            '"jit(step)/train.grad/jvp(lm.forward)/lm.attn/sub"}\n'
            '  ROOT %t = (bf16[2]{0}, bf16[2]{0}) tuple(%sub.1, %sub.1)\n'
            '}\n\nENTRY %main (p: bf16[2]) -> bf16[2] {\n'
            '  %fusion.12 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(step)/train.optimizer/mul" '
            'stack_frame_id=3}\n'
            '  %subtract_convert_fusion.3 = (bf16[2]{0}, bf16[2]{0}) '
            'fusion(%p), kind=kLoop, calls=%fc.7\n'
            '  ROOT %copy.4 = f32[] copy(%x)\n}\n')
    # a multi-output fusion has no op_name of its own: it takes the one
    # nearest the root of the computation it calls
    assert _phase.names_from_text(text) == {
        "sub.1": "jit(step)/train.grad/jvp(lm.forward)/lm.attn/sub",
        "fusion.12": "jit(step)/train.optimizer/mul",
        "subtract_convert_fusion.3":
            "jit(step)/train.grad/jvp(lm.forward)/lm.attn/sub"}
    assert _phase.instruction(
        "%fusion.12 = bf16[2]{0} fusion(%p)") == "fusion.12"


def _synthetic_ctx(names, spans=()):
    """Two steps of a step program: [0, 100) and [120, 220) ns, each
    with a forward, a remat, a backward and an optimizer op, and a copy
    that carries no op_name."""
    ops = []
    for s0 in (0, 120):
        ops += [("%while.1 = (...) while(...)", s0, s0 + 80),
                ("%fusion.1 = bf16[2] fusion(...)", s0, s0 + 20),
                ("%fusion.2 = bf16[2] fusion(...)", s0 + 20, s0 + 35),
                ("%fusion.3 = bf16[2] fusion(...)", s0 + 35, s0 + 75),
                ("%fusion.4 = bf16[2] fusion(...)", s0 + 80, s0 + 90),
                ("%copy.5 = bf16[2] copy(...)", s0 + 90, s0 + 100)]
    mods = [("jit_step(1)", 0, 100), ("jit_step(1)", 120, 220)]
    tr = tracing.Trace(ops, mods, [("bench.window", 0, 230)])
    return {"kind": "train", "trace": tr, "spans": list(spans),
            "log": lambda *a: None, "cell": None, "window": 0,
            "window_batches": []}


STEP_NAMES = {
    "fusion.1": "jit(step)/train.grad/jvp(lm.forward)/lm.mlp/lora/dot",
    "fusion.2": "jit(step)/train.grad/transpose(jvp(lm.forward))/"
                "checkpoint/rematted_computation/lm.attn/dot",
    "fusion.3": "jit(step)/train.grad/transpose(jvp(lm.forward))/"
                "checkpoint/lora/dot",
    "fusion.4": "jit(step)/train.optimizer/mul",
}


def _readers(root):
    return {m: harness.load_module(root, "metrics", m) for m in (
        "train_fwd_ms", "train_remat_ms", "train_bwd_ms", "train_opt_ms",
        "train_lora_ms", "train_host_ms")}


def test_phase_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(_phase, "_relowered_names", lambda ctx: STEP_NAMES)
    ctx = _synthetic_ctx(STEP_NAMES)
    got = {m: r.read(ctx) for m, r in _readers(REPO).items()}
    ns = 1e-6          # ms in a nanosecond
    assert got["train_fwd_ms"] == pytest.approx(20 * ns)
    assert got["train_remat_ms"] == pytest.approx(15 * ns)
    assert got["train_bwd_ms"] == pytest.approx(40 * ns)
    assert got["train_opt_ms"] == pytest.approx(10 * ns)
    assert got["train_lora_ms"] == pytest.approx(60 * ns)
    assert got["train_host_ms"] is None            # no train.wait spans


def test_phase_readers_are_silent_on_an_unscoped_program(monkeypatch):
    """The parent of the scopes: no op carries ``train.grad``, so every
    phase reader finds nothing and none raises."""
    bare = {k: re.sub(r"train\.grad/|lm\.\w+/|lora/|train\.optimizer/",
                      "", v) for k, v in STEP_NAMES.items()}
    monkeypatch.setattr(_phase, "_relowered_names", lambda ctx: bare)
    ctx = _synthetic_ctx(bare)
    assert all(r.read(ctx) is None for r in _readers(REPO).values())


def test_host_ms_reads_the_gaps_between_waits():
    spans = [{"name": "train.wait", "ph": "X", "ts": t, "dur": 2000.0}
             for t in (1000.0, 5000.0, 9500.0)]
    spans.append({"name": "train.step", "ph": "X", "ts": 0.0, "dur": 3.0})
    r = _readers(REPO)["train_host_ms"]
    # gaps 5000 - 3000 and 9500 - 7000 us: 2.0 and 2.5 ms
    assert r.read({"kind": "train", "spans": spans}) == pytest.approx(2.25)
    assert r.read({"kind": "serve", "spans": spans}) is None


def test_trainer_pad_counter_is_train_pad_frac(tiny_ctx):
    """The trainer's ``train.pad_tokens`` / ``train.tokens`` over a run is
    the batch part of ``train_pad_frac`` on the same batches (no trace,
    so no kernel pad)."""
    from repro.obs.trace import SpanTracer
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import Trainer, init_train_state
    cell = tiny_ctx["cell"]
    gen = harness.generator(cell)
    bs = gen.batches(cell.mix, cell.config["model"]["vocab_size"],
                     cell.config["train"]["rows_per_step"], cell.seed)
    state = init_train_state({"w": np.zeros(2, np.float32)},
                             OptimizerConfig(lr=1e-3))
    trainer = Trainer(lambda s, b, r: (s, {"loss": np.float32(0.0)}), state,
                      log_every=100, tracer=SpanTracer())
    trainer.run(iter(bs), n_steps=len(bs))
    c = trainer.metrics.snapshot("train.")
    pad = harness.load_module(REPO, "metrics", "train_pad_frac").read(
        {"kind": "train", "window_batches": bs, "log": lambda *a: None})
    assert 100.0 * c["train.pad_tokens"]["value"] / c["train.tokens"][
        "value"] == pytest.approx(pad, rel=1e-12)
    assert c["train.targets"]["value"] == sum(int(b["is_sum"].sum())
                                              for b in bs)
    assert c["train.steps"]["value"] == len(bs)


# A recorded slice of the traced train step on a v5e (one whole step and
# the host gap on either side, cut by ``make_trace_slice.py``): the XLA
# ops, the jitted modules, the program's annotated spans and a
# ``bench.window`` mark set over the slice. The trace carries no op_name;
# beside it, the op_name of each of its instructions as the phase readers
# found them by lowering the step again in that run.
RECORDED = os.path.join(HERE, "data", "train_step_phases.xplane.pb.gz")
RECORDED_NAMES = os.path.join(HERE, "data", "train_step_phases.names.json.gz")


@pytest.fixture(scope="module")
def recorded_trace():
    import gzip
    import json
    from jax.profiler import ProfileData
    with gzip.open(RECORDED, "rb") as f:
        tr = tracing.from_profile(ProfileData.from_serialized_xspace(f.read()))
    with gzip.open(RECORDED_NAMES, "rt") as f:
        names = json.load(f)
    return tr, names


@pytest.fixture
def recorded(recorded_trace, monkeypatch):
    tr, names = recorded_trace
    monkeypatch.setattr(_phase, "_relowered_names", lambda ctx: names)
    spans = [{"name": n, "ph": "X", "ts": a / 1e3, "dur": (b - a) / 1e3}
             for n, a, b in tr.host if n.startswith("train.")]
    return {"kind": "train", "trace": tr, "spans": spans,
            "log": lambda *a: None}


def test_readers_on_a_recorded_step(recorded):
    got = {m: r.read(dict(recorded)) for m, r in _readers(REPO).items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert _phase.steps(recorded) == 1
    busy_ms = tracing.busy_s(recorded["trace"]) * 1e3
    phases = sum(got[f"train_{p}_ms"] for p in _phase.PHASES)
    assert 0.95 * busy_ms <= phases <= busy_ms
    assert got["train_lora_ms"] < phases
    assert 0 < got["train_host_ms"] < 50


def test_kernel_kind_agrees_with_the_kernel_names(recorded):
    """Every event that ``tracing.kernel_kind`` names carries that
    kernel's name, and no other kernel's, in its instruction name and in
    its op_name; and the forward kernel runs twice a layer (forward and
    remat), dq and dk/dv once."""
    tr = recorded["trace"]
    kinds = ("winattn_fwd", "winattn_dq", "winattn_dkv")
    seen = collections.Counter()
    for name, a, _, op in _phase.ops(dict(recorded)):
        k = tracing.kernel_kind(name, tracing.module_at(tr, a))
        if k:
            seen[k] += 1
            assert _phase.instruction(name).split(".")[0] == k, name[:80]
            assert [n for n in kinds if f"/{n}/" in op] == [k], op
    assert seen["winattn_fwd"] == 2 * seen["winattn_dq"] == 2 * seen[
        "winattn_dkv"] > 0
