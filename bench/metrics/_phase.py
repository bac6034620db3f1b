"""The training step's device time by phase, read from the ``op_name`` that
the program's named scopes leave on every op it compiles.

The program scopes its step (``repro.train.trainer``, ``repro.models``):
``train.grad`` around ``value_and_grad``, ``lm.forward`` / ``lm.attn`` /
``lm.mlp`` / ``lm.loss`` inside it, ``lora`` around each adapter term and
``train.optimizer`` around the update. JAX adds ``transpose(...)`` to the
name of every op of the backward pass and ``rematted_computation`` to
remat's second forward inside it. :func:`phase` is the one rule that turns
an ``op_name`` into a phase; every reader of this layer goes through it.

The trace does not carry ``op_name``: an event of the ``XLA Ops`` line has
the instruction's HLO text for its name and only timing stats (a v5e
trace, jax 0.9). So the step is lowered again from the cell's
configuration, as the train driver builds it (a hit in the persistent
compile cache), and each event's instruction name (``fusion.1049``, the
head of its name) is mapped to the ``op_name`` of its line in
``compiled.as_text()``. Ops that XLA adds (``copy-start``/``copy-done``,
``slice-start``/``slice-done``) carry none and fall outside every phase.

A program without the scopes (the parent of the change that added them)
gives no op a phase, and the readers return None.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import tracing

PHASES = ("fwd", "remat", "bwd", "opt")
_FWD = re.compile(r"train\.grad|lm\.(forward|attn|mlp|loss)|(^|/)lora(/|$)")
_LORA = re.compile(r"(^|/)lora(/|$)")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def phase(op_name: Optional[str]) -> Optional[str]:
    """``opt``, ``remat``, ``bwd`` or ``fwd``; None for an op outside the
    program's scopes."""
    if not op_name:
        return None
    if "train.optimizer" in op_name:
        return "opt"
    if "transpose(" in op_name:
        return "remat" if "rematted_computation" in op_name else "bwd"
    if _FWD.search(op_name):
        return "fwd"
    return None


def is_lora(op_name: Optional[str]) -> bool:
    return bool(op_name) and bool(_LORA.search(op_name))


def instruction(event_name: str) -> str:
    """``%fusion.1049 = bf16[...] fusion(...)`` -> ``fusion.1049``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def names_from_text(text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled HLO module's text. A
    fusion whose own line has no op_name (a multi-output fusion: its root
    is a tuple) takes the op_name nearest the root of the computation it
    calls."""
    last: Dict[str, str] = {}           # computation -> op_name nearest ROOT
    rows = []
    comp = None
    for line in text.splitlines():
        c = _COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if op and comp:
            last[comp] = op.group(1)
        calls = _CALLS.search(line)
        rows.append((m.group(1), op and op.group(1),
                     calls and calls.group(1)))
    out = {}
    for name, op, calls in rows:
        op = op or (calls and last.get(calls))
        if op:
            out[name] = op
    return out


def _relowered_names(ctx) -> Dict[str, str]:
    """{instruction: op_name} of the cell's step, lowered again."""
    return names_from_text(_step_text(ctx))


def _step_text(ctx) -> str:
    """The compiled text of the cell's step, built from its configuration
    as the train driver builds it and lowered for the window's batches."""
    import jax
    from bench import harness, weights
    from repro.launch.train import make_lm_loss_fn
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import (TrainOptions, init_train_state,
                                     make_train_step)
    cell = ctx["cell"]
    mcfg = harness.model_config(cell)
    opt = cell.config["optimizer"]
    ocfg = OptimizerConfig(lr=opt["lr"], betas=tuple(opt["betas"]),
                           eps=opt["eps"], weight_decay=opt["weight_decay"],
                           grad_clip=opt["grad_clip"],
                           schedule=opt["schedule"],
                           warmup_steps=opt["warmup_steps"],
                           total_steps=opt["total_steps"], trainable="lora")
    step = make_train_step(make_lm_loss_fn(mcfg, ctx["window"]), ocfg,
                           TrainOptions(donate=True))
    state = jax.eval_shape(lambda p: init_train_state(p, ocfg),
                           weights.layout(mcfg))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in ctx["window_batches"][0].items()}
    return step.lower(state, batch, jax.random.PRNGKey(0)).compile().as_text()


def ops(ctx) -> List[Tuple[str, float, float, Optional[str]]]:
    """The window's leaf device ops as (name, start_ns, end_ns, op_name),
    clipped to the window; read once per run."""
    if "_phase_ops" in ctx:
        return ctx["_phase_ops"]
    tr = ctx["trace"]
    try:
        names = _relowered_names(ctx)
    except Exception as e:  # noqa: BLE001 - a reader never fails a run
        ctx["log"](f"[metric] phases: lowering the step failed: {e!r}")
        names = {}
    t0, t1 = tr.mark()
    out = [(n, max(a, t0), min(b, t1), names.get(instruction(n)))
           for n, a, b in tracing.leaf_ops(tr.ops) if b > t0 and a < t1]
    ctx["log"](f"[metric] phases: {sum(op is not None for *_, op in out)} "
               f"of {len(out)} window ops named by the step lowered again")
    ctx["_phase_ops"] = out
    return out


def steps(ctx) -> int:
    """``jit_step`` module runs that start inside the window."""
    tr = ctx["trace"]
    t0, t1 = tr.mark()
    return sum(1 for m in tr.modules
               if m[0].startswith("jit_step") and t0 <= m[1] < t1)


def ms_per_step(ctx, keep) -> Optional[float]:
    """Mean device ms a step of the window's leaf ops whose op_name
    ``keep`` accepts; None off a traced train run, or where no op carries
    the program's ``train.grad`` scope."""
    if ctx.get("kind") != "train" or ctx.get("trace") is None:
        return None
    n = steps(ctx)
    if not n:
        return None
    evs = ops(ctx)
    if not any(op and "train.grad" in op for *_, op in evs):
        return None
    summary(ctx)
    return sum(b - a for _, a, b, op in evs if keep(op)) / n / 1e6


def summary(ctx) -> None:
    """Log each phase's time a step, and the ops that no phase holds."""
    if "_phase_logged" in ctx:
        return
    ctx["_phase_logged"] = True
    evs, n = ops(ctx), max(steps(ctx), 1)
    tot: Dict[str, float] = {}
    rest: Dict[str, float] = {}
    for name, a, b, op in evs:
        p = phase(op) or "none"
        tot[p] = tot.get(p, 0.0) + (b - a) / n / 1e6
        if p == "none":
            k = re.sub(r"\.\d+$", "", instruction(name))
            rest[k] = rest.get(k, 0.0) + (b - a) / n / 1e6
    busy = sum(tot.values())
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:6]
    ctx["log"](f"[metric] phases, ms a step over {n} steps: "
               + ", ".join(f"{p} {tot.get(p, 0.0):.3f}"
                           for p in PHASES + ("none",))
               + f"; leaf ops {busy:.3f}; outside every phase: "
               + ", ".join(f"{k} {v:.3f}" for k, v in top))
