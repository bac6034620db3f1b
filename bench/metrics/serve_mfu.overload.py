"""Model FLOPs of the scheduler steps in the traced window
(``accounts/lm_step.serve_flops``: fed tokens, bucket padding left out)
over the traced window and the chip's bf16 peak."""
from bench import tracing
from bench.accounts import lm_step
from bench.metrics import _lib


def read(ctx):
    tr = ctx.get("trace")
    steps = _lib.serve_steps(ctx) if tr is not None else []
    if not steps:
        return None
    cfg = _lib.ref_cfg(ctx)
    flops = sum(lm_step.serve_flops(cfg, u, ctx["window"]) for u in steps)
    return 100.0 * flops / (tracing.window_s(tr) * _lib.peak_flops(ctx))
