"""Model FLOPs of the window's training steps (``accounts/lm_step``) over
the window's wall time and the chip's bf16 peak."""
from bench.accounts import lm_step
from bench.metrics import _lib


def read(ctx):
    if ctx.get("kind") != "train" or not ctx["window_batches"]:
        return None
    cfg = _lib.ref_cfg(ctx)
    flops = sum(lm_step.train_flops(cfg, b, ctx["window"])
                for b in ctx["window_batches"])
    return 100.0 * flops / (ctx["window_s"] * _lib.peak_flops(ctx))
