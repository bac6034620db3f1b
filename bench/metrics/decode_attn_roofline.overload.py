"""The decode kernel's calls in the traced window against their roofline:
per scheduler step, the least time ``accounts/decode_attn`` allows for its
units, times the layers, over the kernel's summed device time."""
from bench import peaks, tracing
from bench.accounts import decode_attn
from bench.metrics import _lib


def read(ctx):
    tr = ctx.get("trace")
    ev = tracing.kernel_events(tr).get("decode_attn") if tr else None
    steps = _lib.serve_steps(ctx) if ev else []
    if not ev or not steps:
        return None
    cfg, kind = _lib.ref_cfg(ctx), _lib.device_kind(ctx)
    t_min = sum(peaks.roofline_s(*decode_attn.account(cfg, u, ctx["window"]),
                                 kind)[0] for u in steps)
    busy = sum(b - a for a, b in ev) / 1e9
    ctx["log"](f"[metric] decode_attn: {len(ev)} calls over {len(steps)} "
               f"steps, {busy:.4f}s, least {t_min * cfg['n_layers']:.4f}s")
    return 100.0 * t_min * cfg["n_layers"] / busy
