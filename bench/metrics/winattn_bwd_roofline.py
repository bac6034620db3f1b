"""The windowed kernel's backward (dq and dk/dv kernels of each layer)
against the roofline of ``accounts/winattn_bwd``."""
from bench import peaks, tracing
from bench.accounts import winattn_bwd
from bench.metrics import _lib


def read(ctx):
    tr = ctx.get("trace")
    ev = tracing.kernel_events(tr) if tr else {}
    dq, dkv = ev.get("winattn_dq"), ev.get("winattn_dkv")
    if not dq or not dkv or not ctx.get("window_batches"):
        return None
    cfg, kind = _lib.ref_cfg(ctx), _lib.device_kind(ctx)
    bounds = [peaks.roofline_s(*winattn_bwd.account(cfg, b, ctx["window"]),
                               kind) for b in ctx["window_batches"]]
    t_min = sum(t for t, _ in bounds) / len(bounds)
    busy = sum(b - a for a, b in dq + dkv) / 1e9
    ctx["log"](f"[metric] winattn_bwd: {len(dq)} dq + {len(dkv)} dk/dv calls, "
               f"{busy:.4f}s, bound by {bounds[0][1]}, least "
               f"{t_min * 1e3:.4f} ms per layer")
    return 100.0 * min(len(dq), len(dkv)) * t_min / busy
