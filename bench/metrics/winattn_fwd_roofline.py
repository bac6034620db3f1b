"""The windowed kernel's forward calls (forward and rematerialised) against
their roofline: the least time ``accounts/winattn_fwd`` allows per call,
times the calls, over the calls' summed device time."""
from bench import peaks, tracing
from bench.accounts import winattn_fwd
from bench.metrics import _lib


def read(ctx):
    tr = ctx.get("trace")
    ev = tracing.kernel_events(tr).get("winattn_fwd") if tr else None
    if not ev or not ctx.get("window_batches"):
        return None
    cfg, kind = _lib.ref_cfg(ctx), _lib.device_kind(ctx)
    bounds = [peaks.roofline_s(*winattn_fwd.account(cfg, b, ctx["window"]),
                               kind) for b in ctx["window_batches"]]
    t_min = sum(t for t, _ in bounds) / len(bounds)
    busy = sum(b - a for a, b in ev) / 1e9
    ctx["log"](f"[metric] winattn_fwd: {len(ev)} calls, {busy:.4f}s, bound "
               f"by {bounds[0][1]}, least {t_min * 1e3:.4f} ms per call")
    return 100.0 * len(ev) * t_min / busy
