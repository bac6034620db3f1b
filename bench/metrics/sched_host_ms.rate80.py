"""Host time per scheduler step: mean ``scheduler.step`` span less its
``harvest`` child, the one place a step waits on the device."""
from bench.metrics import _lib


def read(ctx):
    return _lib.sched_host_ms(ctx) if ctx.get("kind") == "serve" else None
