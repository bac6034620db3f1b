"""Share of the traced window in which no op ran on the device."""
from bench.metrics import _lib


def read(ctx):
    return _lib.idle_pct(ctx) if ctx.get("kind") == "serve" else None
