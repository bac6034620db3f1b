"""Context tokens served from the prefix cache over context tokens of the
requests finished in the window (the scheduler's ``prefix_hit_rate``
counter pair)."""


def read(ctx):
    tel = ctx.get("telemetry")
    if ctx.get("kind") != "serve" or not tel or not tel.get("steps"):
        return None
    return 100.0 * tel["prefix_hit_rate"]
