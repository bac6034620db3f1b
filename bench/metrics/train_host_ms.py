"""Host time between training steps: the mean gap from the end of one
``train.wait`` span (the step's loss is ready) to the start of the next,
over the traced window. It holds ``train.fetch``, ``train.checkpoint``,
``train.feed`` and ``train.dispatch``: the time the chip waits on the host
when the next step is not queued behind the last."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    waits = sorted((s["ts"], s["ts"] + s["dur"]) for s in ctx.get("spans", [])
                   if s.get("ph") == "X" and s["name"] == "train.wait")
    gaps = [b[0] - a[1] for a, b in zip(waits, waits[1:])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e3
