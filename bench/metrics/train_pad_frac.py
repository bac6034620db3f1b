"""Pad tokens of the window's batches, and the kernel's pad of each row
to a block multiple (read from the sequence length of the forward kernel's
output in the trace), over the padded row tokens."""
import re

from bench import tracing


def read(ctx):
    bs = ctx.get("window_batches")
    if ctx.get("kind") != "train" or not bs:
        return None
    rows = sum(b["valid"].size for b in bs)
    pad = sum(int((~b["valid"]).sum()) for b in bs)
    s = bs[0]["valid"].shape[1]
    s_kernel = s
    tr = ctx.get("trace")
    if tr is not None:
        for name, a, _ in tr.ops:
            if tracing.kernel_kind(name, tracing.module_at(tr, a)) == "winattn_fwd":
                m = re.search(r"bf16\[\d+,\d+,(\d+),\d+\]", name)
                s_kernel = int(m.group(1)) if m else s
                break
    kpad = rows // s * (s_kernel - s)
    ctx["log"](f"[metric] train_pad_frac: batch pad {pad} of {rows} tokens, "
               f"kernel pad {kpad} tokens (rows of {s} run as {s_kernel})")
    return 100.0 * (pad + kpad) / (rows + kpad)
