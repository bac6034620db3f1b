"""Device ms a training step spends in the optimizer (``train.optimizer``:
gradient compression and the AdamW update):
leaf ops of the traced window whose op_name ``metrics/_phase.phase`` puts
there, over the window's ``jit_step`` runs."""
from bench.metrics import _phase


def read(ctx):
    return _phase.ms_per_step(ctx, lambda op: _phase.phase(op) == "opt")
