"""Device ms a training step spends in the backward pass (under ``transpose(``,
outside ``rematted_computation``):
leaf ops of the traced window whose op_name ``metrics/_phase.phase`` puts
there, over the window's ``jit_step`` runs."""
from bench.metrics import _phase


def read(ctx):
    return _phase.ms_per_step(ctx, lambda op: _phase.phase(op) == "bwd")
