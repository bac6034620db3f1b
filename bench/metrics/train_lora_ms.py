"""Device ms a training step spends in the LoRA adapter terms (ops under
the program's ``lora`` scope) in the forward, the rematerialised forward
and the backward; the optimizer's update of the adapters is not counted."""
from bench.metrics import _phase


def read(ctx):
    return _phase.ms_per_step(
        ctx, lambda op: _phase.is_lora(op) and _phase.phase(op) is not None
        and _phase.phase(op) != "opt")
