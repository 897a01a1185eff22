"""Model step: model flops of the traced slice's
decode steps and prefill chunks over the slice's length times the
traced devices times the bf16 peak (``flops.step_mfu``)."""
import flops


def read(ctx):
    return flops.step_mfu(ctx)
