"""Model step: device time per run of the contiguous prefill chunk
program, which the engine names ``jit_prefill_chunk``, found by that
name among the traced slice's programs (the prefill half of the model
step; ``decode_step_ms`` is the decode half).  A program that has no
such name, as before the engine named its steps, gives nothing."""
PROGRAM = "jit_prefill_chunk"


def read(ctx):
    tr = ctx.device_trace()
    if tr is None:
        return None
    names = [m for m in tr.module_s if m.split("(", 1)[0] == PROGRAM]
    n = sum(tr.module_n[m] for m in names)
    return 1e3 * sum(tr.module_s[m] for m in names) / n if n else None
