"""Kernels: the routed experts' grouped matmul (``EXPERT_KERNEL`` of the
architecture module) as its roofline time over its device time in the
traced slice.

Roofline time is what the slice's calls need in every MoE layer
(``Lm`` of them), from the arch module's ``expert_ffn``: for a decode
step of t rows or a prefill chunk of t tokens, the flops of t x k
token-expert assignments, which are exact, and bytes of the
assignments' activations plus the weights of the experts those t tokens
hit.  How many distinct experts a call hits is an expectation, E (1 -
(1 - k/E)^t) under uniform routing, and not a count: the engine counts
tokens per expert on the device (``ServingEngine.stats()``
``moe_expert_tokens``), but readers see only the counters the harness
takes around the engine's calls, and those hold no expert counts.  An
architecture without the kernel gives None."""
from archlib import roofline_s


def read(ctx):
    tr = ctx.device_trace()
    a = ctx.arch
    kernel = getattr(a, "EXPERT_KERNEL", None)
    if tr is None or kernel is None:
        return None
    secs, n = tr.sum_ops(kernel)
    calls = ([rows for rows, _ in ctx.decode_calls]
             + [t for _, t in ctx.prefill_calls])
    if not n or not calls:
        return None
    need = sum(ctx.d["Lm"] * roofline_s(*a.expert_ffn(ctx.d, t), ctx.peaks)
               for t in calls)
    return 100.0 * need / secs
