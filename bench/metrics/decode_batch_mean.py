"""Scheduler: rows decoded per fused decode step, over the steps of the
traced slice (counted around the engine's decode call)."""


def read(ctx):
    if not ctx.decode_calls:
        return None
    return sum(r for r, _ in ctx.decode_calls) / len(ctx.decode_calls)
