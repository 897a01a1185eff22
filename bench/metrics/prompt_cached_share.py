"""Cache manager: share of the prompt tokens of requests due in the
window that came from reused blocks (prefix and segment hits, counted
by the engine per request) rather than from prefill."""


def read(ctx):
    tot = sum(r.prompt_len for r in ctx.records if r.admit is not None)
    hit = sum(r.hit_tokens for r in ctx.records if r.admit is not None)
    return 100.0 * hit / tot if tot and hit else None
