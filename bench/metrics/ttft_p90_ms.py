"""90th percentile of time to first token, from each request's due
time, over the requests due in the window (host clock)."""
from window import percentile


def read(ctx):
    if not ctx.records:
        return None
    return 1e3 * percentile([r.ttft for r in ctx.records], 90)
