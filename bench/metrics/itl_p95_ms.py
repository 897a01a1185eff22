"""95th percentile of the gaps between consecutive tokens of a request,
over every gap that lies in the window (host clock, as the client
receives the tokens)."""
from window import percentile


def read(ctx):
    gaps = ctx.itl_gaps()
    if not gaps:
        return None
    return 1e3 * percentile(gaps, 95)
