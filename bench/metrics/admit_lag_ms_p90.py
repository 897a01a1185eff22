"""Front end: 90th percentile of the lag from a request's due time to
its submission into the engine, over the requests due in the window
(host clock).  It grows when the pump loop falls behind the schedule."""
from window import percentile


def read(ctx):
    lags = [r.admit - r.due for r in ctx.records if r.admit is not None]
    return 1e3 * percentile(lags, 90) if lags else None
