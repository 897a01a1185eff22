"""Device: share of the traced slice in which no operation ran on the
chip (one minus the union of operation intervals over the slice)."""


def read(ctx):
    tr = ctx.device_trace()
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
