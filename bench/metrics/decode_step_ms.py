"""Model step: device time per run of the fused decode program, from
the device trace (the program runs that ran the paged decode kernel)."""
KERNEL = "_paged_decode_pallas"


def read(ctx):
    tr = ctx.device_trace()
    if tr is None:
        return None
    secs, n = tr.sum_programs(KERNEL)
    return 1e3 * secs / n if n else None
