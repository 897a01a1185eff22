"""Model step: device time per run of the fused decode program, from
the device trace (the program runs that ran the architecture's decode
kernel, ``DECODE_KERNEL`` of its module)."""


def read(ctx):
    tr = ctx.device_trace()
    kernel = getattr(ctx.arch, "DECODE_KERNEL", None)
    if tr is None or kernel is None:
        return None
    secs, n = tr.sum_programs(kernel)
    return 1e3 * secs / n if n else None
