"""Shares of the chip's peaks read from a traced slice (``window.Ctx``).

The operations and bytes come from the cell's architecture module
(``ctx.arch``, found by name in ``arch/``): ``decode_attention`` and
``prefill_attention`` per layer, ``decode_step_flops`` and
``prefill_chunk_flops`` per step, and the op names of its attention
kernels in the device trace, ``DECODE_KERNEL`` and ``PREFILL_KERNEL``.
An architecture that has no such kernel gives None, and the share is
left out.

On several devices (one replica per chip) a share sums the work of
every replica and divides by what every traced device could do: the
model flops over the slice's length times the devices times the peak,
and a kernel's roofline time over the kernel's device time summed over
the devices.
"""
from __future__ import annotations

from archlib import roofline_s


def step_mfu(ctx):
    """Model flops of the slice's decode steps and prefill chunks over
    the slice's length times the traced devices times the bf16 peak, in
    percent."""
    tr = ctx.device_trace()
    if tr is None or not (ctx.decode_calls or ctx.prefill_calls):
        return None
    a = ctx.arch
    f = sum(a.decode_step_flops(ctx.d, r, c) for r, c in ctx.decode_calls)
    f += sum(a.prefill_chunk_flops(ctx.d, s, n)
             for s, n in ctx.prefill_calls)
    return 100.0 * f / (tr.window_s * tr.n_devices * ctx.peaks["bf16_flops"])


def _roofline(ctx, kernel, calls, cost):
    tr = ctx.device_trace()
    if tr is None or kernel is None or not calls:
        return None
    secs, n = tr.sum_ops(kernel)
    if not n:
        return None
    need = sum(ctx.d["L"] * roofline_s(*cost(ctx.d, *c), ctx.peaks)
               for c in calls)
    return 100.0 * need / secs


def decode_roofline(ctx):
    """Paged decode attention: roofline time of the slice's decode steps
    (every layer) over the kernel's device time, in percent."""
    a = ctx.arch
    return _roofline(ctx, getattr(a, "DECODE_KERNEL", None),
                     ctx.decode_calls, a.decode_attention)


def prefill_roofline(ctx):
    """Paged prefill attention: the same over the slice's prefill
    chunks."""
    a = ctx.arch
    return _roofline(ctx, getattr(a, "PREFILL_KERNEL", None),
                     ctx.prefill_calls, a.prefill_attention)
