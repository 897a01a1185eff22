"""Kernels: the paged decode attention kernel's roofline time over its
device time in the traced slice.  Roofline time is what the kernel's
calls need (keys and values of each active row's valid tokens, per
layer) at the chip's peaks; device time sums the kernel's events.  The
kernel and its needs are the architecture module's."""
import flops


def read(ctx):
    return flops.decode_roofline(ctx)
