"""Kernels: the paged prefill attention kernel's roofline time over its
device time in the traced slice (chat cells).  The kernel and its needs
are the architecture module's."""
import flops


def read(ctx):
    return flops.prefill_roofline(ctx)
