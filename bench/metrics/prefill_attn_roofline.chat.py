"""Kernels: the paged prefill attention kernel's roofline time over its
device time in the traced slice (chat cells)."""
import flops

KERNEL = ("_paged_prefill",)


def read(ctx):
    return flops.prefill_roofline(ctx, KERNEL)
