"""Kernels: the in-pixel forward kernel of each training step
(`p2m_conv_pallas`) against its roofline for the whole batch.  In %."""
from bench import readers, yardstick


def read(ctx):
    k = readers.kernel_calls(ctx, "p2m_conv_pallas")
    if k is None:
        return None
    flops, byts = yardstick.pixel_fwd_cost(ctx["cfg"], ctx["batch"])
    return readers.roofline_share(ctx, flops * k[0], byts * k[0], k[1])
