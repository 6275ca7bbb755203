"""Kernels: the in-pixel weight-gradient kernel (`p2m_bwd_dw_pallas`)
of each training step against its roofline for the whole batch.  In %."""
from bench import readers, yardstick


def read(ctx):
    k = readers.kernel_calls(ctx, "p2m_bwd_dw_pallas")
    if k is None:
        return None
    flops, byts = yardstick.pixel_dw_cost(ctx["cfg"], ctx["batch"])
    return readers.roofline_share(ctx, flops * k[0], byts * k[0], k[1])
