"""Kernels: the in-pixel forward kernel (`p2m_conv_pallas`) of each
serving launch against its roofline, max(FLOPs / peak, bytes / HBM
bandwidth) for a full microbatch; bandwidth bounds it at the paper
geometry.  In %."""
from bench import readers, yardstick


def read(ctx):
    k = readers.kernel_calls(ctx, "p2m_conv_pallas")
    if k is None:
        return None
    flops, byts = yardstick.pixel_fwd_cost(ctx["cfg"], ctx["slots"])
    return readers.roofline_share(ctx, flops * k[0], byts * k[0], k[1])
