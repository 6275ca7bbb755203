"""Model step: model FLOPs of the frames each launch computes (every
slot, 2 per MAC of the census, the in-pixel layer included) over the
step program's device time, as a share of the chip's bf16 peak, in %."""
from bench import readers, yardstick


def read(ctx):
    m = readers.module_calls(ctx, "jit_forward")
    if m is None:
        return None
    flops = yardstick.forward_flops(ctx["cfg"], ctx["slots"] * m[0])
    return readers.peak_share(ctx, flops, m[1])
