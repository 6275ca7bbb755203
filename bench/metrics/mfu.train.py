"""Model step: training FLOPs of every image of every step in the window
(6 per MAC, 4 for the in-pixel layer) over the window's wall time, as a
share of the chip's bf16 peak, in %."""
from bench import readers, yardstick


def read(ctx):
    if not ctx.get("steps"):
        return None
    flops = yardstick.train_flops(ctx["cfg"], ctx["batch"] * ctx["steps"])
    return readers.peak_share(ctx, flops, ctx["wall_s"])
