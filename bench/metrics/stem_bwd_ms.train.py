"""Model parts: device ms per training step of the in-pixel layer's backward pass (`p2m_stem` transposed, and `p2m_conv_bwd`: im2col, the dX/dW kernels, col2im).
Summed over the operations inside each `jit_step` program (see
`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "stem_bwd")
