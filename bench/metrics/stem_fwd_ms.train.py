"""Model parts: device ms per training step of the in-pixel layer's forward pass (`p2m_stem`, with the P²M conv kernel and its reshapes).
Summed over the operations inside each `jit_step` program (see
`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "stem_fwd")
