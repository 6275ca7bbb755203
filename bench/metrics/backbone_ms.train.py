"""Model parts: device ms per training step of the inverted-residual blocks, the head and the classifier, forward and backward (`backbone/`, `classifier`).
Summed over the operations inside each `jit_step` program (see
`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "backbone")
