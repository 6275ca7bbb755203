"""Model parts: device ms per training step of the loss and the optimizer update (`loss`, `optimizer`).
Summed over the operations inside each `jit_step` program (see
`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "update")
