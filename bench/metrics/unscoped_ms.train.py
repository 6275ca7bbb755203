"""Model parts: device ms per training step of the step's operations that carry none of the program's scopes.
Summed over the operations inside each `jit_step` program (see
`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "unscoped")
