"""Model step: mean device time of one serving launch's program
(`jit_forward` on the trace's XLA Modules line), in ms."""
from bench import readers


def read(ctx):
    m = readers.module_calls(ctx, "jit_forward")
    return None if m is None else m[1] / m[0] * 1e3
