"""Device: 1 − the union of the device's operation intervals over the
traced window (the profiler's XLA Ops line), in %."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
