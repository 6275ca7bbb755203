"""Load generator: 95th percentile of how late each frame was submitted
after its due time on the open-loop schedule (host clock), in ms."""
import numpy as np


def read(ctx):
    lag = ctx.get("gen_lag_s")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(lag, 95) * 1e3)
