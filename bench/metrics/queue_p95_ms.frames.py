"""Scheduler: 95th percentile, over answered frames, of the time from a
frame's submit to the start of the `FrontDoor.step` that served it
(host clock), in ms."""
import numpy as np


def read(ctx):
    q = ctx.get("queue_s")
    if q is None or not len(q):
        return None
    return float(np.percentile(q, 95) * 1e3)
