"""Model step, host side: mean host time of the window's
``step(state, batch)`` calls (harness clock around each call), in ms.
Where it nears the step's device time, the host sets the rate."""


def read(ctx):
    if not ctx.get("steps") or "dispatch_s" not in ctx:
        return None
    return ctx["dispatch_s"] / ctx["steps"] * 1e3
