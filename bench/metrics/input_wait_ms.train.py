"""Input pipeline: mean host time to place the next batch on the device
(harness clock around the feed), in ms."""


def read(ctx):
    if not ctx.get("feeds"):
        return None
    return ctx["input_wait_s"] / ctx["feeds"] * 1e3
