"""Engine: mean host wall time of one `VisionEngine` launch, staging,
device step and readback (`SlotEngine.stats`: wall_us / launches), ms."""


def read(ctx):
    s = ctx.get("stats")
    if not s or not s["launches"]:
        return None
    return s["wall_us"] / s["launches"] / 1e3
