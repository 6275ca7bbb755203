"""Scheduler: occupied slots over slot-ticks of the launches
(`SlotEngine.stats`: busy_slot_ticks / slot_ticks), in %."""


def read(ctx):
    s = ctx.get("stats")
    if not s or not s["slot_ticks"]:
        return None
    return 100.0 * s["busy_slot_ticks"] / s["slot_ticks"]
