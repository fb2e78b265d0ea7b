"""Share of the router's assignments that fell on the experts this chip
holds, in the last learn step the driver saw (the program's own counter, the
mean over its expert layers): 100 x 8/256 = 3.1 if routing is even.  None
where the driver keeps no such counter."""


def read(ctx):
    value = getattr(ctx.driver, "counters", {}).get("moe_held_assign_share")
    return None if value is None else 100.0 * value
