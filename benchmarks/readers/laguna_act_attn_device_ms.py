"""Device self time a tick of the five attention layers' attention proper on
the act path (`mha_attn` inside `tick_act`): one slot written into each of a
lane's rings (three of 512 slots, two of 1,024), the rotation of the ring's
keys by their ages, one row of scores a head over the ring, the values and
the gate's product: the rings' read.  None where the program has no such
scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "segments", "tick_act", "mha_attn",
                         every=ctx.driver.ticks) or None
