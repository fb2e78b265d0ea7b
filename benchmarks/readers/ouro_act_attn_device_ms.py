"""Device self time a tick of attention in the act path (`mha_proj` +
`mha_attn`, which holds `mha_rope`, inside `tick_act`): a tick projects one
step and reads, rotates and rolls 16 windows of 120 keys and values a lane,
0.5 GB for the 16 lanes.  None where the program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "segments", "tick_act", scope,
                           every=ctx.driver.ticks)
             for scope in ("mha_proj", "mha_attn")]
    return None if None in parts or not all(parts) else sum(parts)
