"""Device self time a tick of acting with the core (`tick_act`: shift_stack,
trunk, one step of every layer of the core, heads)."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "segments", "tick_act", every=ctx.driver.ticks)
