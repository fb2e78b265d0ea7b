"""Device self time a tick of acting, stepping the env and appending
(`tick_act` + `tick_env` + `tick_append`): every tick pays it, learning or
not."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "segments", scope, every=ctx.driver.ticks)
             for scope in ("tick_act", "tick_env", "tick_append")]
    return None if None in parts else sum(parts)
