"""Device idle inside a dispatch a tick, where the op that ends the gap is in
`tick_act`, `tick_env` or `tick_append`: the waits between the small ops of
acting, stepping the env and appending, which every tick pays (the idle twin
of `act_tick_device_ms`).  From the second capture of `benchmarks/idle.py`."""

from benchmarks import idle

ACT = {"tick_act", "tick_env", "tick_append"}


def read(ctx):
    s = idle.idle_seconds(ctx, lambda path: bool(path & ACT))
    if s is None:
        return None
    row = idle.device_time(ctx)
    ticks = row["dispatches"] * row["ticks"]
    return 1e3 * s / ticks if ticks else None
