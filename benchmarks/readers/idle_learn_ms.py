"""Device idle inside a dispatch a learn step, where the op that ends the gap
is in `tick_learn`: the waits between the ops of the learn step, the draw,
the gather and the write-back, by the scope of the work they hold up.  From a
second capture through the program's own `TraceWindow` (`benchmarks/idle.py`);
with `idle_act_ms` x ticks and `idle_outside_ms` x dispatches it adds to that
capture's window less its busy time.  None on a program whose `device_time`
row has no idle by path."""

from benchmarks import idle


def read(ctx):
    s = idle.idle_seconds(ctx, lambda path: "tick_learn" in path)
    if s is None:
        return None
    steps = idle.device_time(ctx)["steps"]
    return 1e3 * s / steps if steps else None
