"""Device self time a tick, outside every `tick_*` scope, of all but the
scan's own `while`: in this cell the work on the lanes' 16 K/V windows that
wears no tick scope.  The roll and the reset (`[window; new][:, 1:]` times
`zero_lanes`' keep, 0.5 GB read and 0.5 GB written a tick) stand after
`tick_act` in the tick's body, and the fusions that turn a window's keys by
their slots have several outputs and so lose their `op_name`.
`ouro_act_attn_device_ms` reads what wears `tick_act` alone and excludes all
of this; the two together are what a tick pays for its windows.  The run's
stderr lists the largest (`idle: window_roll`).  None on an untraced window,
and where the program's `obs/device_scopes.py` cannot tell an instruction's
opcode (`instruction_origins`)."""

from benchmarks import idle

TICK = set(idle.TICK_SCOPES)


def read(ctx):
    attr, ds = idle.made(ctx), idle.program()
    if attr is None:
        return None
    idle.say_largest(ctx, "window_roll", lambda path: not path & TICK)
    origins = ds.instruction_origins(idle.module_text(ctx))
    ticks = ctx.window["traced"]["segments"] * ctx.driver.ticks
    return 1e3 * sum(t for inst, t in attr["outside"]
                     if origins[inst].opcode != "while") / ticks
