"""Device idle a dispatch that no tick scope explains: gaps inside a dispatch
ended by an op in no `tick_*` scope (the scan's own bookkeeping, what the
compiler put round the tick, an op the module's text does not hold), plus the
idle between two dispatches, which is the host's.  From the second capture of
`benchmarks/idle.py`; the third part of that capture's idle beside
`idle_learn_ms` and `idle_act_ms`."""

from benchmarks import idle


def read(ctx):
    s = idle.idle_seconds(
        ctx, lambda path: not path & set(idle.TICK_SCOPES))
    if s is None:
        return None
    row = idle.device_time(ctx)
    if not row["dispatches"]:
        return None
    return 1e3 * (s + row["idle_between_dispatches_s"]) / row["dispatches"]
