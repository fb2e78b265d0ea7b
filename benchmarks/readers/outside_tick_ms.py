"""Device self time a dispatch of the operations in no `tick_*` scope: what
the segment does round its scan (today the two whole-ring relayout copies on
entry and exit), the scan's own `while`, whose self time is the waits
between the operations of a tick, and what the compiler adds at the top of
the scan's body (carry slices and copies)."""

from benchmarks import scopes


def read(ctx):
    attr = scopes.attribution(ctx)
    if attr is None:
        return None
    return 1e3 * attr["outside_tick_s"] / ctx.window["traced"]["segments"]
