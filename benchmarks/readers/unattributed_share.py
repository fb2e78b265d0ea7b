"""Share of the traced device self time on operations the compiled module's
text does not hold: how far the attribution by scope is broken."""

from benchmarks import scopes


def read(ctx):
    attr = scopes.attribution(ctx)
    if attr is None or not attr["total_s"]:
        return None
    return 100.0 * attr["unresolved_s"] / attr["total_s"]
