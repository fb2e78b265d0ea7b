"""Device self time a learn step of the expert layers: router and sort
(`moe_route`), gather, grouped products and scatter-add (`moe_experts`), the
shared expert (`moe_shared`), inside `learn_step`.  None where the program has
no such scope."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "steps", "learn_step", scope)
             for scope in ("moe_route", "moe_experts", "moe_shared")]
    return None if None in parts else sum(parts) or None
