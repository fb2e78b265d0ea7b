"""Device self time a learn step of routing alone in the four expert layers
(`moe_route` inside `learn_step`): the softmax over 512 experts, the top-10
and the sort of 76,800 assignment keys a layer, which is what this expert
geometry adds to the two accepted ones'.  None where the program has no such
scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "moe_route") or None
